"""One reader per metric: ``<name>.py`` defines ``read(run)``, which returns
the metric's value from a finished run (``bench.run.Run``), or None where
the run has nothing to read it from."""
