"""Share of serving time in which no operation ran on the device: one
minus the union of device-op intervals over the union of ``generate``
spans, both from the profiler trace of a ``--trace 1`` run."""


def read(run):
    t = run.trace
    if t is None or not t["chips_traced"] or not t["serving_s"]:
        return None
    return 100.0 * (1.0 - t["busy_in_serving_s"] / t["serving_s"])
