"""On-chip serving benchmark: one cell (configuration x traffic mix) per run.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

``BENCHMARK.json`` names the cells. Each configuration, traffic mix and
metric is a file of its own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the name the cell gives it; each kind of model
a configuration names is a module under ``bench/kinds``, with its reference
under ``bench/references``.
"""
