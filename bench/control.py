#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload <name> --seconds <s> --seeds 1 2 3 \\
        [--control-seeds 1 2 3]

For each seed, one run of the cell as ``bench/run.py`` makes it (a window
of ``--seconds``). On a plain seed the run's own comparison gives the lower
reading: the widest logit gap of what the program served. On a control
seed the run's comparison judges, in place of the served tokens, the
tokens that the reference computed in fp8 puts first at the same positions
(``correct.control_tokens``): its ``correct`` and ``checks`` are the
control's, and must come out false. The program's own gap on the same
requests is read beside it. Prints one JSON line per seed. The benchmark's
own runs never run the control.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    a = ap.parse_args(argv)
    from bench import run as bench_run
    from bench.correct import control_tokens, served_gap
    for seed in a.seeds:
        args = bench_run.parse(["--workload", a.workload, "--seed",
                                str(seed), "--seconds", str(a.seconds),
                                "--trace", "0"])
        control = seed in a.control_seeds
        result, checks, made = bench_run.run_cell(
            args, judged=control_tokens if control else None)
        line = {"seed": seed, "attempted": result["attempted"],
                "metrics": result["metrics"],
                "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        if control:
            t = time.perf_counter()
            line["program_gap"], _ = served_gap(
                made["conf"], seed, made["sample"], made["prompt_of"],
                pad_to=made["conf"]["serve"]["max_len"])
            line["program_gap_s"] = time.perf_counter() - t
            line["control"] = {"correct": result["correct"],
                               "checks": checks}
        else:
            line["program_gap"] = checks["logit_gap"]["value"]
            line["correct"] = result["correct"]
        print("CONTROL " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
