#!/usr/bin/env python3
"""Finds a cell's knee on the chip: the highest offered rate the server
sustains. One process builds the cell once, then for each rate, lowest
first, and each seed serves the cell's mix (its history first, on an empty
store) for ``--seconds``.

    python3 bench/sweep.py --workload <name> --seeds 1 2 3 --seconds 51 \\
        --rates 1.0 1.5 2.0 2.5

For each rate and seed it prints one JSON line: requests due and served,
the 90th percentile and the mean time to first token over the first and
the last third of the window, the backlog at the close (requests due
before it and not yet started) and the mean service time. A rate is
sustained where, on every seed, the backlog at the close is at most
``--max-backlog`` and the last third waits less than ``--max-wait-s`` on
average. The knee is the highest swept rate that is sustained with every
lower swept rate: the sweep stops at the first rate that is not, and the
last line (``KNEE``) gives the knee and 0.8 of it. The cells' rates are
fixed from this once: a cell below the knee runs at 0.8 of it.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--max-backlog", type=int, default=3)
    ap.add_argument("--max-wait-s", type=float, default=1.0)
    a = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from bench import cells, configs, kinds, schedule
    from bench.compile_clock import use_compile_cache
    from bench.driver import drive, serve_history
    from bench.quantile import percentile
    from bench.run import find_devices, make_store, warm
    from repro.serving.realexec import RealExecutionEngine
    use_compile_cache()
    cell = cells.resolve(a.workload)
    find_devices(cell.chips)
    conf = configs.load(cell.config)
    base = schedule.load_mix(cell.traffic)
    cfg = configs.model_config(conf)
    dtype = jnp.dtype(conf["serve"]["dtype"])
    params = jax.block_until_ready(kinds.module(
        conf["dims"]["kind"]).program_params(a.seeds[0], conf["dims"], dtype))
    eng = RealExecutionEngine(cfg, params, make_store(conf, base, cfg),
                              max_len=conf["serve"]["max_len"], dtype=dtype)
    warm(eng, base, conf["dims"]["vocab"])
    knee = None
    for rate in sorted(a.rates):
        sustained = True
        for seed in a.seeds:
            mix = dict(base, rate_req_per_s=rate)
            eng.store = make_store(conf, mix, cfg)
            sessions = schedule.build(mix, seed, conf["dims"]["vocab"])
            serve_history(eng, sessions)
            reqs, _, _ = drive(eng, sessions, a.seconds)
            third = a.seconds / 3
            first = [r.ttft_s for r in reqs if r.due < third]
            last = [r.ttft_s for r in reqs if r.due >= 2 * third]
            line = {
                "rate": rate, "seed": seed, "due": len(reqs),
                "failed": sum(r.failed for r in reqs),
                "ttft_p90_s": percentile([r.ttft_s for r in reqs], 90)
                if reqs else None,
                "ttft_mean_first_third": sum(first) / max(len(first), 1),
                "ttft_mean_last_third": sum(last) / max(len(last), 1),
                "backlog_at_close": sum(r.t_call > a.seconds for r in reqs),
                "service_s_mean": sum(r.t_end - r.t_call for r in reqs)
                / max(len(reqs), 1),
                "reuse": sum(r.reused for r in reqs)
                / max(sum(r.prompt_len for r in reqs), 1)}
            line["sustained"] = (
                line["backlog_at_close"] <= a.max_backlog
                and line["ttft_mean_last_third"] < a.max_wait_s)
            print("SWEEP " + json.dumps(line), flush=True)
            sustained = sustained and line["sustained"]
        if not sustained:
            break
        knee = rate
    print("KNEE " + json.dumps({
        "workload": a.workload, "knee": knee,
        "rate_at_0.8": None if knee is None else round(0.8 * knee, 3)}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
