#!/usr/bin/env python3
"""Run one cell with the serving engine's recorder attached, under the
profiler, and print what ``bench/trace_program.py`` reduces from the trace.

    python3 bench/engine_trace.py --workload <name> --seed <n> --seconds <s>
        [--keep OUT.xplane.pb] [--tiny]

The set-up and the window are those of ``bench/run.py --trace 1`` (the same
weights, warm-up, history, schedule and harness spans), with a
``repro.obs.TraceRecorder`` attached to the engine for the window; no
reference comparison follows. The last line of standard output is one JSON
object: the cell's per-layer metrics as ``bench/run.py --trace 1`` reads
them, the decode split per step (``decode_device_ms_per_step``,
``decode_overhead_ms_per_step``), ``store_payload_ratio``, the clock lag,
``programs`` and ``idle_in_serving``. ``--keep`` copies the trace file out;
``--tiny`` serves the two-layer model of ``bench/configs/tiny.json`` for a
second, which is how ``tests/bench/data/engine_tiny.xplane.pb`` was
recorded on one TPU v5e (then cut down by ``tools/slim_xplane.py``).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(Path(__file__).resolve().parent) in sys.path:
    sys.path.remove(str(Path(__file__).resolve().parent))
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY_MIX = {"name": "tiny", "turns": 2, "first_prompt_tokens": [32, 48],
            "append_tokens": 16, "output_tokens": 4, "gap_mean_s": 0.2,
            "rate_req_per_s": 4.0, "nominal_turn_s": 0.05, "history_s": 0,
            "horizon_s": 2, "schedule_seed": 1,
            "store": {"policy": "lru", "capacity_tokens": 400},
            "sample_requests": 0}


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None, metavar="OUT.xplane.pb")
    ap.add_argument("--tiny", action="store_true")
    return ap.parse_args(argv)


def traced_run(args, *, require_tpu: bool = True) -> dict:
    """One traced window of the cell with the engine's recorder attached,
    at the cell's size, or at the ``tiny`` configuration and ``TINY_MIX``
    under ``--tiny``."""
    from bench.compile_clock import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp

    from bench import cells, configs, kinds, schedule, trace_program
    from bench import trace_reduce
    from bench.driver import drive, serve_history
    from bench.peaks import peaks_for
    from bench.run import (SPANS, Run, _span, _spanned, find_devices,
                           make_store, warm)
    from repro.obs import TraceRecorder
    from repro.serving.realexec import RealExecutionEngine

    cell = cells.resolve(args.workload)
    dev = find_devices(cell.chips, require_tpu)
    peaks = peaks_for(dev["kind"] if require_tpu else "TPU v5 lite")
    if args.tiny:
        conf, mix = configs.load("tiny"), TINY_MIX
    else:
        conf, mix = configs.load(cell.config), schedule.load_mix(cell.traffic)
    dims = conf["dims"]
    cfg = configs.model_config(conf)
    params = jax.block_until_ready(kinds.module(dims["kind"]).program_params(
        args.seed, dims, jnp.dtype(conf["serve"]["dtype"])))
    eng = RealExecutionEngine(cfg, params, make_store(conf, mix, cfg),
                              max_len=conf["serve"]["max_len"],
                              dtype=jnp.dtype(conf["serve"]["dtype"]))
    warm(eng, mix, dims["vocab"])
    sessions = schedule.build(mix, args.seed, dims["vocab"])
    serve_history(eng, sessions)
    rec = eng.recorder = TraceRecorder()
    eng.prefill = _spanned(eng.prefill, "prefill")
    eng._decode = _spanned(eng._decode, "decode_step")
    eng.store.lookup = _spanned(eng.store.lookup, "store_lookup")
    eng.store.insert = _spanned(eng.store.insert, "store_insert")
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s} s", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="engine-trace-")
    jax.profiler.start_trace(trace_dir)
    requests, _, _ = drive(eng, sessions, args.seconds, span=_span)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(trace_dir)
    t_read = time.perf_counter()
    modules, ops, spans = trace_program.read_xplane(path, SPANS)
    if args.keep:
        shutil.copyfile(path, args.keep)
    shutil.rmtree(trace_dir, ignore_errors=True)
    red = trace_program.reduce(modules, ops, spans)
    harness = trace_reduce.reduce(ops, [s[:3] for s in spans
                                        if s[2] in SPANS], dev["count"])
    print(f"trace: {len(spans)} host spans, "
          f"{sum(map(len, modules.values()))} modules, read and reduced in "
          f"{time.perf_counter() - t_read} s", flush=True)
    run = Run(requests=requests, seconds=args.seconds, dims=dims,
              peaks=peaks, setup_s=setup_s, trace=harness)
    split = red["decode"] or {}
    return {
        "workload": args.workload, "seed": args.seed,
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"]},
        "requests": len(requests), "rows": rec.n,
        "metrics": {k: v["value"] for k, v in
                    cells.read_metrics(cell, run, True).items()},
        "decode_device_ms_per_step": split.get("device_ms_per_step"),
        "decode_overhead_ms_per_step": split.get("overhead_ms_per_step"),
        "store_payload_ratio": trace_program.store_payload_ratio(rec.events),
        "decode": split,
        "programs": red["programs"],
        "idle_in_serving": red["idle_in_serving"],
        "serving_s": red["serving_s"], "window_s": red["window_s"],
    }


def main(argv=None) -> int:
    from bench.run import NoChip
    args = parse(argv)
    try:
        out = traced_run(args)
    except NoChip as e:
        print(f"bench/engine_trace.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
