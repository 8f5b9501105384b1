#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: turn on the persistent compile cache, find the chip (a run that
finds no TPU, or fewer chips than the cell asks for, fails and prints no
result), build the cell's configuration and its weights from the seed, warm
every prefill shape the traffic can produce, serve the turns that sessions
in mid-conversation had before the window, then drive the open-loop
schedule through ``RealExecutionEngine.generate`` for ``--seconds``. Set-up
is everything up to the window's opening. Once the window has closed and
the program's state is freed, a sample of what it served is compared with
the plain float32 reference. The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
``--trace 0``, per-layer with ``--trace 1``), ``device``, with ``--trace 1``
a ``breakdown``, and last ``checks``: each number compared, with its limit.
The same checks are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(Path(__file__).resolve().parent) in sys.path:
    sys.path.remove(str(Path(__file__).resolve().parent))
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

SPANS = ("generate", "arrival_wait", "prefill", "decode_step",
         "store_lookup", "store_insert")


class NoChip(RuntimeError):
    pass


@dataclass
class Run:
    """What the metric readers see of a finished run."""
    requests: list
    seconds: float
    dims: dict
    peaks: dict
    setup_s: float
    trace: Optional[dict]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_devices(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform {info['platform']} kind {info['kind']!r} "
          f"count {info['count']}", flush=True)
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {info['platform']!r}")
    if info["count"] < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found "
                     f"{info['count']}")
    return info


def _spanned(fn, name):
    import jax

    def inner(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return inner


def _span(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def make_store(conf, mix, cfg):
    from repro.core.kvstore import KVStore
    from repro.core.policies import POLICIES
    kvb = cfg.kv_bytes_per_token
    st = mix["store"]
    return KVStore(st["capacity_tokens"] * kvb, POLICIES[st["policy"]], kvb)


def warm(eng, mix: dict, vocab: int) -> int:
    """Serve, through ``generate``, one request of every prefill shape the
    mix can produce (``schedule.warm_shapes``), so that every program and
    every host-side operation of the path is compiled before the window.
    Uses a store of its own, dropped afterwards. Returns the shapes warmed."""
    import numpy as np
    from repro.core.kvstore import KVStore
    from repro.core.policies import POLICIES
    from bench.schedule import prompt_lengths, warm_shapes
    served = eng.store
    eng.store = KVStore(float("inf"), POLICIES["lru"],
                        eng.cfg.kv_bytes_per_token)
    rng = np.random.default_rng(0)
    shapes = set()
    for first in mix["first_prompt_tokens"]:
        lens = prompt_lengths(mix, first)
        toks = rng.integers(0, vocab, lens[-1]).tolist()
        for k, n in enumerate(lens):
            g = eng.generate(f"warm{first}", toks[:n], num_new=1, now=0.0)
            shapes.add((g.prefill_tokens_computed, g.reused_tokens))
            if k:
                g = eng.generate("warm-miss", toks[:n], num_new=1, now=0.0)
                shapes.add((g.prefill_tokens_computed, g.reused_tokens))
                eng.store.pop_entry("warm-miss")
    eng.store = served
    assert shapes == set(warm_shapes(mix)), (shapes, warm_shapes(mix))
    return len(shapes)


def run_cell(args, *, require_tpu: bool = True, conf: dict = None,
             mix: dict = None, limits: dict = None, fault=None,
             judged=None):
    """One run of a cell. The keyword arguments let a test run it at a
    reduced size (``conf``, ``mix``, ``limits``) and break the timed path
    (``fault(engine)`` after set-up), and let the control put its tokens
    in place of the served ones in the comparison (``judged(conf, seed,
    sample, prompt_of, pad_to)`` returns them, as
    ``correct.control_tokens`` does). Returns the result, the checks, and
    what the comparison was made on (``conf``, ``sample``, ``prompt_of``)."""
    from bench.compile_clock import CompileClock, use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import cells, configs, kinds, schedule, trace_reduce
    from bench.correct import draw_sample, served_gap, verdict
    from bench.driver import drive, serve_history
    from bench.peaks import peaks_for
    from repro.serving.realexec import RealExecutionEngine

    cell = cells.resolve(args.workload)
    dev = find_devices(cell.chips, require_tpu)
    peaks = peaks_for(dev["kind"]) if require_tpu else \
        peaks_for("TPU v5 lite")
    conf = conf or configs.load(cell.config)
    mix = mix or schedule.load_mix(cell.traffic)
    limits = limits or cell.limits
    dims = conf["dims"]
    cfg = configs.model_config(conf)
    max_len = conf["serve"]["max_len"]
    dtype = jnp.dtype(conf["serve"]["dtype"])
    print(f"compile cache: {cache_dir}")
    print(f"cell {cell.name}: config {cell.config} {dims} max_len {max_len}"
          f" {dtype}; traffic {cell.traffic} {mix}", flush=True)

    phases = {"start": time.perf_counter() - T_START}
    with CompileClock() as setup_clock:
        params = jax.block_until_ready(
            kinds.module(dims["kind"]).program_params(args.seed, dims, dtype))
        phases["weights"] = time.perf_counter() - T_START
        eng = RealExecutionEngine(cfg, params, make_store(conf, mix, cfg),
                                  max_len=max_len, dtype=dtype)
        n_shapes = warm(eng, mix, dims["vocab"])
        phases["warm"] = time.perf_counter() - T_START
        sessions = schedule.build(mix, args.seed, dims["vocab"])
        n_hist = serve_history(eng, sessions)
        phases["history"] = time.perf_counter() - T_START
    if fault is not None:
        fault(eng)
    st0 = eng.store.stats
    hist_stats = (st0.lookups, st0.hits, st0.evictions)
    span = _span if args.trace else None
    if args.trace:
        eng.prefill = _spanned(eng.prefill, "prefill")
        eng._decode = _spanned(eng._decode, "decode_step")
        eng.store.lookup = _spanned(eng.store.lookup, "store_lookup")
        eng.store.insert = _spanned(eng.store.insert, "store_insert")
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    setup_s = time.perf_counter() - T_START
    print(f"set-up: {setup_s} s; {setup_clock.programs} programs compiled "
          f"or loaded in {setup_clock.seconds} s ({setup_clock.cache_hits} "
          f"from the persistent cache); {n_shapes} prefill shapes warmed; "
          f"{n_hist} history turns served; {len(sessions)} sessions; "
          f"seconds from process start at the end of each phase {phases}",
          flush=True)

    with CompileClock() as window_clock:
        if args.trace:
            jax.profiler.start_trace(trace_dir)
        requests, _, (late_max, late_sum) = drive(
            eng, sessions, args.seconds, **({"span": span} if span else {}))
        if args.trace:
            jax.profiler.stop_trace()
    st = eng.store.stats
    mem = jax.devices()[0].memory_stats() or {}
    peak_bytes = mem.get("peak_bytes_in_use")
    print(f"window: {len(requests)} requests due, "
          f"{sum(r.failed for r in requests)} failed; generator late by at "
          f"most {late_max} s ({late_sum} s in all); store lookups "
          f"{st.lookups - hist_stats[0]} hits {st.hits - hist_stats[1]} "
          f"evictions {st.evictions - hist_stats[2]}; programs compiled in "
          f"the window {window_clock.programs}; peak_bytes_in_use "
          f"{peak_bytes} of {mem.get('bytes_limit')}", flush=True)

    trace = None
    if args.trace:
        ops, spans = trace_reduce.read_xplane(
            trace_reduce.find_xplane(trace_dir), SPANS)
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace = trace_reduce.reduce(ops, spans, dev["count"])
        print(f"trace: busy {trace['busy_s']} s of {trace['window_s']} s; "
              f"serving {trace['serving_s']} s, busy in it "
              f"{trace['busy_in_serving_s']} s", flush=True)

    # free the program's state before the reference runs
    eng.store = None
    del eng, params
    gc.collect()

    by_sid = {s.sid: s for s in sessions}
    prompt_of = lambda r: by_sid[r.sid].prompt(r.turn)  # noqa: E731
    sample = draw_sample(requests, args.seed, mix["sample_requests"])
    t_ref = time.perf_counter()
    chosen = judged(conf, args.seed, sample, prompt_of, max_len) \
        if judged is not None and sample else None
    gap, n_tok = served_gap(conf, args.seed, sample, prompt_of,
                            pad_to=max_len, chosen=chosen) \
        if sample else (math.inf, 0)
    print(f"reference: {len(sample)} requests ({sum(r.reused > 0 for r in sample)}"
          f" hits), {n_tok} {'control' if chosen else 'served'} tokens "
          f"compared in {time.perf_counter() - t_ref} s", flush=True)
    checks, correct = verdict(gap, n_tok, window_clock.programs, limits)

    run = Run(requests=requests, seconds=args.seconds, dims=dims,
              peaks=peaks, setup_s=setup_s, trace=trace)
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": peak_bytes}
    if trace:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    result = {"correct": correct, "attempted": len(requests),
              "failed": sum(r.failed for r in requests),
              "metrics": cells.read_metrics(cell, run, bool(args.trace)),
              "device": device}
    if trace:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["checks"] = checks
    return result, checks, {"conf": conf, "sample": sample,
                            "prompt_of": prompt_of}


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result, checks, _ = run_cell(args)
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
