"""Serving driver: runs the GreenCache 24-hour evaluation (simulation mode)
or the real-execution demo (actual JAX model with KV-prefix reuse).

    # paper evaluation slice (Fig 12-14 style):
    PYTHONPATH=src python -m repro.launch.serve --model llama3-70b \
        --task conversation --grid FR --mode greencache

    # resource plans: pin one, or give several for hourly co-decision
    PYTHONPATH=src python -m repro.launch.serve \
        --plan "cache=auto fleet=a100:2,l40:4"
    PYTHONPATH=src python -m repro.launch.serve \
        --plan "cache=auto fleet=h100:2" "cache=auto fleet=a100:3"

    # prefill/decode disaggregation: the solver searches the cross
    # product (cache, prefill fleet, decode fleet)
    PYTHONPATH=src python -m repro.launch.serve \
        --prefill-fleet h100:1 h100:2 --decode-fleet a100:2 a100:3

    # real execution with a reduced model:
    PYTHONPATH=src python -m repro.launch.serve --real --arch yi-6b

The pre-plan ``--replicas``/``--fleet`` flags remain as deprecated shims
that build the equivalent ``--plan`` candidates.
"""
from __future__ import annotations

import argparse
import os
import time
import warnings
from dataclasses import dataclass
from pathlib import Path

import numpy as np


def build_plans(args) -> list:
    """Normalize every fleet-shaped CLI flag into the candidate
    ``ResourcePlan`` list — the single place the legacy ``--replicas``
    int-vs-list and ``--fleet`` spellings are resolved."""
    from repro.core.plan import UNSET_EPS, ResourcePlan, normalize_replicas

    # None = flag not given (plan strings / defaults win); negative =
    # explicit disable (pure affinity)
    eps_given = args.balance_eps is not None
    eps = UNSET_EPS if not eps_given \
        else (None if args.balance_eps < 0 else args.balance_eps)
    if args.plan:
        if args.fleet or args.replicas is not None \
                or args.prefill_fleet or args.decode_fleet:
            raise SystemExit("--plan replaces --fleet/--replicas/"
                             "--prefill-fleet/--decode-fleet; pass one "
                             "spelling")
        plans = [ResourcePlan.parse(p) for p in args.plan]
        if eps_given:
            # an explicit --balance-eps overrides the plan strings' eps
            # (the controller applies the same precedence)
            from dataclasses import replace
            plans = [replace(p, pools=tuple(
                pool if pool.role == "decode"
                else replace(pool, balance_eps=eps)
                for pool in p.pools)) for p in plans]
        return plans
    if args.prefill_fleet:
        if not args.decode_fleet:
            raise SystemExit("--prefill-fleet needs --decode-fleet")
        return [ResourcePlan.disaggregated(None, prefill=pf, decode=df,
                                           router=args.router,
                                           balance_eps=eps)
                for pf in args.prefill_fleet for df in args.decode_fleet]
    if args.decode_fleet:
        raise SystemExit("--decode-fleet needs --prefill-fleet")
    if args.fleet:
        warnings.warn("--fleet is deprecated; use --plan "
                      "'cache=auto fleet=...'", DeprecationWarning,
                      stacklevel=2)
        return [ResourcePlan.single(None, fleet=f, router=args.router,
                                    balance_eps=eps)
                for f in args.fleet]
    counts = normalize_replicas(args.replicas)
    if args.replicas is not None:
        warnings.warn("--replicas is deprecated; use --plan "
                      "'cache=auto fleet=l40:N'", DeprecationWarning,
                      stacklevel=2)
    return [ResourcePlan.single(None, n_replicas=k, router=args.router,
                                balance_eps=eps)
            for k in counts]


def build_transitions(args):
    """Construct the ``TransitionConfig`` from the CLI knobs: any of
    ``--transitions``/``--boot-latency``/``--rebalance``/``--min-dwell``
    enables the transition model (None = legacy instant switching)."""
    from repro.core.plan import TransitionConfig
    enabled = (args.transitions or args.boot_latency is not None
               or args.rebalance is not None or args.min_dwell > 1)
    if not enabled:
        return None
    kw = {}
    if args.boot_latency is not None:
        kw["boot_latency_s"] = args.boot_latency
    if args.rebalance is not None:
        kw["rebalance"] = args.rebalance
    return TransitionConfig(**kw)


def run_simulation(args):
    from repro.core.carbon import CarbonModel
    from repro.core.controller import GreenCacheController
    from repro.core.profiler import run_profiler
    from repro.serving.perfmodel import SERVING_MODELS
    from repro.workloads.agents import AgentLoopWorkload
    from repro.workloads.conversations import ConversationWorkload
    from repro.workloads.documents import DocumentWorkload
    from repro.workloads.traces import azure_rate_trace, ci_trace

    model = SERVING_MODELS[args.model]
    carbon = CarbonModel()
    plans = build_plans(args)
    transitions = build_transitions(args)
    # the day's load scales with the arrival-carrying (prefill) capacity:
    # a disaggregated plan's decode pool adds token throughput, not
    # request admission (for fused plans prefill == the whole fleet)
    scale = max(p.prefill.capacity for p in plans)
    prefix = args.prefix_caching or args.task == "agent"
    if args.task == "conversation":
        wf = lambda s: ConversationWorkload(seed=s, load_scale=scale,
                                            prefix=prefix)
        policy = "lcs_chat"
    elif args.task == "agent":
        # branching agent loops are always structured-prefix (the
        # whole-context key is derived from the blocks)
        wf = lambda s: AgentLoopWorkload(seed=s, load_scale=scale)
        policy = "lcs_chat"
    else:
        wf = lambda s: DocumentWorkload(seed=s, zipf_alpha=args.zipf,
                                        load_scale=scale, prefix=prefix)
        policy = "lcs_doc"
    sizes = [0, 1, 2, 4, 8, 12, 16] if model.max_cache_tb >= 16 else \
        [0, 1, 2, 4, 6, 8]
    rates = [0.2, 0.6, 1.0, 1.3, 1.6] if args.model == "llama3-70b" else \
        [0.5, 2.0, 4.0, 6.0, 8.0]
    print("profiling ...")
    prof = run_profiler(model, args.task, lambda s: wf(s), carbon,
                        rates=rates, sizes_tb=sizes,
                        warmup_prompts=args.warmup,
                        prefix_aware=prefix)
    rate_trace = azure_rate_trace(rates[-1] * scale, seed=3)
    cis = ci_trace(args.grid, seed=4)
    # --balance-eps is fully resolved into the candidate plans by
    # build_plans (the controller adopts the plans' pool value)
    admission = None
    if args.admission == "write_aware":
        from repro.core.storage import (DEFAULT_DEVICE, StorageSpec,
                                        write_aware_admission)
        dev = StorageSpec.parse(args.storage[0]).cold.device \
            if args.storage else DEFAULT_DEVICE
        admission = write_aware_admission(model, carbon, dev)
    ctl = GreenCacheController(model, prof, carbon, args.task,
                               mode=args.mode, policy=policy,
                               warm_requests=args.warmup,
                               plans=plans, router=args.router,
                               max_requests_per_hour=int(1200 * scale),
                               transitions=transitions,
                               min_dwell_hours=args.min_dwell,
                               storage=args.storage,
                               wear_aware=not args.calendar_lifetime,
                               admission=admission,
                               prefix_caching=prefix,
                               solver_prune=not args.no_solver_prune,
                               beam_width=args.beam_width,
                               trace=bool(args.trace),
                               metrics=bool(args.trace or args.metrics))
    res = ctl.run_day(wf, rate_trace, cis)
    write_observability(args, ctl, res)
    many = len(plans) > 1
    clustered = scale > 1 or plans[0].n_replicas > 1
    print(f"mode={args.mode} grid={args.grid} task={args.task}")
    print(f"  carbon/request: {res.carbon_per_request_g:.4f} g")
    print(f"  SLO attainment: {res.slo_attainment:.3f}")
    print(f"  avg cache size: {res.avg_cache_tb:.1f} TB")
    print(f"  hourly sizes:   {[int(h.cache_tb) for h in res.hours]}")
    if args.storage:
        print(f"  hourly tiers:   "
              f"{[h.plan.split()[0][len('cache='):] for h in res.hours]}")
        print(f"  cache churn:    "
              f"{sum(h.written_gb for h in res.hours):.0f} GB written")
    if many or clustered:
        print(f"  avg fleet cap:  {res.avg_fleet_capacity:.2f} "
              f"(reference-server units)")
        print(f"  hourly plans:   {[h.plan for h in res.hours]}")
    if transitions is not None:
        print(f"  plan changes:   {res.plan_changes} "
              f"(transition carbon {res.total_transition_g:.1f} g)")
    return res


def write_trace(rec, path: str) -> str:
    """Write ``rec`` as JSONL to ``path`` and as a Chrome ``trace_event``
    file beside it (``out.jsonl`` -> ``out.trace.json``); returns the
    latter's path."""
    rec.write_jsonl(path)
    chrome = path
    for suf in (".jsonl", ".json"):
        if chrome.endswith(suf):
            chrome = chrome[:-len(suf)]
            break
    chrome += ".trace.json"
    rec.write_chrome(chrome)
    return chrome


def write_observability(args, ctl, res):
    """Flight-recorder exports after a simulated day: the JSONL span
    trace plus its Chrome ``trace_event`` twin (``--trace out.jsonl`` →
    ``out.jsonl`` + ``out.trace.json``), the Prometheus text exposition
    (``--metrics out.prom``), and the final hour's solver candidate
    table (``--explain``)."""
    if args.trace:
        chrome = write_trace(ctl.trace, args.trace)
        s = ctl.trace.summary()
        print(f"  trace:          {ctl.trace.n} spans, "
              f"{len(ctl.trace.events)} events -> {args.trace} "
              f"(+ {chrome}); render with tools/trace_report.py")
        print(f"  traced p99 TTFT {s['ttft']['p99']:.3f}s  "
              f"p99 TPOT {s['tpot']['p99'] * 1000:.1f}ms")
    if args.metrics:
        with open(args.metrics, "w") as f:
            f.write(ctl.metrics.expose_text())
        print(f"  metrics:        -> {args.metrics}")
    if args.explain and ctl.last_solve is not None:
        print("\nfinal solve, surviving candidates per hour "
              "(SolveResult.explain):")
        print(ctl.last_solve.explain(hours=range(3)))
    if res.ledger is not None:
        by_cat = res.ledger.by("category")
        cuts = "  ".join(f"{k}={v:.1f}g" for k, v in by_cat.items())
        print(f"  carbon ledger:  audited, {cuts}")


CHECKOUT = Path(__file__).resolve().parents[3]

# JAX monitoring events that make up a compile: trace, lower, and the
# backend compile, which fires once per program whether it is compiled or
# read back from the persistent cache
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. ``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it
    itself and nothing is set here. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``, so that a later run finds it again."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


SESSIONS = ("s0", "s1")


@dataclass(frozen=True)
class SessionTraffic:
    """Two two-turn conversations (``SESSIONS``), then one unshared control
    request. Turn 2 is turn 1's prompt, its output and ``followup_len`` new
    user tokens, so it reuses turn 1's ``prompt_len`` cached tokens; the
    control request must miss."""
    prompt_len: int
    followup_len: int
    control_len: int
    num_new: int            # tokens generated per request

    def store_bytes(self, cfg) -> int:
        """Store capacity that holds every prompt of the traffic at once:
        each session's turn-2 prompt (which extends its turn-1 entry) and
        the control prompt, at the store's bytes per token for ``cfg``."""
        held = (len(SESSIONS) * (self.prompt_len + self.num_new
                                 + self.followup_len) + self.control_len)
        return held * max(cfg.kv_bytes_per_token, 1)


@dataclass
class RealRun:
    engine: "RealExecutionEngine"
    prompts: dict               # request name -> prompt tokens
    warmup: dict                # request name -> GenerationResult
    results: dict               # the measured pass, same names
    setup_s: float              # wall time of init and the warm-up pass
    setup: "CompileClock"       # compiles during init and the warm-up
    window: "CompileClock"      # compiles during the measured pass


class CompileClock:
    """Counts programs compiled (or read back from the persistent cache)
    and sums their trace + lower + compile seconds, from JAX's own
    monitoring events, while in a ``with`` block."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event, **_):
        self.cache_hits += event == _CACHE_HIT_EVENT

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def _serve(eng, traffic: SessionTraffic, seed: int):
    """Serve ``traffic`` on ``eng``; prompts are drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    draw = lambda n: [int(t) for t in
                      rng.integers(0, eng.cfg.vocab_size, size=n)]
    turn1 = {s: draw(traffic.prompt_len) for s in SESSIONS}
    followup = {s: draw(traffic.followup_len) for s in SESSIONS}
    prompts = {f"{s}/turn1": turn1[s] for s in SESSIONS}
    out = {n: eng.generate(n.split("/")[0], p, num_new=traffic.num_new)
           for n, p in prompts.items()}
    for s in SESSIONS:
        p = turn1[s] + out[f"{s}/turn1"].tokens + followup[s]
        prompts[f"{s}/turn2"] = p
        out[f"{s}/turn2"] = eng.generate(s, p, num_new=traffic.num_new)
    prompts["control"] = draw(traffic.control_len)
    out["control"] = eng.generate("control", prompts["control"],
                                  num_new=traffic.num_new)
    return prompts, out


def run_real(cfg, traffic: SessionTraffic, *, dtype, max_len: int,
             store_bytes: float, seed: int = 0, recorder=None) -> RealRun:
    """Serve ``traffic`` with a random-weight ``cfg`` behind a GreenCache
    ``KVStore`` of ``store_bytes``, through ``RealExecutionEngine``.

    The traffic runs twice on the same engine, each time on an empty
    store: a warm-up pass that compiles every shape, then the measured
    pass, whose results are returned. The same seed gives both passes the
    same prompts, so the measured pass should compile nothing. A
    ``recorder`` (``repro.obs.TraceRecorder``) is attached to the engine
    for the measured pass."""
    import jax
    from repro.core.kvstore import KVStore
    from repro.core.policies import POLICIES
    from repro.models.transformer import init_params
    from repro.serving.realexec import RealExecutionEngine

    def store():
        return KVStore(store_bytes, POLICIES["lcs"],
                       max(cfg.kv_bytes_per_token, 1))

    t0 = time.perf_counter()
    with CompileClock() as setup:
        params = jax.block_until_ready(
            init_params(jax.random.PRNGKey(seed), cfg, dtype))
        eng = RealExecutionEngine(cfg, params, store(), max_len=max_len,
                                  dtype=dtype)
        _, warmup = _serve(eng, traffic, seed)
    setup_s = time.perf_counter() - t0
    eng.store = store()
    eng.recorder = recorder
    with CompileClock() as window:
        prompts, results = _serve(eng, traffic, seed)
    for name, r in results.items():
        print(f"{name}: reused {r.reused_tokens} computed "
              f"{r.prefill_tokens_computed} prefill_s {r.prefill_time_s} "
              f"decode_s {r.decode_time_s} -> {r.tokens[:8]}")
    return RealRun(eng, prompts, warmup, results, setup_s, setup, window)


def compare_hit_prefill(eng, prompt, prefix_len: int) -> dict:
    """Prefill ``prompt`` the way a cache hit does (its first
    ``prefix_len`` tokens, then the rest after their cached KV) and in one
    full pass, through ``eng``'s own programs; compare the logits of the
    suffix positions. Returns the relative L2 error, the largest absolute
    difference, the share of positions whose argmax agrees, and whether
    both are finite."""
    import jax.numpy as jnp

    toks = jnp.asarray(prompt, jnp.int32)[None]
    _, prefix_cache = eng.prefill(toks[:, :prefix_len])
    hit, _ = eng.prefill(toks[:, prefix_len:], prefix_cache, prefix_len)
    full, _ = eng.prefill(toks)
    V = eng.cfg.vocab_size
    hit = hit[0, :, :V].astype(jnp.float32)
    ref = full[0, prefix_len:, :V].astype(jnp.float32)
    return {
        "rel_l2": float(jnp.linalg.norm(hit - ref) / jnp.linalg.norm(ref)),
        "max_abs": float(jnp.max(jnp.abs(hit - ref))),
        "argmax_agree": float(jnp.mean(jnp.argmax(hit, -1)
                                       == jnp.argmax(ref, -1))),
        "finite": bool(jnp.isfinite(hit).all() & jnp.isfinite(ref).all()),
    }


def run_real_demo(args):
    """``--real``: a reduced model on the CPU; turn 2 must hit. With
    ``--trace OUT.jsonl`` the engine's recorder is attached to the measured
    pass and exported as for a simulated day."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.obs import TraceRecorder

    use_compile_cache()
    cfg = get_config(args.arch).reduced(num_layers=2, d_model=128)
    traffic = SessionTraffic(prompt_len=24, followup_len=8, control_len=12,
                             num_new=4)
    rec = TraceRecorder() if args.trace else None
    run = run_real(cfg, traffic, dtype=jnp.float32, max_len=128,
                   store_bytes=traffic.store_bytes(cfg), recorder=rec)
    if rec is not None:
        chrome = write_trace(rec, args.trace)
        print(f"engine trace: {rec.n} requests, {len(rec.events)} events "
              f"-> {args.trace} (+ {chrome})")
    r2 = run.results["s0/turn2"]
    if r2.reused_tokens != traffic.prompt_len:
        raise SystemExit(f"expected turn 2 to reuse {traffic.prompt_len} "
                         f"cached tokens, it reused {r2.reused_tokens}")
    print("cache hit verified: suffix-only prefill")
    return run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="llama3-70b",
                    choices=["llama3-70b", "llama3-8b"])
    ap.add_argument("--task", default="conversation",
                    choices=["conversation", "document", "agent"])
    ap.add_argument("--prefix-caching", action="store_true",
                    help="radix prefix-tree KV sharing: workloads emit "
                         "structured prefix segments (system prompt x "
                         "document x turn history), partial hits shorten "
                         "prefill proportionally, and the store/profiler/"
                         "controller run the RadixKVStore (--task agent "
                         "implies this)")
    ap.add_argument("--zipf", type=float, default=0.4)
    ap.add_argument("--grid", default="FR")
    ap.add_argument("--mode", default="greencache",
                    choices=["greencache", "full", "none", "oracle"])
    ap.add_argument("--warmup", type=int, default=12000)
    ap.add_argument("--plan", nargs="+", default=None,
                    help="resource plan spec(s) like 'cache=auto "
                         "fleet=a100:2,l40:4' or 'cache=4tb prefill=h100:2"
                         " decode=a100:3'; several specs let the solver "
                         "co-decide the plan hourly")
    ap.add_argument("--prefill-fleet", nargs="+", default=None,
                    help="disaggregation: prefill-pool fleet spec(s); "
                         "crossed with --decode-fleet into candidate "
                         "plans")
    ap.add_argument("--decode-fleet", nargs="+", default=None,
                    help="disaggregation: decode-pool fleet spec(s)")
    ap.add_argument("--replicas", type=int, nargs="+", default=None,
                    help="DEPRECATED (use --plan): prefill replica count; "
                         "several values let the solver co-decide "
                         "(cache_tb, n_replicas) hourly")
    ap.add_argument("--fleet", nargs="+", default=None,
                    help="DEPRECATED (use --plan): heterogeneous fleet "
                         "mix spec(s) like 'a100:2,l40:4'")
    ap.add_argument("--balance-eps", type=float, default=None,
                    help="bounded-load spill factor of the cache_affinity "
                         "router (default 0.15, or the plan string's eps);"
                         " negative disables spill (pure affinity: best "
                         "hit rate, worst p90 TTFT under skew)")
    ap.add_argument("--router", default=None,
                    choices=[None, "single", "round_robin", "least_loaded",
                             "cache_affinity"],
                    help="cluster router (default: single for 1 replica, "
                         "cache_affinity otherwise)")
    ap.add_argument("--transitions", action="store_true",
                    help="model plan transitions as first-class events "
                         "(per-type boot latency, drain accounting, KV "
                         "rebalancing, switching-cost-aware solver) "
                         "instead of free instant reconfiguration")
    ap.add_argument("--boot-latency", type=float, default=None,
                    help="replica warmup seconds before a booted replica "
                         "serves (default: per-ReplicaType boot_s; "
                         "implies --transitions)")
    ap.add_argument("--rebalance", default=None,
                    choices=["migrate", "cold"],
                    help="partitioned-store ring resize policy: bulk KV "
                         "migration or cold-start misses on reassigned "
                         "keys (implies --transitions)")
    ap.add_argument("--min-dwell", type=int, default=1,
                    help="minimum hours a plan shape must dwell before "
                         "the solver may switch it again (>1 implies "
                         "--transitions)")
    ap.add_argument("--beam-width", type=int, default=None,
                    help="approximate planning: keep only the K cheapest "
                         "options per (hour, switch class) in the DP; the "
                         "result reports an optimality bound "
                         "(SolveResult.beam_bound_g). Default: exact")
    ap.add_argument("--no-solver-prune", action="store_true",
                    help="disable the lossless Pareto dominance pruning "
                         "in the planning DP (debugging knob; results "
                         "are bit-identical either way)")
    ap.add_argument("--storage", nargs="+", default=None,
                    help="typed cache tier spec(s) like 'nvme_gen4:8tb' "
                         "or 'dram:0.5tb+nvme_gen4:4tb'; several specs "
                         "let the solver size the tiers hourly (wear-"
                         "aware by default). Default: the legacy flat-"
                         "SSD size grid")
    ap.add_argument("--calendar-lifetime", action="store_true",
                    help="disable the wear clock: storage embodied "
                         "carbon amortizes over calendar lifetimes even "
                         "under churn (the baseline the wear-aware "
                         "solver is compared against)")
    ap.add_argument("--admission", default=None,
                    choices=[None, "write_aware"],
                    help="cache admission policy: write_aware only "
                         "caches contexts whose expected reuse amortizes"
                         " the insert's write energy + wear")
    ap.add_argument("--trace", default=None, metavar="OUT.jsonl",
                    help="record the flight-recorder span trace and "
                         "write it as JSONL plus a Chrome trace_event "
                         "file (OUT.trace.json); tracing off is the "
                         "default and bit-reproduces the untraced run")
    ap.add_argument("--metrics", default=None, metavar="OUT.prom",
                    help="write the Prometheus-style text exposition of "
                         "the run's MetricsRegistry")
    ap.add_argument("--explain", action="store_true",
                    help="print the final solve's surviving candidate "
                         "table (SolveResult.explain)")
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--arch", default="yi-6b")
    args = ap.parse_args(argv)
    if args.real:
        return run_real_demo(args)
    return run_simulation(args)


if __name__ == "__main__":
    main()
