"""Real-execution mode: actual JAX model with KV-prefix reuse — the cached
path must be numerically identical to recomputing the full prompt."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.kvstore import KVStore
from repro.core.policies import POLICIES
from repro.launch import serve
from repro.launch.serve import SessionTraffic, compare_hit_prefill, run_real
from repro.models import transformer as tfm
from repro.models.transformer import init_cache, init_params, prefill
from repro.serving.realexec import RealExecutionEngine

# real JAX execution / end-to-end simulation: excluded from the fast CI
# tier (run with `pytest -m ""` or `-m slow` for the full suite)
pytestmark = pytest.mark.slow


def make_engine(arch, seed=0):
    nl = 4 if get_config(arch).family == "hybrid" else 2
    cfg = get_config(arch).reduced(num_layers=nl, d_model=128)
    params = init_params(jax.random.PRNGKey(seed), cfg, jnp.float32)
    store = KVStore(64e6, POLICIES["lcs"], max(cfg.kv_bytes_per_token, 1.0))
    return cfg, params, RealExecutionEngine(cfg, params, store, max_len=128)


def test_prefix_prefill_matches_full_prefill():
    """prefill(suffix | cached prefix KV) == prefill(full prompt)."""
    cfg, params, _ = make_engine("yi-6b")
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 24)), jnp.int32)
    full_logits, full_cache = prefill(params, cfg, {"tokens": toks},
                                      max_len=64)
    pre_logits, pre_cache = prefill(params, cfg, {"tokens": toks[:, :16]},
                                    max_len=64)
    suf_logits, suf_cache = prefill(params, cfg, {"tokens": toks[:, 16:]},
                                    max_len=64, prefix_cache=pre_cache,
                                    prefix_len=16)
    np.testing.assert_allclose(np.asarray(suf_logits),
                               np.asarray(full_logits[:, 16:]), atol=3e-4)
    np.testing.assert_allclose(np.asarray(suf_cache["k"][:, :, :24]),
                               np.asarray(full_cache["k"][:, :, :24]),
                               atol=3e-4)


@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-1.6b",
                                  "recurrentgemma-2b"])
def test_multi_turn_reuse_identical_output(arch):
    """Generation with cache reuse == generation without (greedy tokens)."""
    cfg, params, eng = make_engine(arch)
    rng = np.random.default_rng(1)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 20)]

    r1 = eng.generate("c", ctx, num_new=3)
    assert r1.reused_tokens == 0
    ctx2 = ctx + r1.tokens + [int(t) for t in
                              rng.integers(0, cfg.vocab_size, 6)]
    r2 = eng.generate("c", ctx2, num_new=3)
    # the stored prefix covers the first turn's prompt (20 tokens)
    assert r2.reused_tokens == len(ctx)
    assert r2.prefill_tokens_computed == len(ctx2) - len(ctx)

    # fresh engine, no cache: same tokens expected
    cfg_, params_, eng_cold = make_engine(arch)
    rc = eng_cold.generate("other", ctx2, num_new=3)
    assert rc.reused_tokens == 0
    assert rc.tokens == r2.tokens


def _load(name, rel_path):
    """The checkout's file ``rel_path`` imported as module ``name``."""
    path = Path(__file__).resolve().parents[1] / rel_path
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_window_engine():
    """The benchmark's tiny dense GQA configuration with a 32-token
    sliding window, in bf16 as the benchmark's configurations run."""
    conf = _load("bench_conftest", "tests/bench/conftest.py").TINY_CONF
    from bench.configs import model_config
    cfg = model_config(dict(conf, dims=dict(conf["dims"], window=32)))
    params = init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    store = KVStore(64e6, POLICIES["lcs"], cfg.kv_bytes_per_token)
    return cfg, params, RealExecutionEngine(cfg, params, store, max_len=128,
                                            dtype=jnp.bfloat16)


def _host_greedy(eng, prompt, num_new):
    """Greedy tokens from a plain loop: ``tfm.decode_step`` in a program,
    the argmax on the host, the next token and position uploaded."""
    cfg, params = eng.cfg, eng.params
    step = jax.jit(lambda p, c, t, pos: tfm.decode_step(p, cfg, c, t, pos))
    if cfg.family in ("ssm", "hybrid"):
        cache = init_cache(cfg, 1, eng.max_len, eng.dtype)
        for pos, t in enumerate(prompt):
            logits, cache = step(params, cache, jnp.array([[t]], jnp.int32),
                                 jnp.asarray(pos))
    else:
        logits, cache = jax.jit(
            lambda p, b: prefill(p, cfg, b, eng.max_len))(
                params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]})
    V = cfg.vocab_size
    out = [int(jnp.argmax(logits[0, -1, :V]))]
    for pos in range(len(prompt), len(prompt) + num_new - 1):
        logits, cache = step(params, cache, jnp.array([[out[-1]]], jnp.int32),
                             jnp.asarray(pos))
        out.append(int(jnp.argmax(logits[0, -1, :V])))
    return out


@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-1.6b", "recurrentgemma-2b",
                                  "window32"])
def test_device_side_greedy_matches_host_argmax(arch):
    """The decode program's own argmax gives the tokens of a loop that
    reads every step's logits on the host; the window case decodes across
    its 32-token window."""
    cfg, _, eng = make_window_engine() if arch == "window32" else \
        make_engine(arch)
    rng = np.random.default_rng(6)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 28)]
    assert eng.generate("a", ctx, num_new=6).tokens == \
        _host_greedy(eng, ctx, 6)


@pytest.mark.parametrize("arch", ["yi-6b", "rwkv6-1.6b"])
def test_decode_program_compiles_once(arch):
    """A step fed from the host (the first) and one fed by the step before
    it run one compiled program, so a warm-up at ``num_new=1`` covers every
    later request length."""
    cfg, _, eng = make_engine(arch)
    rng = np.random.default_rng(7)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    eng.generate("a", ctx, num_new=1)
    assert eng._decode._cache_size() == 1
    eng.generate("b", ctx[:9], num_new=5)
    assert eng._decode._cache_size() == 1


def test_steps_chain_on_device_outputs():
    """Each step takes the very arrays the step before it returned, so no
    host value is uploaded between steps; ``generate`` returns after the
    last step has finished."""
    cfg, _, eng = make_engine("yi-6b")
    inner, calls = eng._decode, []

    def spy(*args):
        out = inner(*args)
        calls.append((args, out))
        return out
    eng._decode = spy
    rng = np.random.default_rng(8)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    g = eng.generate("a", ctx, num_new=5)
    assert len(calls) == 5
    assert int(calls[0][0][3]) == len(ctx)
    for (_, ((tok, pos), cache)), (args, _) in zip(calls, calls[1:]):
        assert args[1] is cache and args[2] is tok and args[3] is pos
    assert g.tokens[1:] == [int(out[0][0][0, 0]) for _, out in calls[:-1]]
    assert all(x.is_ready() for x in jax.tree_util.tree_leaves(calls[-1][1]))


def test_decode_bounds_the_steps_in_flight():
    """Before step k is dispatched the host waits for step k - 2 (each
    queued step holds a cache of its own), and ``generate`` waits for the
    last step before it returns."""
    from repro.serving import realexec
    cfg, _, eng = make_engine("yi-6b")
    inner, log = eng._decode, []

    class Token:        # a step's token that notes when the host waits
        def __init__(self, a, k):
            self.a, self.k = a, k

        def block_until_ready(self):
            log.append(("wait", self.k))
            return self.a.block_until_ready()

        def copy_to_host_async(self):
            self.a.copy_to_host_async()

        def item(self):
            return self.a.item()

    def step(p, c, t, pos):
        k = sum(e == "step" for e, _ in log)
        log.append(("step", k))
        (tok, pos), c = inner(p, c, getattr(t, "a", t), pos)
        return (Token(tok, k), pos), c
    eng._decode = step
    rng = np.random.default_rng(9)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    g = eng.generate("a", ctx, num_new=6)
    n = realexec._IN_FLIGHT
    want = []
    for k in range(6):
        want += [("wait", k - n)] if k >= n else []
        want.append(("step", k))
    assert log == want + [("wait", 5)]
    assert g.tokens == _host_greedy(eng, ctx, 6)


def test_store_tracks_real_payload_bytes():
    cfg, params, eng = make_engine("yi-6b")
    rng = np.random.default_rng(2)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 12)]
    eng.generate("a", ctx, num_new=2)
    assert len(eng.store.entries) == 1
    e = eng.store.entries["a"]
    assert e.payload is not None
    assert e.num_tokens == 12 + 0  # prompt cached (decode tokens excluded
    # from the key count is implementation detail: prompt_tokens inserted)


TINY_TRAFFIC = SessionTraffic(prompt_len=24, followup_len=8, control_len=12,
                              num_new=3)


def _load_chip_smoke():
    return _load("chip_smoke", "chip_smoke.py")


def test_run_real_sized_store_hits_on_turn2():
    """A store sized from the config and the traffic holds every prompt:
    both turn 2s reuse turn 1's prompt, the control misses, nothing is
    evicted, and the measured pass repeats the warm-up without compiling."""
    cfg = get_config("yi-6b").reduced(num_layers=2, d_model=128)
    t = TINY_TRAFFIC
    run = run_real(cfg, t, dtype=jnp.float32, max_len=64,
                   store_bytes=t.store_bytes(cfg))
    res = run.results
    for s in serve.SESSIONS:
        assert res[f"{s}/turn1"].reused_tokens == 0
        assert res[f"{s}/turn2"].reused_tokens == t.prompt_len
        assert res[f"{s}/turn2"].prefill_tokens_computed == \
            t.num_new + t.followup_len
    assert res["control"].reused_tokens == 0
    assert run.engine.store.stats.evictions == 0
    assert run.window.programs == 0
    assert {n: r.tokens for n, r in res.items()} == \
        {n: r.tokens for n, r in run.warmup.items()}
    cmp = compare_hit_prefill(run.engine, run.prompts["s0/turn2"],
                              t.prompt_len)
    assert cmp["finite"] and cmp["argmax_agree"] == 1.0
    assert cmp["rel_l2"] < 1e-5


def test_chip_smoke_checks_pass_on_reduced_model():
    """The smoke's own checks, on a bf16 model far smaller than the chip's,
    at the tolerance the chip run uses."""
    cs = _load_chip_smoke()
    cfg = get_config("llama3-8b").reduced(num_layers=2, d_model=128)
    assert cs.smoke(cfg, TINY_TRAFFIC, dtype=jnp.bfloat16, max_len=64,
                    rel_l2_tol=cs.REL_L2_TOL) == {}


def test_chip_smoke_refuses_a_host_without_tpu(capsys):
    if jax.devices()[0].platform == "tpu":
        pytest.skip("a TPU is attached: the smoke would run")
    assert _load_chip_smoke().main() == 1
    assert "found platform 'cpu'" in capsys.readouterr().err


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """JAX_COMPILATION_CACHE_DIR, where set, is left to JAX; otherwise the
    cache is the checkout's fixed .jax_cache."""
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    before = jax.config.jax_compilation_cache_dir
    try:
        got = serve.use_compile_cache()
        now = jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    if env_dir is None:
        assert got == now == str(serve.CHECKOUT / ".jax_cache")
        assert (serve.CHECKOUT / "chip_smoke.py").exists()
    else:
        assert got == env_dir and now == before


def _serve_two_turns(eng, cfg, seed=3):
    """Two turns of one session and a control request; turn 2 hits."""
    rng = np.random.default_rng(seed)
    draw = lambda n: [int(t) for t in rng.integers(0, cfg.vocab_size, n)]
    ctx = draw(20)
    out = [eng.generate("s", ctx, num_new=3, now=1.0)]
    out.append(eng.generate("s", ctx + out[0].tokens + draw(5), num_new=3,
                            now=2.0))
    out.append(eng.generate("c", draw(12), num_new=2, now=3.0))
    return out


def test_recorder_leaves_generation_unchanged():
    """The bit-identity contract: the same tokens and counts with a
    recorder attached and without one."""
    from repro.obs import TraceRecorder
    cfg, _, bare = make_engine("yi-6b")
    _, _, traced = make_engine("yi-6b")
    assert bare.recorder is None
    traced.recorder = TraceRecorder()
    for a, b in zip(_serve_two_turns(bare, cfg),
                    _serve_two_turns(traced, cfg)):
        assert (a.tokens, a.reused_tokens, a.prefill_tokens_computed) == \
            (b.tokens, b.reused_tokens, b.prefill_tokens_computed)
    assert len(bare.store.entries) == len(traced.store.entries) == 2


def test_recorder_writes_one_row_per_request():
    from repro.obs import TraceRecorder
    cfg, _, eng = make_engine("yi-6b")
    rec = eng.recorder = TraceRecorder()
    res = _serve_two_turns(eng, cfg)
    rows = list(rec.rows())
    assert [r["rid"] for r in rows] == [1, 2, 3]
    assert [r["arrival_s"] for r in rows] == [1.0, 2.0, 3.0]
    assert [r["hit_kind"] for r in rows] == ["miss", "hit", "miss"]
    assert [r["matched_tokens"] for r in rows] == [0, 20, 0]
    assert [r["prompt_tokens"] for r in rows] == [20, 28, 12]
    assert [r["output_tokens"] for r in rows] == [3, 3, 2]
    for r, g in zip(rows, res):
        assert r["prefill_s"] == g.prefill_time_s
        assert r["decode_s"] == g.decode_time_s
        assert r["kv_load_s"] == r["queue_s"] == r["ttft_s"] == 0


def test_store_bytes_event_counts_whole_rings():
    """Each payload is a whole ``max_len`` ring of keys and values, while
    the store charges the prompt's tokens."""
    from repro.obs import TraceRecorder
    cfg, _, eng = make_engine("yi-6b")
    rec = eng.recorder = TraceRecorder()
    _serve_two_turns(eng, cfg)
    ring = 2 * cfg.num_layers * eng.max_len * cfg.num_kv_heads \
        * cfg.head_dim * np.dtype(np.float32).itemsize
    evs = [e for e in rec.events if e["kind"] == "store_bytes"]
    assert [e["entries"] for e in evs] == [1, 1, 2]
    assert [e["payload_bytes"] for e in evs] == [ring, ring, 2 * ring]
    assert [e["ts"] for e in evs] == [1.0, 2.0, 3.0]
    assert evs[-1]["charged_bytes"] == eng.store.used_bytes == \
        (28 + 12) * cfg.kv_bytes_per_token


def test_programs_are_named():
    """The programs a trace shows are named by kind, not by shape."""
    cfg, params, eng = make_engine("yi-6b")
    rng = np.random.default_rng(4)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 16)]
    eng.generate("a", ctx, num_new=1)
    eng.generate("a", ctx + [1, 2, 3], num_new=1)
    toks = jnp.zeros((1, 16), jnp.int32)
    miss = eng._prefill_cached[(16, 0)].lower(params, {"tokens": toks})
    _, cache = eng.prefill(toks)
    hit = eng._prefill_cached[(3, 16)].lower(
        params, {"tokens": toks[:, :3]}, cache)
    step = eng._decode.lower(params, cache, jnp.zeros((1, 1), jnp.int32),
                             jnp.asarray(16))
    for lowered, name in ((miss, "jit_prefill_miss"),
                          (hit, "jit_prefill_hit"),
                          (step, "jit_decode_step")):
        assert f"module @{name} " in lowered.as_text()


def test_profiler_trace_holds_engine_spans_per_request(tmp_path):
    """Two requests under the CPU profiler: every ``engine.*`` span carries
    its request's ``rid`` and ``context_key``, and the decode steps sit
    inside ``engine.decode``."""
    from jax.profiler import ProfileData
    from repro.obs import TraceRecorder
    cfg, _, eng = make_engine("yi-6b")
    eng.recorder = TraceRecorder()
    rng = np.random.default_rng(5)
    ctx = [int(t) for t in rng.integers(0, cfg.vocab_size, 16)]
    eng.generate("a", ctx, num_new=2)         # compile outside the trace
    eng.generate("a", ctx + [7, 8], num_new=2)
    eng.store = KVStore(64e6, POLICIES["lcs"], cfg.kv_bytes_per_token)
    jax.profiler.start_trace(str(tmp_path))
    eng.generate("a", ctx, num_new=2)
    eng.generate("a", ctx + [7, 8], num_new=2)
    jax.profiler.stop_trace()
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    spans = [(ev.name, dict(ev.stats), ev.start_ns, ev.duration_ns)
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("engine.")]
    by_rid = {}
    for name, stats, _, _ in spans:
        assert stats["context_key"] == "a"
        by_rid.setdefault(stats["rid"], []).append(name)
    assert sorted(by_rid) == [3, 4]
    for names in by_rid.values():
        assert names.count("engine.generate") == 1
        for one in ("engine.lookup", "engine.prefill", "engine.insert",
                    "engine.decode"):
            assert names.count(one) == 1
        assert names.count("engine.decode_step") == 2
        # the prefill's token, then the decoded tokens in one read
        assert names.count("engine.sample") == 2
    prefill = [s for n, s, _, _ in spans if n == "engine.prefill"]
    assert [(s["suffix_len"], s["prefix_len"]) for s in prefill] == \
        [(16, 0), (2, 16)]
    decode = [(t, t + d) for n, _, t, d in spans if n == "engine.decode"]
    for n, _, t, d in spans:
        if n == "engine.decode_step":
            assert any(a <= t and t + d <= b for a, b in decode)


def test_serve_real_writes_the_engine_trace(tmp_path, capsys):
    """``serve --real --trace`` attaches the recorder to the measured pass
    and writes the JSONL and Chrome exports."""
    import json
    out = tmp_path / "real.jsonl"
    run = serve.main(["--real", "--arch", "yi-6b", "--trace", str(out)])
    rows = [json.loads(x) for x in out.read_text().splitlines()]
    reqs = [r for r in rows if r["type"] == "request"]
    assert len(reqs) == len(run.results) == 5
    assert sum(r["hit_kind"] == "hit" for r in reqs) == 2
    evs = [r for r in rows if r["type"] == "event"]
    assert len(evs) == 5 and all(e["kind"] == "store_bytes" for e in evs)
    assert all(e["payload_bytes"] > e["charged_bytes"] > 0 for e in evs)
    chrome = json.loads((tmp_path / "real.trace.json").read_text())
    assert {e["name"] for e in chrome["traceEvents"]
            if e["ph"] == "X"} == {"prefill", "decode"}
    assert "engine trace" in capsys.readouterr().out


def test_no_recorder_makes_no_span(monkeypatch):
    """Detached (the default), ``generate`` makes no profiler span."""
    cfg, _, eng = make_engine("yi-6b")

    def refuse(*a, **k):
        raise AssertionError("a span was made with no recorder attached")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    res = _serve_two_turns(eng, cfg)
    assert [g.reused_tokens for g in res] == [0, 20, 0]
