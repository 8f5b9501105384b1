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
from repro.models.transformer import init_params, prefill
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
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
