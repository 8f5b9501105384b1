"""The plain float32 reference against the program's own prefill, hit-path
prefill and decode, at a reduced size on the CPU, with the benchmark's
weights (rounded to bfloat16 as served, computed in float32 here)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import configs
from bench.kinds import dense_gqa as weights
from bench.references import dense_gqa
from conftest import TINY_CONF
from repro.models.transformer import decode_step, prefill

SEED = 2**31 + 99


def _program(dims):
    conf = dict(TINY_CONF, dims=dims)
    cfg = configs.model_config(conf)
    p = weights.program_params(SEED, dims, jnp.bfloat16)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), p)


# The hit path is compared only where the context fits the window (as in
# every cell): past the window the engine's restored prefix is wrong, a
# known fault of the program (PERF.md, Open questions).
@pytest.mark.parametrize("window,hit", [(None, True), (48, True),
                                        (16, False)])
def test_reference_matches_prefill_hit_prefill_and_decode(window, hit):
    dims = dict(TINY_CONF["dims"], window=window)
    cfg, params = _program(dims)
    V, max_len = dims["vocab"], 64
    toks = np.random.default_rng(0).integers(0, V, 40).tolist()
    n, steps = 32, 6
    # miss path: the whole prompt
    lg, cache = prefill(params, cfg, {"tokens": jnp.asarray([toks[:n]])},
                        max_len)
    # hit path: a stored 24-token prefix, then the rest
    _, pc = prefill(params, cfg, {"tokens": jnp.asarray([toks[:24]])},
                    max_len)
    lg_hit, _ = prefill(params, cfg, {"tokens": jnp.asarray([toks[24:n]])},
                        max_len, prefix_cache=pc, prefix_len=24)
    # decode through the cache, teacher-forced with toks[n:]
    dec = []
    for i in range(steps):
        out, cache = decode_step(params, cfg, cache,
                                 jnp.asarray([[toks[n + i]]]), n + i)
        dec.append(np.asarray(out[0, 0, :V]))
    ref = dense_gqa.logits_at(SEED, dims, [toks[:n + steps]],
                              [list(range(n + steps))], block=8)[0]
    np.testing.assert_allclose(np.asarray(lg[0, :, :V]), ref[:n],
                               rtol=1e-4, atol=1e-4)
    if hit:
        np.testing.assert_allclose(np.asarray(lg_hit[0, :, :V]), ref[24:n],
                                   rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.stack(dec), ref[n:n + steps],
                               rtol=1e-4, atol=1e-4)


def test_reference_sees_a_wrong_rope_theta():
    """A program run at the repository's default theta (10000) differs from
    the published Yi theta (5e6) that the reference uses."""
    dims = dict(TINY_CONF["dims"])
    cfg, params = _program(dims)
    cfg = dataclasses.replace(cfg, rope_theta=10_000.0)
    toks = np.random.default_rng(1).integers(0, dims["vocab"], 32).tolist()
    lg, _ = prefill(params, cfg, {"tokens": jnp.asarray([toks])}, 64)
    ref = dense_gqa.logits_at(SEED, dims, [toks], [list(range(32))],
                              block=8)[0]
    assert np.abs(np.asarray(lg[0, :, :dims["vocab"]]) - ref).max() > 1e-2


def test_weights_are_redrawn_bit_for_bit_from_the_seed():
    dims = TINY_CONF["dims"]
    a = weights.program_params(SEED, dims)
    b = weights.program_params(SEED, dims)
    c = weights.program_params(SEED + 1, dims)
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool(jnp.all(a["embed"] == c["embed"]))
    named = weights.by_name(a)
    assert named["gate"] is a["layers"]["mlp"]["w_up"]
    assert named["q"].shape == (dims["layers"], dims["d_model"],
                                dims["heads"] * dims["head_dim"])
