"""Single-token decode attention Pallas kernel (paged/ring KV cache).

Decode is memory-bound: the whole KV cache streams HBM→VMEM once per step.
The kernel fuses the masked online-softmax over key blocks so scores never
round-trip to HBM. GQA is exploited like the prefill kernel: grid over
(batch × kv_head), each step computing the G query heads sharing the kv head
as a (G × hd) · (hd × block_k) MXU matmul.

Ring-buffer semantics: ``valid`` is a precomputed int32 mask over cache
slots (1 = slot holds a key this query may attend to — encodes causality,
ring wrap-around, and sliding windows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref,
                   m_ref, l_ref, acc_ref, *, sm_scale: float,
                   num_k_blocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                       # (G, hd)
    k = k_ref[0].astype(jnp.float32)                       # (bk, hd)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    mask = (valid_ref[0] > 0)[None, :]                     # (1, bk)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_ref[...] = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur

    @pl.when(ik == num_k_blocks - 1)
    def _fin():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, ...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, valid, *, block_k: int = 512,
                     interpret: bool = False):
    """q: (B, H, hd); k_cache, v_cache: (B, KV, W, hd); valid: (W,) int32.
    Returns (B, H, hd)."""
    B, H, hd = q.shape
    KV, W = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    block_k = min(block_k, W)
    assert W % block_k == 0
    nk = W // block_k

    qg = q.reshape(B * KV, G, hd)
    kk = k_cache.reshape(B * KV, W, hd)
    vv = v_cache.reshape(B * KV, W, hd)
    val = valid.astype(jnp.int32).reshape(1, W)

    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=hd ** -0.5,
                          num_k_blocks=nk),
        grid=(B * KV, nk),
        in_specs=[
            pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k), lambda b, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, G, hd), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, G, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kk, vv, val)
    return out.reshape(B, H, hd)
