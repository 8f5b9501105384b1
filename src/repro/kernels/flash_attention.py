"""Prefix-aware flash-attention Pallas TPU kernel (prefill hot-spot).

This is the compute the paper's context cache *saves*: on a cache hit, only
the uncached suffix is prefilled, with queries at absolute offset
``q_offset`` attending to ``cached_prefix + suffix`` keys. The kernel is a
standard online-softmax flash attention with

  * a query-position offset (cached-context prefill),
  * optional sliding-window masking (SWA archs / long-context mode),
  * GQA handled by block index-mapping (no materialized K/V repeat):
    grid runs over (batch × kv_head), each step processing the G query heads
    that share the kv head — keeping the MXU matmul (G·bq × hd × bk) dense.

VMEM tiling: q block (block_q, hd), k/v blocks (block_k, hd), fp32
accumulators (block_q, hd) in scratch. block_q/block_k default 128 to align
with the MXU systolic array; hd is kept whole (pad to a lane multiple of 128
on real hardware for odd head dims like danube's 80).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale: float, block_q: int, block_k: int,
                  q_offset: int, causal: bool, window: Optional[int],
                  num_k_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                      # (G*bq, hd)
    k = k_ref[0].astype(jnp.float32)                      # (bk, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * sm_scale                                      # (G*bq, bk)

    # query rows are G heads × block_q positions: row r -> position
    # q_offset + iq*block_q + (r % block_q)  [head-major packing g*bq + i]
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    qpos = q_offset + iq * block_q + (rows % block_q)
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = l_prev * alpha + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_cur
    l_ref[...] = l_cur

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0, ...] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, q_offset: int = 0, causal: bool = True,
                    window: Optional[int] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q: (B, H, Sq, hd); k, v: (B, KV, Sk, hd) with H % KV == 0.
    Returns (B, H, Sq, hd). q_offset: absolute position of q[:, :, 0]."""
    B, H, Sq, hd = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0
    nq, nk = Sq // block_q, Sk // block_k

    # pack q as (B*KV, nq*G*bq, hd): grid row = (b, kv); each q block holds
    # the G query heads sharing this kv head, stacked head-major [g, bq].
    qg = (q.reshape(B, KV, G, nq, block_q, hd)
          .transpose(0, 1, 3, 2, 4, 5)
          .reshape(B * KV, nq * G * block_q, hd))
    kk = k.reshape(B * KV, Sk, hd)
    vv = v.reshape(B * KV, Sk, hd)

    grid = (B * KV, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, sm_scale=hd ** -0.5, block_q=block_q,
            block_k=block_k, q_offset=q_offset, causal=causal,
            window=window, num_k_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, G * block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, G * block_q, hd),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV, nq * G * block_q, hd),
                                       q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q,), jnp.float32),
            pltpu.VMEM((G * block_q,), jnp.float32),
            pltpu.VMEM((G * block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qg, kk, vv)

    out = (out.reshape(B, KV, nq, G, block_q, hd)
           .transpose(0, 1, 3, 2, 4, 5)
           .reshape(B, H, Sq, hd))
    return out
