"""RG-LRU linear-recurrence Pallas kernel (RecurrentGemma hot-spot).

    h_t = a_t ⊙ h_{t-1} + b_t

The gate/decay computation (sigmoid/softplus matmuls) is dense XLA work;
the kernel handles the inherently-sequential scan, blocked over channels
and time: each grid step streams (block_s, block_d) tiles of a and b, and
the (block_d,) state is carried across time blocks in the resident h_last
output block, so VMEM use does not grow with S. Channels are
embarrassingly parallel (grid axes 0/1 parallel, time axis 2 sequential).
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, hn_ref, *, block_s: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        hn_ref[...] = h0_ref[...]

    def body(t, h):
        h = a_ref[0, t, :] * h + b_ref[0, t, :]
        y_ref[0, t, :] = h
        return h

    hn_ref[0, 0, :] = jax.lax.fori_loop(0, block_s, body, hn_ref[0, 0, :])


def rglru_scan(a, b, h0, *, block_s: int = 256, block_d: int = 512,
               interpret: bool = False):
    """a, b: (B, S, D) fp32 decay/input; h0: (B, D). Returns (y, h_last)."""
    B, S, D = a.shape
    block_s = min(block_s, S)
    block_d = min(block_d, D)
    assert S % block_s == 0 and D % block_d == 0
    ns, nd = S // block_s, D // block_d
    # the state rides as (B, 1, D): a (1, block_d) block over (B, D) breaks
    # the TPU rule that a block's last two dims are (8, 128)-aligned or whole
    h0 = h0.reshape(B, 1, D)
    seq = pl.BlockSpec((1, block_s, block_d), lambda i, j, t: (i, t, j))
    state = pl.BlockSpec((1, 1, block_d), lambda i, j, t: (i, 0, j))

    y, hn = pl.pallas_call(
        functools.partial(_rglru_kernel, block_s=block_s),
        grid=(B, nd, ns),
        in_specs=[seq, seq, state],
        out_specs=[seq, state],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), a.dtype),
            jax.ShapeDtypeStruct((B, 1, D), a.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return y, hn.reshape(B, D)
