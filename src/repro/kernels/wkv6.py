"""RWKV6 WKV recurrence Pallas kernel (Finch hot-spot).

Per head, the matrix-valued state S ∈ R^{hd×hd} (hd = 64 → 16 KB fp32)
lives in VMEM for the whole sequence while r/k/v/w stream in (S, hd) tiles:

    y_t = (S_t + u ⊙ (k_t ⊗ v_t))ᵀ r_t
    S_{t+1} = diag(w_t) S_t + k_t ⊗ v_t

Grid over (batch × heads) — fully parallel; the time loop is in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sn_ref,
                state_ref, *, seq_len: int):
    state_ref[...] = s0_ref[0]

    u = u_ref[0, 0]                                 # (hd,)

    def body(t, _):
        rt = r_ref[0, t, :]
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]
        wt = w_ref[0, t, :]
        kv = kt[:, None] * vt[None, :]              # (hd, hd)
        eff = state_ref[...] + u[:, None] * kv
        y_ref[0, t, :] = jnp.sum(eff * rt[:, None], axis=0)
        state_ref[...] = state_ref[...] * wt[:, None] + kv
        return 0

    jax.lax.fori_loop(0, seq_len, body, 0)
    sn_ref[0] = state_ref[...]


def wkv6(r, k, v, w, u, s0, *, interpret: bool = False):
    """r,k,v,w: (B, H, S, hd) fp32; u: (H, hd); s0: (B, H, hd, hd).
    Returns (y (B,H,S,hd), s_n (B,H,hd,hd))."""
    B, H, S, hd = r.shape
    rr = r.reshape(B * H, S, hd)
    kk = k.reshape(B * H, S, hd)
    vv = v.reshape(B * H, S, hd)
    ww = w.reshape(B * H, S, hd)
    # u rides as (B*H, 1, hd): a (1, hd) block over (B*H, hd) breaks the TPU
    # rule that a block's last two dims are (8, 128)-aligned or whole
    uu = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    ss = s0.reshape(B * H, hd, hd)

    y, sn = pl.pallas_call(
        functools.partial(_wkv_kernel, seq_len=S),
        grid=(B * H,),
        in_specs=[
            pl.BlockSpec((1, S, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, S, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, S, hd), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, hd, hd), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B * H, hd, hd), r.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(rr, kk, vv, ww, uu, ss)
    return y.reshape(B, H, S, hd), sn.reshape(B, H, hd, hd)
