"""Plain float32 reference of a dense decoder with grouped-query attention
(Llama / Mistral): RMSNorm, rotary embeddings with the configuration's
theta (halves rotated, as Hugging Face's ``rotate_half``), causal attention
within the sliding window where there is one, a SwiGLU MLP
(``down(silu(gate x) * up x)``) and an untied output projection.

It runs in float32 with ``jax.default_matmul_precision("highest")``, one
layer at a time over every sequence, with attention in blocks of queries.
Its weights are drawn anew from the seed by its kind
(``bench/kinds/dense_gqa.py``), held in bfloat16 as served and widened to
float32 one layer at a time, so it fits once the program's state is freed.

``quant="fp8"`` computes the same model one precision step below bfloat16,
for the control, as an fp8 (e4m3) deployment would: weights scaled per
output channel and activations per token into every matmul, attention
probabilities in bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import dense_gqa as kind

NEG = -1e30


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x, axis):
    """float8 e4m3 with one scale per slice along ``axis`` (the largest
    magnitude maps to e4m3's largest finite value, 448), dequantized."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant == "fp8":
        return _fp8(x, -1) @ _fp8(w, 0)
    return x @ w


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (T, heads, hd); pos: (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                    / x.shape[-1])
    ang = pos.astype(jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("dims", "quant", "block"))
def _layer(h, stacks, l, dims, quant, block):
    """Layer ``l`` of the bfloat16 stacks over a padded sequence h: (T, d)."""
    w = {n: a[l].astype(jnp.float32) for n, a in stacks.items()}
    d = dict(dims)
    T = h.shape[0]
    H, KV, hd = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    x = _rms(h, w["attn_norm"], d["norm_eps"])
    q = _rope(_mm(x, w["q"], quant).reshape(T, H, hd), pos, d["rope_theta"])
    k = _rope(_mm(x, w["k"], quant).reshape(T, KV, hd), pos, d["rope_theta"])
    v = _mm(x, w["v"], quant).reshape(T, KV, hd)
    g = H // KV
    k = jnp.repeat(k, g, axis=1)            # query head i uses kv head i // g
    v = jnp.repeat(v, g, axis=1)

    def attend(qb, qpos):                   # qb: (block, H, hd)
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(hd))
        ok = pos[None, :] <= qpos[:, None]
        if d["window"] is not None:
            ok &= pos[None, :] > qpos[:, None] - d["window"]
        s = jnp.where(ok[None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        if quant is not None:
            p = _bf16(p)
        return jnp.einsum("hqk,khd->qhd", p, v)

    qs = q.reshape(T // block, block, H, hd)
    ps = pos.reshape(T // block, block)
    o = jax.lax.map(lambda a: attend(*a), (qs, ps)).reshape(T, H * hd)
    h = h + _mm(o, w["o"], quant)
    x = _rms(h, w["mlp_norm"], d["norm_eps"])
    m = jax.nn.silu(_mm(x, w["gate"], quant)) * _mm(x, w["up"], quant)
    return h + _mm(m, w["down"], quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _head(h_rows, norm, unembed, dims, quant):
    x = _rms(h_rows, norm.astype(jnp.float32), dict(dims)["norm_eps"])
    return _mm(x, unembed.astype(jnp.float32), quant)


def logits_at(seed: int, dims: dict, sequences, positions, *, quant=None,
              pad_to: int = 0, block: int = 512):
    """Logits (float32, numpy) of each sequence at the wanted positions.

    ``sequences``: token lists; ``positions``: for each, the indices whose
    next-token logits are wanted. Sequences are padded with token 0 to one
    length (at least ``pad_to``, a multiple of ``block``): causal attention
    keeps the padding out of every real position."""
    params = kind.program_params(seed, dims)
    stacks = kind.by_name(params)
    frozen = tuple(sorted(dims.items()))
    T = max(max(len(s) for s in sequences), pad_to)
    T = -(-T // block) * block
    with jax.default_matmul_precision("highest"):
        hs = []
        for s in sequences:
            toks = np.zeros(T, np.int32)
            toks[:len(s)] = s
            hs.append(params["embed"][jnp.asarray(toks)].astype(jnp.float32))
        for l in range(dims["layers"]):
            hs = [_layer(h, stacks, l, frozen, quant, block) for h in hs]
        out = []
        for h, want in zip(hs, positions):
            rows = h[jnp.asarray(np.asarray(want, np.int32))]
            out.append(np.asarray(_head(rows, params["final_ln"]["scale"],
                                        params["unembed"], frozen, quant)))
    return out
