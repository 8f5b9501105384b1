"""Plain float32 reference of the squared-ReLU dense kind: the dense GQA
reference's norms, rotary embeddings, windowed causal attention and head,
with ``down(relu(up x) ** 2)`` in place of the SwiGLU MLP."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import relu2_dense as kind
from bench.references.dense_gqa import NEG, _bf16, _head, _mm, _rms, _rope


@functools.partial(jax.jit, static_argnames=("dims", "quant", "block"))
def _layer(h, stacks, l, dims, quant, block):
    w = {n: a[l].astype(jnp.float32) for n, a in stacks.items()}
    d = dict(dims)
    T = h.shape[0]
    H, KV, hd = d["heads"], d["kv_heads"], d["head_dim"]
    pos = jnp.arange(T)
    x = _rms(h, w["attn_norm"], d["norm_eps"])
    q = _rope(_mm(x, w["q"], quant).reshape(T, H, hd), pos, d["rope_theta"])
    k = _rope(_mm(x, w["k"], quant).reshape(T, KV, hd), pos, d["rope_theta"])
    v = _mm(x, w["v"], quant).reshape(T, KV, hd)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)

    def attend(qb, qpos):
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(hd))
        ok = pos[None, :] <= qpos[:, None]
        if d["window"] is not None:
            ok &= pos[None, :] > qpos[:, None] - d["window"]
        p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
        if quant is not None:
            p = _bf16(p)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(lambda a: attend(*a),
                    (q.reshape(T // block, block, H, hd),
                     pos.reshape(T // block, block))).reshape(T, H * hd)
    h = h + _mm(o, w["o"], quant)
    x = _rms(h, w["mlp_norm"], d["norm_eps"])
    m = jnp.square(jax.nn.relu(_mm(x, w["up"], quant)))
    return h + _mm(m, w["down"], quant)


def logits_at(seed: int, dims: dict, sequences, positions, *, quant=None,
              pad_to: int = 0, block: int = 512):
    params = kind.program_params(seed, dims)
    stacks = kind.by_name(params)
    frozen = tuple(sorted(dims.items()))
    T = -(-max(max(len(s) for s in sequences), pad_to) // block) * block
    with jax.default_matmul_precision("highest"):
        hs = []
        for s in sequences:
            toks = np.zeros(T, np.int32)
            toks[:len(s)] = s
            hs.append(params["embed"][jnp.asarray(toks)].astype(jnp.float32))
        for l in range(dims["layers"]):
            hs = [_layer(h, stacks, l, frozen, quant, block) for h in hs]
        return [np.asarray(_head(h[jnp.asarray(np.asarray(want, np.int32))],
                                 params["final_ln"]["scale"],
                                 params["unembed"], frozen, quant))
                for h, want in zip(hs, positions)]
