"""Dense decoder with grouped-query attention and a squared-ReLU MLP that is
not gated (Nemotron / Minitron): ``down(relu(up x) ** 2)``. The published
keys, attention, norms, embeddings and head are those of the dense GQA
kind; the MLP has no ``gate`` tensor, and its two matrices are all the
MLP's work."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.kinds import dense_gqa
from bench.kinds.dense_gqa import (  # noqa: F401  dims, payload_bytes
    _attended, charged_bytes_per_token, dims, embed, head, payload_bytes)
from bench.weights import NORM, base_key, declare, draw

declare({"attn_norm": 4, "q": 5, "k": 6, "v": 7, "o": 8, "mlp_norm": 9,
         "up": 11, "down": 12})


def model_config(conf: dict):
    return dataclasses.replace(dense_gqa.model_config(conf),
                               activation="relu2", gated_mlp=False)


def _shapes(d: dict) -> dict:
    dm, hd = d["d_model"], d["head_dim"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    return {"attn_norm": ((dm,), NORM), "q": ((dm, q), dm),
            "k": ((dm, kv), dm), "v": ((dm, kv), dm), "o": ((q, dm), q),
            "mlp_norm": ((dm,), NORM), "up": ((dm, d["d_ff"]), dm),
            "down": ((d["d_ff"], dm), d["d_ff"])}


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _build(key, dims, dtype):
    d = dict(dims)
    stack = jax.vmap(lambda l: {n: draw(key, n, s, f, l).astype(dtype)
                                for n, (s, f) in _shapes(d).items()})(
        jnp.arange(d["layers"]))
    h = head(key, d, dtype)
    return {
        "embed": embed(key, d, dtype),
        "final_ln": {"scale": h["final_norm"]},
        "unembed": h["unembed"],
        "layers": {
            "ln1": {"scale": stack["attn_norm"]},
            "attn": {"wq": stack["q"], "wk": stack["k"], "wv": stack["v"],
                     "wo": stack["o"]},
            "ln2": {"scale": stack["mlp_norm"]},
            "mlp": {"w_up": stack["up"], "w_down": stack["down"]},
        },
    }


def program_params(seed: int, d: dict, dtype=jnp.bfloat16):
    return _build(base_key(seed), tuple(sorted(d.items())), jnp.dtype(dtype))


LAYOUT = {"attn_norm": ("ln1", "scale"), "q": ("attn", "wq"),
          "k": ("attn", "wk"), "v": ("attn", "wv"), "o": ("attn", "wo"),
          "mlp_norm": ("ln2", "scale"), "up": ("mlp", "w_up"),
          "down": ("mlp", "w_down")}


def by_name(params) -> dict:
    return {n: params["layers"][a][b] for n, (a, b) in LAYOUT.items()}


def layer_matmul_params(dims: dict) -> int:
    d, hd = dims["d_model"], dims["head_dim"]
    q, kv = dims["heads"] * hd, dims["kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 2 * d * dims["d_ff"]


def prefill_flops(dims: dict, new_tokens: int, prefix_tokens: int) -> float:
    dense = 2.0 * new_tokens * layer_matmul_params(dims)
    keys = sum(_attended(dims, prefix_tokens + j) for j in range(new_tokens))
    attn = 4.0 * dims["heads"] * dims["head_dim"] * keys
    return dims["layers"] * (dense + attn) + 2.0 * dims["d_model"] \
        * dims["vocab"]


def weight_bytes(dims: dict, bytes_per_param: int = 2) -> int:
    d = dims["d_model"]
    return bytes_per_param * (dims["layers"] * (layer_matmul_params(dims)
                                                + 2 * d)
                              + d + d * dims["vocab"])


def decode_step_bytes(dims: dict, pos: int, bytes_per_value: int = 2) -> int:
    return weight_bytes(dims, bytes_per_value) + _attended(dims, pos) \
        * charged_bytes_per_token(dims, bytes_per_value)


def decode_bytes(dims: dict, prompt_tokens: int, steps: int) -> int:
    return sum(decode_step_bytes(dims, prompt_tokens + i)
               for i in range(steps))
