"""Random weights from the seed, drawn the same way for the program and for
the reference.

Every tensor is ``normal(key(seed, tensor, layer), shape) * scale`` in
float32, rounded to the served dtype. The program gets all of them from one
jitted call on the device, in its own parameter layout; the reference draws
one layer at a time (``layer``), so it never holds a second copy of the
model. The names are the published ones (Llama/Mistral): ``gate`` is the
projection that goes through SiLU, ``up`` the one it multiplies.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

# tensor ids: fixed, so that a name always draws the same numbers
_IDS = {"embed": 1, "unembed": 2, "final_norm": 3, "attn_norm": 4,
        "q": 5, "k": 6, "v": 7, "o": 8, "mlp_norm": 9, "gate": 10, "up": 11,
        "down": 12}


def base_key(seed: int):
    """A key from any whole number up to 64 bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


NORM = None        # a norm's scale: 1 + 0.1 * normal


def _shapes(d: dict) -> dict:
    """Layer tensors: name -> (shape, fan-in, or NORM)."""
    dm, hd = d["d_model"], d["head_dim"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    return {"attn_norm": ((dm,), NORM), "q": ((dm, q), dm),
            "k": ((dm, kv), dm), "v": ((dm, kv), dm), "o": ((q, dm), q),
            "mlp_norm": ((dm,), NORM), "gate": ((dm, d["d_ff"]), dm),
            "up": ((dm, d["d_ff"]), dm), "down": ((d["d_ff"], dm), d["d_ff"])}


def _draw(key, name: str, shape, fan_in, layer=None):
    """float32 normal draws for one tensor, scaled by 1/sqrt(fan-in); a
    norm's scale is 1 + 0.1 * normal."""
    k = jax.random.fold_in(key, _IDS[name])
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    x = jax.random.normal(k, shape, jnp.float32)
    if fan_in is NORM:
        return 1.0 + 0.1 * x
    return x / math.sqrt(fan_in)


def layer(key, d: dict, l, dtype=jnp.bfloat16) -> dict:
    """Layer ``l``'s tensors by published name, rounded to ``dtype``."""
    return {n: _draw(key, n, s, f, l).astype(dtype)
            for n, (s, f) in _shapes(d).items()}


def embed(key, d: dict, dtype=jnp.bfloat16):
    """Token embeddings with standard deviation 0.02."""
    return _draw(key, "embed", (d["vocab"], d["d_model"]), 2500
                 ).astype(dtype)


def head(key, d: dict, dtype=jnp.bfloat16) -> dict:
    return {"final_norm": _draw(key, "final_norm", (d["d_model"],), NORM
                                ).astype(dtype),
            "unembed": _draw(key, "unembed", (d["d_model"], d["vocab"]),
                             d["d_model"]).astype(dtype)}


@functools.partial(jax.jit, static_argnames=("dims", "dtype"))
def _build(key, dims, dtype):
    d = dict(dims)
    stack = jax.vmap(lambda l: layer(key, d, l, dtype))(jnp.arange(d["layers"]))
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
            # the program applies its activation to ``w_up``
            "mlp": {"w_up": stack["gate"], "w_gate": stack["up"],
                    "w_down": stack["down"]},
        },
    }


def program_params(seed: int, d: dict, dtype=jnp.bfloat16):
    """The whole model in the program's layout (``repro.models.transformer``:
    layer stacks on a leading axis), drawn in one jitted call. The reference
    draws its weights with this same program, so the two hold the same bits
    (the TPU's compiler may fuse one chain of float operations differently
    in two programs)."""
    return _build(base_key(seed), tuple(sorted(d.items())), jnp.dtype(dtype))


# published name -> where the program's layout keeps it
LAYOUT = {"attn_norm": ("ln1", "scale"), "q": ("attn", "wq"),
          "k": ("attn", "wk"), "v": ("attn", "wv"), "o": ("attn", "wo"),
          "mlp_norm": ("ln2", "scale"), "gate": ("mlp", "w_up"),
          "up": ("mlp", "w_gate"), "down": ("mlp", "w_down")}


def by_name(params) -> dict:
    """The layer stacks of ``program_params`` by published name."""
    return {n: params["layers"][a][b] for n, (a, b) in LAYOUT.items()}
