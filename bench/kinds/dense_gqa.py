"""Dense decoder with grouped-query attention (Llama / Mistral): RMSNorm,
rotary embeddings, causal attention within the sliding window where there
is one, a SwiGLU MLP and an untied output projection.

Sizes (``dims``): d_model, heads, kv_heads, head_dim, d_ff, vocab, layers,
window (None for full attention), rope_theta and norm_eps.

Weights: the names are the published ones (Llama/Mistral): ``gate`` is the
projection that goes through SiLU, ``up`` the one it multiplies. The
program gets all of them from one jitted call on the device, in its own
parameter layout; the reference draws them with the same call.

Work: counts are of the mathematics, not of what the program happens to
execute: causal attention is counted once (each query against the keys at
or before it, within the window), prefill's output projection is counted
for the last position only (the one that yields the next token), and
decode reads the weights once and the keys and values of the positions
attended, not the padded cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.weights import NORM, base_key, declare, draw

# tensor ids: fixed, so that a name always draws the same numbers
declare({"embed": 1, "unembed": 2, "final_norm": 3, "attn_norm": 4,
         "q": 5, "k": 6, "v": 7, "o": 8, "mlp_norm": 9, "gate": 10, "up": 11,
         "down": 12})


def dims(hf: dict) -> dict:
    """The sizes the benchmark computes with, from the published keys."""
    heads = hf["num_attention_heads"]
    return {
        "layers": hf["num_hidden_layers"],
        "d_model": hf["hidden_size"],
        "heads": heads,
        "kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim") or hf["hidden_size"] // heads,
        "d_ff": hf["intermediate_size"],
        "vocab": hf["vocab_size"],
        "window": hf.get("sliding_window"),
        "rope_theta": float(hf["rope_theta"]),
        "norm_eps": float(hf["rms_norm_eps"]),
    }


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration as run."""
    from repro.configs.base import ModelConfig
    d = conf["dims"]
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=d["layers"],
        d_model=d["d_model"], num_heads=d["heads"],
        num_kv_heads=d["kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab"], activation="silu", gated_mlp=True,
        rope_theta=d["rope_theta"], window_size=d["window"],
        norm_eps=d["norm_eps"], tie_embeddings=False,
        source=conf["source"])


# ----------------------------------------------------------------- weights

def _shapes(d: dict) -> dict:
    """Layer tensors: name -> (shape, fan-in, or NORM)."""
    dm, hd = d["d_model"], d["head_dim"]
    q, kv = d["heads"] * hd, d["kv_heads"] * hd
    return {"attn_norm": ((dm,), NORM), "q": ((dm, q), dm),
            "k": ((dm, kv), dm), "v": ((dm, kv), dm), "o": ((q, dm), q),
            "mlp_norm": ((dm,), NORM), "gate": ((dm, d["d_ff"]), dm),
            "up": ((dm, d["d_ff"]), dm), "down": ((d["d_ff"], dm), d["d_ff"])}


def layer(key, d: dict, l, dtype=jnp.bfloat16) -> dict:
    """Layer ``l``'s tensors by published name, rounded to ``dtype``."""
    return {n: draw(key, n, s, f, l).astype(dtype)
            for n, (s, f) in _shapes(d).items()}


def embed(key, d: dict, dtype=jnp.bfloat16):
    """Token embeddings with standard deviation 0.02."""
    return draw(key, "embed", (d["vocab"], d["d_model"]), 2500).astype(dtype)


def head(key, d: dict, dtype=jnp.bfloat16) -> dict:
    return {"final_norm": draw(key, "final_norm", (d["d_model"],), NORM
                               ).astype(dtype),
            "unembed": draw(key, "unembed", (d["d_model"], d["vocab"]),
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


# -------------------------------------------------------------------- work

def _attended(dims: dict, pos: int) -> int:
    """Keys a query at absolute position ``pos`` attends to."""
    w = dims["window"]
    return pos + 1 if w is None else min(pos + 1, w)


def layer_matmul_params(dims: dict) -> int:
    d, hd = dims["d_model"], dims["head_dim"]
    q, kv = dims["heads"] * hd, dims["kv_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * dims["d_ff"]


def prefill_flops(dims: dict, new_tokens: int, prefix_tokens: int) -> float:
    """FLOPs to prefill ``new_tokens`` after ``prefix_tokens`` cached ones."""
    dense = 2.0 * new_tokens * layer_matmul_params(dims)
    keys = sum(_attended(dims, prefix_tokens + j) for j in range(new_tokens))
    attn = 4.0 * dims["heads"] * dims["head_dim"] * keys
    head = 2.0 * dims["d_model"] * dims["vocab"]
    return dims["layers"] * (dense + attn) + head


def weight_bytes(dims: dict, bytes_per_param: int = 2) -> int:
    """Bytes of every weight a decode step reads: the layers, the final norm
    and the output projection; not the embedding table, of which a step
    reads one row."""
    d = dims["d_model"]
    per_layer = layer_matmul_params(dims) + 2 * d
    return bytes_per_param * (dims["layers"] * per_layer + d
                              + d * dims["vocab"])


def decode_step_bytes(dims: dict, pos: int, bytes_per_value: int = 2) -> int:
    """Bytes one decode step at absolute position ``pos`` has to move: the
    weights once and the keys and values of the positions it attends."""
    kv = (dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"]
          * bytes_per_value * _attended(dims, pos))
    return weight_bytes(dims, bytes_per_value) + kv


def decode_bytes(dims: dict, prompt_tokens: int, steps: int) -> int:
    """Bytes of ``steps`` decode steps after a prompt of ``prompt_tokens``:
    step i writes and attends from position ``prompt_tokens + i``."""
    return sum(decode_step_bytes(dims, prompt_tokens + i)
               for i in range(steps))


def payload_bytes(dims: dict, max_len: int, bytes_per_value: int = 2) -> int:
    """Device bytes one stored prefix holds: the engine keeps the whole
    ring of ``min(max_len, window)`` slots, whatever the prefix length."""
    w = dims["window"]
    slots = max_len if w is None else min(max_len, w)
    return (dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"]
            * bytes_per_value * slots)


def charged_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """What the store charges per cached token (its own accounting)."""
    return (dims["layers"] * 2 * dims["kv_heads"] * dims["head_dim"]
            * bytes_per_value)
