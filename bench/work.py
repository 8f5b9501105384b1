"""Operations and bytes that the algorithm needs, computed from shapes.

``dims`` is a configuration's ``dims`` (``bench.configs.load``): d_model,
heads, kv_heads, head_dim, d_ff, vocab, layers and window (None for full
attention). Counts are of the mathematics, not of what the program happens
to execute: causal attention is counted once (each query against the keys
at or before it, within the window), prefill's output projection is counted
for the last position only (the one that yields the next token), and decode
reads the weights once and the keys and values of the positions attended,
not the padded cache.
"""


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
