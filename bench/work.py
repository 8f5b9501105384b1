"""Operations and bytes that the algorithm needs, computed from shapes.

``dims`` is a configuration's ``dims`` (``bench.configs.load``); each count
is its kind's (``bench/kinds/<kind>.py``), which says what it counts.
"""
from bench.kinds import module


def _kind(dims: dict):
    return module(dims["kind"])


def layer_matmul_params(dims: dict) -> int:
    return _kind(dims).layer_matmul_params(dims)


def prefill_flops(dims: dict, new_tokens: int, prefix_tokens: int) -> float:
    """FLOPs to prefill ``new_tokens`` after ``prefix_tokens`` cached ones."""
    return _kind(dims).prefill_flops(dims, new_tokens, prefix_tokens)


def weight_bytes(dims: dict, bytes_per_param: int = 2) -> int:
    """Bytes of every weight a decode step reads."""
    return _kind(dims).weight_bytes(dims, bytes_per_param)


def decode_step_bytes(dims: dict, pos: int, bytes_per_value: int = 2) -> int:
    """Bytes one decode step at absolute position ``pos`` has to move."""
    return _kind(dims).decode_step_bytes(dims, pos, bytes_per_value)


def decode_bytes(dims: dict, prompt_tokens: int, steps: int) -> int:
    """Bytes of ``steps`` decode steps after a prompt of ``prompt_tokens``."""
    return _kind(dims).decode_bytes(dims, prompt_tokens, steps)


def payload_bytes(dims: dict, max_len: int, bytes_per_value: int = 2) -> int:
    """Device bytes one stored prefix holds."""
    return _kind(dims).payload_bytes(dims, max_len, bytes_per_value)


def charged_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """What the store charges per cached token (its own accounting)."""
    return _kind(dims).charged_bytes_per_token(dims, bytes_per_value)
