"""Jitted public wrappers for the Pallas kernels.

The model code does not call these yet: ``repro.models`` computes
attention and the recurrences in plain ``jax.numpy``. The wrappers are
exercised by the kernel tests, which run them with ``interpret=True`` on the
CPU against the oracles in ``repro.kernels.ref``, and by
``tests/test_tpu_compile.py``, which compiles them for a described TPU v5e.

``interpret`` is an explicit static argument, default ``False``: a wrapper
never infers it from the backend, so code that meant to run the compiled
kernel fails off the chip instead of silently running the interpreter.
``use_reference`` runs the pure-jnp oracle instead of the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pl
from repro.kernels.flash_attention import flash_attention as _flash_pl
from repro.kernels.rglru import rglru_scan as _rglru_pl
from repro.kernels.wkv6 import wkv6 as _wkv6_pl


@functools.partial(jax.jit, static_argnames=("q_offset", "causal", "window",
                                             "use_reference", "interpret"))
def flash_attention(q, k, v, *, q_offset: int = 0, causal: bool = True,
                    window: Optional[int] = None, use_reference: bool = False,
                    interpret: bool = False):
    if use_reference:
        return ref.flash_attention_ref(q, k, v, q_offset=q_offset,
                                       causal=causal, window=window)
    return _flash_pl(q, k, v, q_offset=q_offset, causal=causal,
                     window=window, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_reference", "interpret"))
def decode_attention(q, k_cache, v_cache, valid, *,
                     use_reference: bool = False, interpret: bool = False):
    if use_reference:
        return ref.decode_attention_ref(q, k_cache, v_cache, valid)
    return _decode_pl(q, k_cache, v_cache, valid, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_reference", "interpret"))
def rglru_scan(a, b, h0, *, use_reference: bool = False,
               interpret: bool = False):
    if use_reference:
        return ref.rglru_scan_ref(a, b, h0)
    return _rglru_pl(a, b, h0, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("use_reference", "interpret"))
def wkv6(r, k, v, w, u, s0, *, use_reference: bool = False,
         interpret: bool = False):
    if use_reference:
        return ref.wkv6_ref(r, k, v, w, u, s0)
    return _wkv6_pl(r, k, v, w, u, s0, interpret=interpret)
