"""Real-execution serving: an actual JAX model behind the GreenCache store.

This is the paper's mechanism running for real (at reduced scale on CPU,
published widths on TPU; see ``chip_smoke.py``), where the rest of the repo
simulates it analytically:

* ``generate(context_key, tokens, num_new)`` looks the context prefix up
  in the same ``repro.core.kvstore.KVStore`` the simulator uses. The KV
  caches of context prefixes are *stored as stacked JAX arrays* in the
  entry payload and *restored on hit*, so a hit prefills only the uncached
  suffix (flash-attention queries run at offset ``prefix_len`` against the
  restored keys/values) — numerically identical to full prefill
  (``tests/test_realexec.py`` asserts logit equality).
* After prefill the full context+question prefix is (re)inserted, so the
  next conversation turn reuses it — the suffix-only prefill whose saved
  compute is the operational-carbon term of the cache/carbon tradeoff.
* Decode runs step-wise with the standard incremental KV cache and
  returns per-phase wall times (``prefill_time_s`` / ``decode_time_s``),
  the real-mode analogue of the simulator's TTFT/TPOT split.

Transformers cache per-token KV; recurrent/hybrid families (RWKV6,
Griffin/RG-LRU) use state-snapshot caching instead — the fixed-size
recurrent state after the prefix is stored, since their "KV" does not grow
with context. Drive it via ``python -m repro.launch.serve --real
--arch yi-6b`` or the quickstart example.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.kvstore import KVStore
from repro.models.transformer import (decode_step, init_cache, prefill)


@dataclass
class GenerationResult:
    tokens: List[int]
    prefill_tokens_computed: int      # uncached tokens actually prefilled
    reused_tokens: int
    prefill_time_s: float
    decode_time_s: float


class RealExecutionEngine:
    def __init__(self, cfg: ModelConfig, params, store: KVStore, *,
                 max_len: int = 512, dtype=jnp.float32):
        self.cfg = cfg
        self.params = params
        self.store = store
        self.max_len = max_len
        self.dtype = dtype
        self._prefill_cached = {}
        self._decode = jax.jit(
            lambda p, c, t, pos: decode_step(p, cfg, c, t, pos))

    # ------------------------------------------------------------------ #
    def prefill(self, tokens: jnp.ndarray, prefix_cache=None,
                prefix_len: int = 0):
        """Prefill ``tokens`` (1, S) after a cached prefix of ``prefix_len``
        tokens, through one jitted program per (S, prefix_len). Returns
        (logits (1, S, V), cache)."""
        key = (tokens.shape[1], prefix_len)
        if key not in self._prefill_cached:
            cfgl = self.cfg
            if prefix_len:
                fn = lambda p, b, pc: prefill(p, cfgl, b, self.max_len,
                                              prefix_cache=pc,
                                              prefix_len=prefix_len)
            else:
                fn = lambda p, b: prefill(p, cfgl, b, self.max_len)
            self._prefill_cached[key] = jax.jit(fn)
        fn = self._prefill_cached[key]
        batch = {"tokens": tokens}
        if prefix_len:
            return fn(self.params, batch, prefix_cache)
        return fn(self.params, batch)

    # ------------------------------------------------------------------ #
    def generate(self, context_key: str, prompt_tokens: List[int],
                 num_new: int = 8, now: Optional[float] = None
                 ) -> GenerationResult:
        """Serve one request: reuse the cached prefix KV for ``context_key``
        if present, prefill the suffix, then greedy-decode ``num_new``."""
        now = time.time() if now is None else now
        recurrent = self.cfg.family in ("ssm", "hybrid")
        entry = self.store.lookup(context_key, len(prompt_tokens), now)
        prefix_len = 0
        prefix_cache = None
        if entry is not None and entry.payload is not None:
            plen, pcache = entry.payload
            if plen <= len(prompt_tokens):
                prefix_len, prefix_cache = plen, pcache

        t0 = time.perf_counter()
        if recurrent:
            # state-snapshot caching: restore state, run the suffix through
            # decode steps (prefill from state not implemented for brevity —
            # suffix processed token by token, still skipping prefix compute)
            if prefix_cache is not None:
                cache = prefix_cache
            else:
                cache = init_cache(self.cfg, 1, self.max_len, self.dtype)
                prefix_len = 0
            logits = None
            pos = prefix_len
            for t in prompt_tokens[prefix_len:]:
                logits, cache = self._decode(
                    self.params, cache, jnp.array([[t]], jnp.int32),
                    jnp.asarray(pos))
                pos += 1
        else:
            suffix = jnp.asarray(prompt_tokens[prefix_len:],
                                 jnp.int32)[None]
            logits, cache = self.prefill(suffix, prefix_cache, prefix_len)
            pos = len(prompt_tokens)
        logits.block_until_ready()      # time the device, not the dispatch
        t_prefill = time.perf_counter() - t0

        # store the full-prompt cache back (extends the prefix entry)
        self.store.insert(context_key, len(prompt_tokens), now,
                          payload=(len(prompt_tokens), cache))

        # greedy decode
        t1 = time.perf_counter()
        out = []
        tok = int(jnp.argmax(logits[0, -1, :self.cfg.vocab_size]))
        for _ in range(num_new):
            out.append(tok)
            logits, cache = self._decode(
                self.params, cache, jnp.array([[tok]], jnp.int32),
                jnp.asarray(pos))
            pos += 1
            tok = int(jnp.argmax(logits[0, -1, :self.cfg.vocab_size]))
        return GenerationResult(
            tokens=out,
            prefill_tokens_computed=len(prompt_tokens) - prefix_len,
            reused_tokens=prefix_len,
            prefill_time_s=t_prefill,
            decode_time_s=time.perf_counter() - t1)
