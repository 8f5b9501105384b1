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
  the real-mode analogue of the simulator's TTFT/TPOT split. The decode
  program picks the greedy token itself and returns it with the next
  position, so step k+1 is dispatched on step k's outputs without waiting
  for the host (at most ``_IN_FLIGHT`` steps queued); the tokens are read
  back once, after the last step.

Transformers cache per-token KV; recurrent/hybrid families (RWKV6,
Griffin/RG-LRU) use state-snapshot caching instead — the fixed-size
recurrent state after the prefix is stored, since their "KV" does not grow
with context. Drive it via ``python -m repro.launch.serve --real
--arch yi-6b`` or the quickstart example.

The jitted programs are named, so a profiler trace shows them as
``jit_prefill_miss``, ``jit_prefill_hit`` and ``jit_decode_step`` whatever
the shape. With a ``repro.obs.TraceRecorder`` attached as ``recorder``
(``None`` by default), ``generate`` also writes what it does onto the
profiler's clock and into the recorder:

* ``jax.profiler.TraceAnnotation`` spans, each carrying the request's
  ``rid`` (a per-engine counter) and ``context_key`` as metadata:
  ``engine.generate`` (the whole call) holds ``engine.lookup`` (the store
  lookup and the payload hand-over), ``engine.prefill`` (dispatch through
  ``block_until_ready``, with ``suffix_len`` and ``prefix_len``; on a hit
  the restore happens inside the prefill program, which reads the stored
  ring, so there is no restore span), ``engine.insert``, and
  ``engine.decode`` (the interval of ``decode_time_s``). Inside
  ``engine.decode`` each step is ``engine.decode_step`` (the dispatch of
  the decode program on the previous step's outputs, and the start of its
  token's copy to the host; the wait that bounds the steps in flight falls
  between these spans), and ``engine.sample`` appears twice: the host read
  of the prefill's token (with its upload as the first step's input), and,
  after the last step, the wait for that step and the one host read of the
  decoded tokens.
* one request row (``TraceRecorder.record_request``);
* a ``store_bytes`` event: the device bytes of every live entry's payload
  against the bytes the store charges for them.

With no recorder none of this happens: no span object is made and
nothing is written.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.kvstore import KVStore
from repro.models import transformer as tfm
from repro.models.transformer import init_cache

_OFF = contextlib.nullcontext()

# Decode steps in flight at once. A step writes a new cache rather than
# updating its input (the first step's input is the stored prefix's), and
# the runtime frees a step's input only once the step has run, so each
# queued step holds a cache of its own: unbounded, 32 steps of yi-6b at 19
# layers hold 4.7 GB more than one step does. With two, step k is
# dispatched while step k-1 runs, so the device need not wait for the host.
_IN_FLIGHT = 2


def _span(meta: Optional[dict], name: str, **extra):
    """The profiler span ``name`` with the request's metadata, or a shared
    no-op when the engine records nothing (``meta`` is None)."""
    if meta is None:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **meta, **extra)


def _step_inputs(token: int, pos: int):
    """A host token and position as the decode program returns them: int32
    arrays of shapes (1, 1) and (), so a step fed from the host and a step
    fed by the step before it run one compiled program."""
    return jax.device_put((np.array([[token]], np.int32), np.int32(pos)))


def payload_bytes(store: KVStore) -> int:
    """Device bytes held by the payloads of every live entry of ``store``."""
    return sum(getattr(leaf, "nbytes", 0)
               for e in store.entries.values() if e.payload is not None
               for leaf in jax.tree_util.tree_leaves(e.payload))


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
        self.recorder = None    # repro.obs.TraceRecorder, when attached
        self._rid = 0

        V = cfg.vocab_size

        def decode_step(p, c, t, pos):
            """One greedy step: ((next token (1, 1), pos + 1), cache), the
            next step's token and position first."""
            logits, c = tfm.decode_step(p, cfg, c, t, pos)
            nxt = jnp.argmax(logits[0, -1, :V]).astype(jnp.int32)
            return (nxt.reshape(1, 1), pos + 1), c
        self._decode = jax.jit(decode_step)

    # ------------------------------------------------------------------ #
    def prefill(self, tokens: jnp.ndarray, prefix_cache=None,
                prefix_len: int = 0):
        """Prefill ``tokens`` (1, S) after a cached prefix of ``prefix_len``
        tokens, through one jitted program per (S, prefix_len). Returns
        (logits (1, S, V), cache)."""
        key = (tokens.shape[1], prefix_len)
        if key not in self._prefill_cached:
            cfg, max_len = self.cfg, self.max_len

            def prefill_hit(p, b, pc):
                return tfm.prefill(p, cfg, b, max_len, prefix_cache=pc,
                                   prefix_len=prefix_len)

            def prefill_miss(p, b):
                return tfm.prefill(p, cfg, b, max_len)
            self._prefill_cached[key] = jax.jit(
                prefill_hit if prefix_len else prefill_miss)
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
        if present, prefill the suffix, then greedy-decode ``num_new``.
        With a recorder attached, also write the spans, the request row
        and the ``store_bytes`` event of the module docstring."""
        now = time.time() if now is None else now
        rec = self.recorder
        if rec is None:
            return self._generate(context_key, prompt_tokens, num_new, now,
                                  None)
        self._rid += 1
        meta = {"rid": self._rid, "context_key": context_key}
        with _span(meta, "engine.generate"):
            g = self._generate(context_key, prompt_tokens, num_new, now,
                               meta)
        rec.record_request(
            rid=self._rid, arrival_s=now,
            hit_kind="hit" if g.reused_tokens else "miss",
            matched_tokens=g.reused_tokens,
            prompt_tokens=len(prompt_tokens), output_tokens=len(g.tokens),
            prefill_s=g.prefill_time_s, decode_s=g.decode_time_s)
        rec.record_event("store_bytes", now,
                         payload_bytes=payload_bytes(self.store),
                         charged_bytes=self.store.used_bytes,
                         entries=len(self.store.entries))
        return g

    def _generate(self, context_key, prompt_tokens, num_new, now, meta
                  ) -> GenerationResult:
        recurrent = self.cfg.family in ("ssm", "hybrid")
        with _span(meta, "engine.lookup"):
            entry = self.store.lookup(context_key, len(prompt_tokens), now)
            prefix_len = 0
            prefix_cache = None
            if entry is not None and entry.payload is not None:
                plen, pcache = entry.payload
                if plen <= len(prompt_tokens):
                    prefix_len, prefix_cache = plen, pcache

        with _span(meta, "engine.prefill",
                   suffix_len=len(prompt_tokens) - prefix_len,
                   prefix_len=prefix_len):
            t0 = time.perf_counter()
            if recurrent:
                # state-snapshot caching: restore state, run the suffix
                # through decode steps (prefill from state not implemented
                # for brevity — suffix processed token by token, still
                # skipping prefix compute)
                if prefix_cache is not None:
                    cache = prefix_cache
                else:
                    cache = init_cache(self.cfg, 1, self.max_len, self.dtype)
                    prefix_len = 0
                for pos, t in enumerate(prompt_tokens[prefix_len:],
                                        prefix_len):
                    nxt, cache = self._decode(self.params, cache,
                                              *_step_inputs(t, pos))
            else:
                suffix = jnp.asarray(prompt_tokens[prefix_len:],
                                     jnp.int32)[None]
                logits, cache = self.prefill(suffix, prefix_cache, prefix_len)
            # time the device, not the dispatch
            jax.block_until_ready(nxt if recurrent else logits)
            t_prefill = time.perf_counter() - t0

        # store the full-prompt cache back (extends the prefix entry)
        with _span(meta, "engine.insert"):
            self.store.insert(context_key, len(prompt_tokens), now,
                              payload=(len(prompt_tokens), cache))

        # greedy decode: each step takes the previous step's outputs as
        # they are, so the host never waits between steps
        V = self.cfg.vocab_size
        with _span(meta, "engine.decode"):
            t1 = time.perf_counter()
            with _span(meta, "engine.sample"):
                if recurrent:
                    first = nxt[0].item()
                else:
                    first = int(jnp.argmax(logits[0, -1, :V]))
                    nxt = _step_inputs(first, len(prompt_tokens))
            toks = []
            for k in range(num_new):
                if k >= _IN_FLIGHT:
                    toks[k - _IN_FLIGHT].block_until_ready()
                with _span(meta, "engine.decode_step"):
                    nxt, cache = self._decode(self.params, cache, *nxt)
                    nxt[0].copy_to_host_async()
                toks.append(nxt[0])
            with _span(meta, "engine.sample"):
                jax.block_until_ready((nxt, cache))
                # the last step's token is not kept
                out = [first, *(t.item() for t in
                                jax.device_get(toks[:-1]))][:num_new]
            t_decode = time.perf_counter() - t1
        return GenerationResult(
            tokens=out,
            prefill_tokens_computed=len(prompt_tokens) - prefix_len,
            reused_tokens=prefix_len,
            prefill_time_s=t_prefill,
            decode_time_s=t_decode)
