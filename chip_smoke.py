#!/usr/bin/env python3
"""Smoke run of the real serving path on one TPU chip.

Serves Llama-3-8B (random bf16 weights from a seed) at its published
widths through ``repro.launch.serve.run_real``: a JAX model behind the
GreenCache ``KVStore``, where a cache hit restores the stored prefix KV and
prefills only the uncached suffix. Two sessions of two turns each, then one
unshared request:

* turn 1: a 2048-token prompt, 32 new tokens (a miss);
* turn 2: turn 1's prompt, its output and 256 new user tokens, 32 new
  tokens (must reuse the 2048 cached tokens);
* control: an unshared 512-token prompt (must miss).

It checks the reuse counts, that the measured pass compiled nothing and
repeated the warm-up pass's tokens, and that turn 2's hit-path prefill
logits agree with a full prefill of the same prompt. It prints compile
time apart from the per-request prefill and decode seconds, and the
device's peak memory. The last line is one JSON object naming the device.

    python chip_smoke.py

It needs a TPU and exits non-zero, naming the platform it found, without
one. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX keeps its compile
cache there; otherwise the cache is ``<checkout>/.jax_cache``.
"""
import dataclasses
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.serve import (SESSIONS, SessionTraffic,  # noqa: E402
                                compare_hit_prefill, run_real,
                                use_compile_cache)

ARCH = "llama3-8b"
LAYERS = 16
DEPTH_CUT = ("depth cut to 16 of 32 layers: full depth is 16.06 GB of bf16 "
             "weights, and its decode step's arguments plus outputs (17.1 GB)"
             " leave no room on a 16 GiB chip for the store's KV payloads; "
             "the other 16 layers would be a second pipeline stage")
MAX_LEN = 4096
TRAFFIC = SessionTraffic(prompt_len=2048, followup_len=256, control_len=512,
                         num_new=32)
# Hit-path and full prefill do the same arithmetic in differently shaped
# programs (288 vs 2336 query rows; the prefix KV comes from a 2048-row
# program), so bf16 roundings differ, by up to 2**-9 relative each, and the
# differences grow through the residual layers. On a 16-layer bf16 model at
# d_model 256 on the CPU the two paths differ by 0.009 (relative L2 over
# the suffix logits); shifting the suffix's RoPE positions by one token
# makes it 0.32. The tolerance sits between the two.
REL_L2_TOL = 0.05


def smoke(cfg, traffic: SessionTraffic, *, dtype, max_len: int,
          rel_l2_tol: float) -> dict:
    """Serve ``traffic`` on ``cfg`` and print what was served. Returns the
    checks that failed, each with what was seen; empty when all passed."""
    run = run_real(cfg, traffic, dtype=dtype, max_len=max_len,
                   store_bytes=traffic.store_bytes(cfg))
    res = run.results
    weights = sum(x.nbytes for x in jax.tree.leaves(run.engine.params))
    print(f"weights: {weights} bytes")
    print(f"set-up (init + warm-up pass): {run.setup_s} s, of which compile "
          f"{run.setup.seconds} s for {run.setup.programs} programs "
          f"({run.setup.cache_hits} read from the persistent cache)")
    cmp = compare_hit_prefill(run.engine, run.prompts["s0/turn2"],
                              traffic.prompt_len)
    print(f"turn-2 hit-path vs full prefill logits: {cmp} "
          f"(tolerance rel_l2 <= {rel_l2_tol})")

    failed = {}
    for s in SESSIONS:
        if res[f"{s}/turn1"].reused_tokens != 0:
            failed[f"{s}/turn1 misses"] = res[f"{s}/turn1"].reused_tokens
        if res[f"{s}/turn2"].reused_tokens != traffic.prompt_len:
            failed[f"{s}/turn2 reuses {traffic.prompt_len}"] = \
                res[f"{s}/turn2"].reused_tokens
    if res["control"].reused_tokens != 0:
        failed["control misses"] = res["control"].reused_tokens
    if run.window.programs:
        failed["measured pass compiles nothing"] = run.window.programs
    for name, r in res.items():
        if r.tokens != run.warmup[name].tokens:
            failed[f"{name} repeats the warm-up tokens"] = r.tokens[:8]
    if not (cmp["finite"] and cmp["rel_l2"] <= rel_l2_tol):
        failed["hit-path logits match full prefill"] = cmp
    return failed


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")
    cfg = dataclasses.replace(get_config(ARCH), num_layers=LAYERS)
    print(f"config: {ARCH} bf16 d_model {cfg.d_model} heads {cfg.num_heads} "
          f"kv_heads {cfg.num_kv_heads} head_dim {cfg.head_dim} d_ff "
          f"{cfg.d_ff} vocab {cfg.vocab_size} rope_theta {cfg.rope_theta} "
          f"layers {cfg.num_layers}; {DEPTH_CUT}")
    print(f"traffic: {TRAFFIC}, max_len {MAX_LEN}, store "
          f"{TRAFFIC.store_bytes(cfg)} bytes")
    failed = smoke(cfg, TRAFFIC, dtype=jnp.bfloat16, max_len=MAX_LEN,
                   rel_l2_tol=REL_L2_TOL)
    print(f"peak_bytes_in_use: {dev.memory_stats()['peak_bytes_in_use']}")
    if failed:
        print(f"chip_smoke: FAILED {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
