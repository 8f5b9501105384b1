"""Shared pieces of the benchmark's CPU tests: the checkout root on the
path, and a reduced cell (``bench/configs/tiny.json`` and the mix below)
that runs end to end in seconds."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import configs  # noqa: E402

TINY_CONF = configs.load("tiny")
TINY_MIX = {"name": "tiny", "turns": 3, "first_prompt_tokens": [32, 48, 64],
            "append_tokens": 16, "output_tokens": 4, "gap_mean_s": 0.3,
            "rate_req_per_s": 6.0, "nominal_turn_s": 0.05, "history_s": 2,
            "horizon_s": 3, "schedule_seed": 1,
            "store": {"policy": "lru", "capacity_tokens": 400},
            "sample_requests": 4}


@pytest.fixture
def tiny_run(monkeypatch, tmp_path):
    """Runs ``yi6b.agent``'s harness at the reduced size above on the CPU,
    skipping only the look for a chip; returns what ``run_cell`` does."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from bench import run as bench_run

    def go(seed=2**31 + 7, seconds=2.0, fault=None, limit=0.05,
           judged=None):
        args = bench_run.parse(["--workload", "yi6b.agent", "--seed",
                                str(seed), "--seconds", str(seconds),
                                "--trace", "0"])
        return bench_run.run_cell(args, require_tpu=False, conf=TINY_CONF,
                                  mix=TINY_MIX, limits={"logit_gap": limit},
                                  fault=fault, judged=judged)
    yield go
    jax.config.update("jax_enable_compilation_cache", enabled)
