"""Compile the Pallas kernels for a described TPU v5e at real model widths.

The TPU compiler is installed even where no chip is attached, so these tests
catch what interpret mode cannot: block shapes that break the (8, 128)
tiling rule and kernels that outgrow the scoped VMEM limit. Nothing runs.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and a multi-worker
pytest run imports every test file in every worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops

PREFILL_LEN = 2048      # the prefill length the chip smoke serves
CACHE_SLOTS = 4096      # its decode cache (max_len)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


def _shapes(sharding, dtype, *shapes):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


def _compile(fn, args, **static):
    compiled = fn.lower(*args, interpret=False, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()   # the Mosaic kernel
    return compiled


@pytest.mark.parametrize("sq,q_offset", [(PREFILL_LEN, 0),
                                         (256, PREFILL_LEN)])  # hit path
def test_flash_attention_compiles_llama3_8b(one_chip, sq, q_offset):
    c = get_config("llama3-8b")
    sk = q_offset + sq
    args = _shapes(one_chip, jnp.bfloat16,
                   (1, c.num_heads, sq, c.head_dim),
                   (1, c.num_kv_heads, sk, c.head_dim),
                   (1, c.num_kv_heads, sk, c.head_dim))
    _compile(ops.flash_attention, args, q_offset=q_offset)


def test_decode_attention_compiles_llama3_8b(one_chip):
    c = get_config("llama3-8b")
    kv = (1, c.num_kv_heads, CACHE_SLOTS, c.head_dim)
    args = _shapes(one_chip, jnp.bfloat16, (1, c.num_heads, c.head_dim),
                   kv, kv)
    args += _shapes(one_chip, jnp.int32, (CACHE_SLOTS,))
    _compile(ops.decode_attention, args)


def test_rglru_scan_compiles_recurrentgemma_2b(one_chip):
    d = get_config("recurrentgemma-2b").rnn_width
    args = _shapes(one_chip, jnp.float32, (1, PREFILL_LEN, d),
                   (1, PREFILL_LEN, d), (1, d))
    _compile(ops.rglru_scan, args)


def test_wkv6_compiles_rwkv6_1_6b(one_chip):
    c = get_config("rwkv6-1.6b")
    H, hd = c.num_rwkv_heads, c.rwkv_head_dim
    seq = (1, H, PREFILL_LEN, hd)
    args = _shapes(one_chip, jnp.float32, seq, seq, seq, seq, (H, hd),
                   (1, H, hd, hd))
    _compile(ops.wkv6, args)
