"""Compile the Pallas kernels for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jaxlib and compiles for a topology that
is only described, so these tests catch what interpret mode cannot: block
shapes the TPU tiling rule refuses, VMEM overuse, unsupported vector ops.
Nothing runs; the results are checked on the chip by ``chip_smoke.py``.

The topology is described inside a module fixture (never at import), so
with several pytest workers only the worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cascade_gate import cascade_gate
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rglru_scan import rglru_scan

# (num_heads, num_kv_heads, head_dim) at published widths
WIDTHS = {"smollm-135m": (9, 3, 64), "qwen3-4b": (32, 8, 128)}
# T = 1 is a decode step; 64 is the largest chunk bucket of a
# chunk_tokens=64 scheduler (chunk_buckets(64) = [8, 16, 32, 64])
QUERY_TOKENS = (1, 64)
SLOTS, RING_WIDTH = 4, 512
POOL_BLOCKS, BLOCK_SIZE, TABLE_WIDTH = 64, 16, 32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-device compile is written to the persistent cache but
    # cannot be read back without the chip: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("t", QUERY_TOKENS)
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_ring_decode_attention_compiles(one_chip, arch, t):
    h, kv, hd = WIDTHS[arch]
    bf, i32 = jnp.bfloat16, jnp.int32
    _compile(lambda q, k, v, qp, kp: decode_attention(q, k, v, qp, kp),
             one_chip,
             ((SLOTS, t, h, hd), bf), ((SLOTS, RING_WIDTH, kv, hd), bf),
             ((SLOTS, RING_WIDTH, kv, hd), bf), ((SLOTS,), i32),
             ((SLOTS, RING_WIDTH), i32))


@pytest.mark.parametrize("t", QUERY_TOKENS)
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_compiles(one_chip, arch, t):
    h, kv, hd = WIDTHS[arch]
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = (POOL_BLOCKS, BLOCK_SIZE, kv, hd)
    _compile(lambda q, k, v, qp, kp, bt: paged_decode_attention(
                 q, k, v, qp, kp, bt),
             one_chip,
             ((SLOTS, t, h, hd), bf), (pool, bf), (pool, bf),
             ((SLOTS,), i32), ((POOL_BLOCKS, BLOCK_SIZE), i32),
             ((SLOTS, TABLE_WIDTH), i32))


# the benchmark cells' paged decode: (slots, pool blocks) at table width 128
# (max_seq_len 2048 in blocks of 16); T = 256 is their prefill chunk
CELL_POOLS = ((12, 1600), (32, 4097))


@pytest.mark.parametrize("t", (1, 256))
@pytest.mark.parametrize("slots,blocks", CELL_POOLS)
@pytest.mark.parametrize("arch", sorted(WIDTHS))
def test_paged_decode_attention_compiles_at_cell_shapes(one_chip, arch,
                                                        slots, blocks, t):
    h, kv, hd = WIDTHS[arch]
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = (blocks, BLOCK_SIZE, kv, hd)
    _compile(lambda q, k, v, qp, kp, bt: paged_decode_attention(
                 q, k, v, qp, kp, bt),
             one_chip,
             ((slots, t, h, hd), bf), (pool, bf), (pool, bf),
             ((slots,), i32), ((blocks, BLOCK_SIZE), i32),
             ((slots, 2048 // BLOCK_SIZE), i32))


# Kernels no serving path calls (nothing in src/ reaches ops.attention,
# ops.rglru or ops.gate); the TPU compiler still refuses them.
@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="block (1, block_q, 1, hd) on (B, S, H, hd): the "
                          "last two block dims must be divisible by 8 and "
                          "128 or equal the array's")
def test_flash_attention_compiles(one_chip):
    h, kv, hd = WIDTHS["smollm-135m"]
    bf = jnp.bfloat16
    _compile(lambda q, k, v: flash_attention(q, k, v), one_chip,
             ((1, 256, h, hd), bf), ((1, 256, kv, hd), bf),
             ((1, 256, kv, hd), bf))


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="h0 block (1, block_w) on (B, W): the last two "
                          "block dims must be divisible by 8 and 128 or "
                          "equal the array's")
def test_rglru_scan_compiles(one_chip):
    f32 = jnp.float32
    _compile(rglru_scan, one_chip, ((2, 256, 512), f32),
             ((2, 256, 512), f32), ((2, 512), f32))


@pytest.mark.xfail(strict=True, raises=ValueError,
                   reason="the per-tile route counts are scalar stores to a "
                          "VMEM block: 'Cannot store scalars to VMEM'")
def test_cascade_gate_compiles(one_chip):
    _compile(cascade_gate, one_chip, ((256, 49152), jnp.float32))
