"""Jit'd dispatch wrappers over the Pallas kernels.

On TPU the Pallas kernels run natively; on CPU the default is the jnp
oracle (running full models through interpret mode would be pathologically
slow), while tests force ``use_kernel=True`` with ``interpret=True`` to
exercise the kernel bodies.

A Pallas kernel is a single-device program: GSPMD cannot split it, and
would gather its operands onto every device. Under tensor-parallel decode
the caller passes ``heads=(mesh, axis)``, the mesh axis its K/V pools
split their heads over, and the cached-attention kernels run per shard
inside ``shard_map`` — attention is independent per KV head, so each
device attends its own heads over its own slice of the KV cache.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import PartitionSpec as PS

from repro.kernels import ref as _ref
from repro.kernels.cascade_gate import cascade_gate as _gate_kernel
from repro.kernels.decode_attention import decode_attention as _da_kernel
from repro.kernels.decode_attention import (paged_decode_attention
                                            as _pda_kernel)
from repro.kernels.flash_attention import flash_attention as _fa_kernel
from repro.kernels.rglru_scan import rglru_scan as _rglru_kernel


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _heads_per_shard(kernel, heads, q, k, v, *rest):
    """``kernel(q, k, v, *rest)``, per shard of ``heads = (mesh, axis)``
    when given: q (..., H, hd) splits H and k/v split their KV heads (dim
    2) over ``axis``, which must divide them; ``rest`` (positions, block
    tables) is replicated."""
    if heads is None:
        return kernel(q, k, v, *rest)
    mesh, axis = heads
    q_spec = PS(*([None] * (q.ndim - 2)), axis, None)
    kv_spec = PS(None, None, axis, None)
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec) + (PS(),) * len(rest),
        out_specs=q_spec, check_vma=False)(q, k, v, *rest)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "use_kernel", "interpret"))
def attention(q, k, v, *, causal: bool = True,
              window: Optional[int] = None,
              use_kernel: Optional[bool] = None,
              interpret: Optional[bool] = None):
    use = _on_tpu() if use_kernel is None else use_kernel
    if use:
        return _fa_kernel(q, k, v, causal=causal, window=window,
                          interpret=not _on_tpu() if interpret is None
                          else interpret)
    return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "use_kernel", "interpret",
                                             "heads"))
def decode_attn(q, k, v, q_pos, k_pos, *, window: Optional[int] = None,
                scale: Optional[float] = None,
                use_kernel: Optional[bool] = None,
                interpret: Optional[bool] = None, heads=None):
    use = _on_tpu() if use_kernel is None else use_kernel
    if use:
        kernel = functools.partial(
            _da_kernel, window=window, scale=scale,
            interpret=not _on_tpu() if interpret is None else interpret)
        return _heads_per_shard(kernel, heads, q, k, v, q_pos, k_pos)
    return _ref.decode_attention_ref(q, k, v, q_pos, k_pos, window=window,
                                     scale=scale)


@functools.partial(jax.jit, static_argnames=("window", "scale",
                                             "use_kernel", "interpret",
                                             "heads"))
def paged_decode_attn(q, k, v, q_pos, k_pos, block_tables, *,
                      window: Optional[int] = None,
                      scale: Optional[float] = None,
                      use_kernel: Optional[bool] = None,
                      interpret: Optional[bool] = None, heads=None):
    """Paged-pool variant of ``decode_attn``: k/v/k_pos are the global block
    pool (N, bs, ...) and ``block_tables`` (B, M) maps each slot's logical
    blocks to physical pool blocks (−1 = unallocated)."""
    use = _on_tpu() if use_kernel is None else use_kernel
    if use:
        kernel = functools.partial(
            _pda_kernel, window=window, scale=scale,
            interpret=not _on_tpu() if interpret is None else interpret)
        return _heads_per_shard(kernel, heads, q, k, v, q_pos, k_pos,
                                block_tables)
    return _ref.paged_decode_attention_ref(q, k, v, q_pos, k_pos,
                                           block_tables, window=window,
                                           scale=scale)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def rglru(a, b, h0, *, use_kernel: Optional[bool] = None,
          interpret: Optional[bool] = None):
    use = _on_tpu() if use_kernel is None else use_kernel
    if use:
        return _rglru_kernel(a, b, h0,
                             interpret=not _on_tpu() if interpret is None
                             else interpret)
    return _ref.rglru_scan_ref(a, b, h0)


@functools.partial(jax.jit, static_argnames=("hi", "lo", "use_kernel",
                                             "interpret"))
def gate(logits, *, hi: float = 0.8, lo: float = 0.1,
         use_kernel: Optional[bool] = None,
         interpret: Optional[bool] = None):
    use = _on_tpu() if use_kernel is None else use_kernel
    if use:
        return _gate_kernel(logits, hi=hi, lo=lo,
                            interpret=not _on_tpu() if interpret is None
                            else interpret)
    from repro.cascade.gate import GateThresholds
    import jax.numpy as jnp
    out = _ref.cascade_gate_ref(
        logits, GateThresholds(jnp.float32(hi), jnp.float32(lo)))
    return out["conf"], out["routes"], out["counts"]
