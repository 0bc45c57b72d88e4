"""Cached-attention Pallas kernels — decode tokens or prompt chunks against
a ring KV cache or a paged (block-table) KV pool (causal, sliding-window,
GQA).

This is the memory-bound half of serving: every decode step streams the
whole cache through the core once per layer, so the kernel's job is to keep
that stream at HBM bandwidth while the MXU work stays tiny. TPU mapping:
grid ``(B, num_kv_blocks)``; the last axis is the sequential ("arbitrary")
reduction over cache blocks with the streaming-softmax carry (acc, m, l)
held in VMEM scratch, one carry per KV head. Each grid step fetches one
``(block_k, KV, head_dim)`` tile holding every KV head — the TPU's tiling
rule wants the last two block dims to be whole ``(KV, head_dim)`` — and
loops over the heads inside the kernel. GQA is handled by folding the
query group into the head tile: each KV head attends with a
``(group, head_dim)`` q tile against its ``(block_k, head_dim)`` k/v
slice, so KV blocks are fetched once per group rather than once per q head.

Queries generalize from one decode token to a ``T``-token prompt chunk
(chunked prefill): the q tile becomes ``(T x group, head_dim)`` with one
position per q row (row ``i`` is token ``i // group``), so the validity
mask is computed directly in the row layout — the streaming carry and the
block skip are shape-agnostic. The chunk's own K/V are appended to the
cache before the call, so intra-chunk causality is ordinary position
masking.

Positions are data, not geometry: the cache is a ring (slot = pos % width),
so causal/window masking reads the per-slot ``k_pos`` array (−1 = empty
slot) instead of assuming contiguous layout. Positions enter the kernel as
``(rows, 1)`` query columns and ``(1, block_k)`` key rows (a unit axis is
added outside, so every block's last two dims are legal TPU tiles). Blocks
whose every slot is masked (empty ring tail, outside the window) skip the
MXU work entirely via ``pl.when`` — on a cold cache only the written prefix
costs anything.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fold_queries(q, q_pos, kv: int):
    """q (B, T, H, hd) -> (B, KV, T*G, hd) with row ``i`` = token ``i // G``,
    head ``i % G`` of the group; plus each row's position (B, T*G, 1). The
    position rule is shared with the oracle (``ref.query_positions``) so
    kernel and reference can never disagree about chunk geometry."""
    from repro.kernels.ref import query_positions
    b, t, h, hd = q.shape
    g = h // kv
    qg = jnp.moveaxis(q.reshape(b, t, kv, g, hd), 2, 1).reshape(
        b, kv, t * g, hd)
    qp = query_positions(q_pos, t).reshape(b, t)
    rows = jnp.repeat(qp, g, axis=1)[:, :, None]
    return qg, rows


def _unfold_output(out, b: int, t: int, h: int):
    kv, hd = out.shape[1], out.shape[3]
    return jnp.moveaxis(out.reshape(b, kv, t, h // kv, hd), 1, 2).reshape(
        b, t, h, hd)


def _attend_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, valid, *,
                  scale: float, kv: int):
    """One cache block's streaming-softmax update for every KV head.
    ``valid`` (rows, bk) is shared by all heads."""
    for h in range(kv):
        q = q_ref[0, h]                       # (T*G, hd)
        k = k_ref[0, :, h, :]                 # (bk, hd)
        v = v_ref[0, :, h, :]
        # bf16 x bf16 products are exact in the f32 accumulator
        dt = jnp.promote_types(q.dtype, k.dtype)
        s = jax.lax.dot_general(
            q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale       # (T*G, bk)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha + pv
        m_ref[h] = m_new


def _init_carry(acc_ref, m_ref, l_ref):
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)


def _flush_carry(o_ref, acc_ref, l_ref):
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


def _valid_mask(qp, kp, window: Optional[int]):
    """qp (rows, 1) query positions, kp (1, bk) key positions (−1 = empty)
    -> (rows, bk) attendable."""
    valid = (kp >= 0) & (kp <= qp)
    if window is not None:
        valid &= kp > (qp - window)
    return valid


def _any(valid) -> jnp.ndarray:
    return jnp.max(valid.astype(jnp.int32)) > 0


def _scratch(kv: int, rows: int, hd: int):
    return [pltpu.VMEM((kv, rows, hd), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32),
            pltpu.VMEM((kv, rows, 1), jnp.float32)]


def _kernel(q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, window: Optional[int],
            num_k: int, kv: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    valid = _valid_mask(qpos_ref[0], kpos_ref[0], window)    # (T*G, bk)

    # data-dependent block skip: a ring cache is mostly empty early on, and
    # a sliding window masks all but ~window/block_k blocks
    @pl.when(_any(valid))
    def _compute():
        _attend_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, valid,
                      scale=scale, kv=kv)

    @pl.when(ik == num_k - 1)
    def _flush():
        _flush_carry(o_ref, acc_ref, l_ref)


def decode_attention(q, k, v, q_pos, k_pos, *, window: Optional[int] = None,
                     scale: Optional[float] = None, block_k: int = 128,
                     interpret: bool = False):
    """q: (B, T, H, hd) or (B, H, hd) (T = 1); k, v: (B, W, KV, hd) ring
    cache; q_pos: (B,) int32 chunk start positions (per-token positions are
    start + i) or (B, T) explicit positions; k_pos: (B, W) int32 cache-slot
    positions (−1 = empty). Returns attention output shaped like q.

    On a TPU ``block_k`` must be a multiple of 128 or at least ``W`` (the
    key-position tile is lane-major); interpret mode takes any size.
    """
    no_time = q.ndim == 3
    if no_time:
        q = q[:, None]
    b, t, h, hd = q.shape
    w, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    rows = t * (h // kv)
    scale = scale if scale is not None else hd ** -0.5
    block_k = min(block_k, w)

    pad = (-w) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nk = k.shape[1] // block_k

    qg, qp = _fold_queries(q, q_pos, kv)
    kp = jnp.asarray(k_pos, jnp.int32)[:, None, :]          # (B, 1, W)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               num_k=nk, kv=kv)
    out = pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kv, rows, hd), lambda b_, ik: (b_, 0, 0, 0)),
            pl.BlockSpec((1, block_k, kv, hd), lambda b_, ik: (b_, ik, 0, 0)),
            pl.BlockSpec((1, block_k, kv, hd), lambda b_, ik: (b_, ik, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda b_, ik: (b_, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, ik: (b_, 0, ik)),
        ],
        out_specs=pl.BlockSpec((1, kv, rows, hd),
                               lambda b_, ik: (b_, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
        scratch_shapes=_scratch(kv, rows, hd),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(qg, k, v, qp, kp)
    out = _unfold_output(out, b, t, h)
    return out[:, 0] if no_time else out


# ---------------------------------------------------------------------------
# Paged (block-table) variant
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, q_ref, k_ref, v_ref, qpos_ref, kpos_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, scale: float,
                  window: Optional[int], num_k: int, kv: int):
    ib, ik = pl.program_id(0), pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    valid = _valid_mask(qpos_ref[0], kpos_ref[0], window)    # (T*G, bs)

    # skip unallocated table entries (−1: the DMA fetched the trash block
    # in their place) and fully-masked blocks entirely: a slot's table only
    # covers its live tokens, so grid steps past the allocated prefix cost
    # no MXU work
    @pl.when(jnp.logical_and(bt_ref[ib, ik] >= 0, _any(valid)))
    def _compute():
        _attend_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, valid,
                      scale=scale, kv=kv)

    @pl.when(ik == num_k - 1)
    def _flush():
        _flush_carry(o_ref, acc_ref, l_ref)


def paged_decode_attention(q, k, v, q_pos, k_pos, block_tables, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False):
    """Paged cached attention: gather K/V through a block table per grid step.

    q: (B, T, H, hd) or (B, H, hd) (T = 1); k, v: (N, bs, KV, hd) global
    block pool (block 0 is the engines' trash block); q_pos: (B,) chunk
    start positions or (B, T) per-token positions; k_pos: (N, bs) per-token
    positions (−1 = never written); block_tables: (B, M) int32 physical
    block ids per slot (−1 = unallocated). Returns output shaped like q.

    Same streaming-softmax carry, GQA group folding and masked-block skip as
    the ring kernel; the only difference is that the KV tile for grid step
    ``ik`` is DMA'd from pool block ``block_tables[b, ik]`` (scalar-prefetch
    index map) instead of a contiguous slice of a per-slot ring.
    """
    no_time = q.ndim == 3
    if no_time:
        q = q[:, None]
    b, t, h, hd = q.shape
    bs, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    rows = t * (h // kv)
    m = block_tables.shape[1]
    scale = scale if scale is not None else hd ** -0.5

    qg, qp = _fold_queries(q, q_pos, kv)
    kp = jnp.asarray(k_pos, jnp.int32)[:, None, :]          # (N, 1, bs)
    bt = jnp.asarray(block_tables, jnp.int32)

    def pool_block(b_, ik, bt_):
        return jnp.maximum(bt_[b_, ik], 0)

    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               num_k=m, kv=kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, m),
        in_specs=[
            pl.BlockSpec((1, kv, rows, hd),
                         lambda b_, ik, bt_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd),
                         lambda b_, ik, bt_: (pool_block(b_, ik, bt_),
                                              0, 0, 0)),
            pl.BlockSpec((1, bs, kv, hd),
                         lambda b_, ik, bt_: (pool_block(b_, ik, bt_),
                                              0, 0, 0)),
            pl.BlockSpec((1, rows, 1), lambda b_, ik, bt_: (b_, 0, 0)),
            pl.BlockSpec((1, 1, bs),
                         lambda b_, ik, bt_: (pool_block(b_, ik, bt_), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, kv, rows, hd),
                               lambda b_, ik, bt_: (b_, 0, 0, 0)),
        scratch_shapes=_scratch(kv, rows, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(bt, qg, k, v, qp, kp)
    out = _unfold_output(out, b, t, h)
    return out[:, 0] if no_time else out
