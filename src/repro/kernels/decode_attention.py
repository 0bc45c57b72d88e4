"""Cached-attention Pallas kernels — decode tokens or prompt chunks against
a ring KV cache or a paged (block-table) KV pool (causal, sliding-window,
GQA).

This is the memory-bound half of serving: every decode step streams the
live cache through the core once per layer, so the kernel's job is to keep
that stream at HBM bandwidth while the MXU work stays tiny. The streaming-
softmax carry (acc, m, l) sits in VMEM scratch, one carry per KV head, and
is updated one compute block of keys at a time. A KV tile holds every KV
head — the TPU's tiling rule wants the last two block dims to be whole
``(KV, head_dim)`` — and the kernel loops over the heads inside. GQA is
handled by folding the query group into the head tile: each KV head
attends with a ``(group, head_dim)`` q tile against its ``(keys,
head_dim)`` k/v slice, so KV is fetched once per group rather than once
per q head.

Ring cache: grid ``(B, num_kv_blocks)``, the last axis the sequential
("arbitrary") reduction over ``block_k``-wide slices of each slot's ring.

Paged pool: grid ``(B, ceil(M / P))``. A slot's queries can see only the
table entries ``live_pages`` bounds (up to the page of the last query;
from the page of the first query's window start), so step ``i`` of slot
``b`` is the ``i``-th block of ``P`` live entries (``_pages_per_block``:
128 keys, fewer for a long chunk). Each of the block's pages is its own
pipelined input, whose pool block comes from a work list in scalar
prefetch; steps past a slot's live pages keep every input's block, so
they copy nothing and compute nothing. Entries that are unallocated (−1)
are never fetched.

Queries generalize from one decode token to a ``T``-token prompt chunk
(chunked prefill): the q tile becomes ``(T x group, head_dim)`` with one
position per q row (row ``i`` is token ``i // group``), so the validity
mask is computed directly in the row layout — the streaming carry and the
block skip are shape-agnostic. The chunk's own K/V are appended to the
cache before the call, so intra-chunk causality is ordinary position
masking.

Positions are data, not geometry: the cache is a ring (slot = pos % width)
or a pool of pages, so causal/window masking reads the per-token ``k_pos``
array (−1 = empty) instead of assuming contiguous layout. Positions enter
the kernel as ``(rows, 1)`` query columns and ``(1, keys)`` key rows (a
unit axis is added outside, so every block's last two dims are legal TPU
tiles). Blocks whose every key is masked (empty ring tail, outside the
window) skip the MXU work entirely via ``pl.when``.
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


def _softmax_update(h: int, q, k, v, acc_ref, m_ref, l_ref, valid, *,
                    scale: float):
    """KV head ``h``'s streaming-softmax update: q (T*G, hd) against k, v
    (keys, hd); ``valid`` (T*G, keys)."""
    # bf16 x bf16 products are exact in the f32 accumulator
    dt = jnp.promote_types(q.dtype, k.dtype)
    s = jax.lax.dot_general(
        q.astype(dt), k.astype(dt), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale           # (T*G, keys)
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


def _attend_block(q_ref, k_ref, v_ref, acc_ref, m_ref, l_ref, valid, *,
                  scale: float, kv: int):
    """One cache block's streaming-softmax update for every KV head.
    ``valid`` (rows, bk) is shared by all heads."""
    for h in range(kv):
        _softmax_update(h, q_ref[0, h], k_ref[0, :, h, :], v_ref[0, :, h, :],
                        acc_ref, m_ref, l_ref, valid, scale=scale)


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

# a compute block gathers enough pages to hold this many keys (one lane
# tile of scores) ...
_BLOCK_KEYS = 128
# ... unless its f32 score tile (T*G rows x keys) would pass this many bytes
_SCORE_BYTES = 2 << 20
# a long chunk holds its queries, output and carry whole in VMEM: at
# qwen3-4b's widths, 256 tokens in a batch of 32 ask 17 MB, past the
# default 16 MB scope (a v5e core has 128 MiB)
_VMEM_LIMIT = 32 << 20


def live_pages(first_pos, last_pos, block_size: int, num_pages: int,
               window: Optional[int] = None, xp=jnp):
    """Table entries ``[lo, hi)`` that hold every key the queries at
    positions ``first_pos .. last_pos`` can see. Page ``j`` holds positions
    ``[j * bs, (j + 1) * bs)``: no page past the last query's holds a
    visible key, and under a window no page before the first query's
    window start does. ``xp`` is ``jnp`` on traced positions and ``np`` on
    the host's."""
    hi = xp.minimum(xp.maximum(last_pos // block_size + 1, 0), num_pages)
    if window is None:
        return 0 * hi, hi
    lo = xp.minimum(xp.maximum((first_pos - window + 1) // block_size, 0),
                    num_pages)
    return lo, hi


def _pages_per_block(block_size: int, rows: int) -> int:
    """Pages one compute block gathers: ``_BLOCK_KEYS`` keys, fewer where a
    long chunk's score tile would crowd VMEM."""
    p = -(-_BLOCK_KEYS // block_size)
    return max(1, min(p, _SCORE_BYTES // (4 * rows * block_size)))


def _work_items(block_tables, lo, hi, pages: int):
    """The grid's work list: one item per compute block of each slot's
    live entries ``[lo, hi)``, a slot's items consecutive, and one item
    for a slot with none (it writes the slot's zero output). Item ``i`` of
    slot ``b`` covers table entries ``lo + i * pages + p``. Returns the
    item count (1,), each item's slot (−1 past the count), and per item
    and page input ``(items, pages)`` whether the entry is used (inside
    ``[lo, hi)`` and allocated) and the pool block the input holds: the
    entry's block where used, else the block the input last read (or
    first will read), so that the pipeline, which copies only when a
    block index changes, fetches nothing for it. An input no item uses
    holds a live block (the trash block 0 where nothing is live): the
    pipeline fills every input at the first step."""
    b, m = block_tables.shape
    cap = b * (-(-m // pages))               # items at most: every entry
    i32, lax = jnp.int32, jax.lax
    blocks = jnp.maximum(lax.div(hi - lo + pages - 1, pages), 1)
    ends = lax.cumsum(blocks)
    count = ends[b - 1]
    item = lax.broadcasted_iota(i32, (cap, pages), 0)
    page = lax.broadcasted_iota(i32, (cap, pages), 1)
    # the slot of an item: how many slots end at or before it
    slot = lax.reduce((item[:, :1] >= ends[None, :]).astype(i32), i32(0),
                      lax.add, (1,))
    slot = jnp.minimum(slot, b - 1)[:, None]
    j = (lo - pages * (ends - blocks))[slot] + pages * item + page
    phys = block_tables.reshape(-1)[slot * m + jnp.minimum(j, m - 1)]
    use = (item < count) & (j < hi[slot]) & (phys >= 0)
    last = lax.cummax(lax.select(use, item, lax.full_like(item, -1)), axis=0)
    first = lax.cummin(lax.select(use, item, lax.full_like(item, cap)),
                       axis=0, reverse=True)
    seen = last >= 0
    held = phys.reshape(-1)[lax.select(seen, last, first) * pages + page]
    some = lax.full_like(held, jnp.max(lax.select(use, phys,
                                                  lax.full_like(phys, 0))))
    held = lax.select(use, phys, lax.select(seen | (first < cap), held, some))
    return (count[None], lax.select(item[:, 0] < count, slot[:, 0],
                                    lax.full_like(slot[:, 0], -1)), use, held)


def _attend_pages(q_ref, k_refs, v_refs, acc_ref, m_ref, l_ref, valid, *,
                  scale: float, kv: int):
    """``_attend_block`` over a compute block gathered from several page
    inputs: each head's keys and values are the pages' slices stacked."""
    ks = jnp.concatenate([r[0] for r in k_refs], axis=0)    # (keys, KV, hd)
    vs = jnp.concatenate([r[0] for r in v_refs], axis=0)
    for h in range(kv):
        _softmax_update(h, q_ref[0, h], ks[:, h, :], vs[:, h, :], acc_ref,
                        m_ref, l_ref, valid, scale=scale)


def _paged_kernel(slot_ref, held_ref, q_ref, qpos_ref, kpos_ref, *refs,
                  scale: float, window: Optional[int], kv: int, pages: int,
                  cap: int):
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    o_ref, acc_ref, m_ref, l_ref = refs[2 * pages:]
    item = pl.program_id(0)
    slot = slot_ref[item]

    @pl.when((item == 0) | (slot_ref[jnp.maximum(item - 1, 0)] != slot))
    def _init():
        _init_carry(acc_ref, m_ref, l_ref)

    # an unused page's keys come as position −1: a slot's item with no
    # live page computes nothing
    valid = _valid_mask(qpos_ref[0], kpos_ref[0], window)   # (T*G, P*bs)

    @pl.when(_any(valid))
    def _compute():
        _attend_pages(q_ref, k_refs, v_refs, acc_ref, m_ref, l_ref, valid,
                      scale=scale, kv=kv)

    # items past the count carry slot −1, so a slot's last item sees the
    # next one change
    @pl.when((item + 1 == cap)
             | (slot_ref[jnp.minimum(item + 1, cap - 1)] != slot))
    def _flush():
        _flush_carry(o_ref, acc_ref, l_ref)


def paged_decode_attention(q, k, v, q_pos, k_pos, block_tables, *,
                           window: Optional[int] = None,
                           scale: Optional[float] = None,
                           interpret: bool = False):
    """Paged cached attention: gather each slot's live pages from the pool.

    q: (B, T, H, hd) or (B, H, hd) (T = 1); k, v: (N, bs, KV, hd) global
    block pool (block 0 is the engines' trash block); q_pos: (B,) chunk
    start positions or (B, T) per-token positions; k_pos: (N, bs) per-token
    positions (−1 = never written); block_tables: (B, M) int32 physical
    block ids per slot (−1 = unallocated). Returns output shaped like q.

    Same streaming-softmax carry, GQA group folding and masked-block skip as
    the ring kernel. The grid is the work list of ``_work_items``, its
    length data: one step per compute block of ``P`` (``_pages_per_block``)
    live table entries (``live_pages``), a slot's steps consecutive. The
    pool is passed ``P`` times, once per page of a block, each input's
    block index read from the work list by scalar prefetch; the block's key
    positions come gathered in one row. Entries outside the live range or
    unallocated are neither fetched nor computed.
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
    pages = _pages_per_block(bs, rows)
    cap = b * -(-m // pages)

    qg, qp = _fold_queries(q, q_pos, kv)
    bt = jnp.asarray(block_tables, jnp.int32)
    lo, hi = live_pages(jnp.min(qp[:, :, 0], axis=1),
                        jnp.max(qp[:, :, 0], axis=1), bs, m, window)
    count, slot, use, held = _work_items(bt, lo, hi, pages)
    # each item's key positions in one (1, P * bs) row; −1 where unused
    kp = jnp.where(use[..., None], jnp.asarray(k_pos, jnp.int32)[held], -1)
    kp = kp.reshape(cap, 1, pages * bs)

    def page_spec(block, p):
        """Input ``p`` of a compute block: the pool block it holds."""
        return pl.BlockSpec(block, lambda item, slot_ref, held_ref: (
            held_ref[item * pages + p],) + (0,) * (len(block) - 1))

    def slot_spec(block):
        return pl.BlockSpec(block, lambda item, slot_ref, held_ref: (
            jnp.maximum(slot_ref[item], 0),) + (0,) * (len(block) - 1))

    pool = [page_spec((1, bs, kv, hd), p) for p in range(pages)]
    kernel = functools.partial(_paged_kernel, scale=scale, window=window,
                               kv=kv, pages=pages, cap=cap)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count[0],),
        in_specs=[
            slot_spec((1, kv, rows, hd)),
            slot_spec((1, rows, 1)),
            pl.BlockSpec((1, 1, pages * bs),
                         lambda item, *_: (item, 0, 0)),
            *pool, *pool,
        ],
        out_specs=slot_spec((1, kv, rows, hd)),
        scratch_shapes=_scratch(kv, rows, hd),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(slot, held.reshape(-1), qg, qp, kp, *[k] * pages, *[v] * pages)
    out = _unfold_output(out, b, t, h)
    return out[:, 0] if no_time else out
