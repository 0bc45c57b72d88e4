"""Paged decode-attention Pallas kernel vs the jnp oracle (interpret mode):
block-table gather, GQA/MQA, sliding window, partially-filled tail blocks,
unallocated table entries, reused-pool fragmentation, freed slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as dattn
from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention

CASES = [
    # (h, kv, hd, bs, window, fills) — fills: live tokens per slot
    (4, 4, 32, 16, None, (64, 64)),       # MHA, full tables
    (4, 2, 32, 16, None, (26, 64)),       # GQA g=2, ragged final block
    (3, 1, 32, 16, None, (48, 5)),        # MQA, short slot
    (4, 4, 32, 16, 24, (64, 64)),         # sliding window
    (8, 2, 64, 32, 16, (96, 40)),         # window + GQA g=4, bs=32
    (4, 2, 16, 8, None, (1, 63)),         # single-token slot, bs=8
]


def _paged_cache(rng, kv, hd, bs, fills, *, dtype=jnp.float32,
                 scatter_seed=None, width=None, lookahead=0):
    """Build a pool + tables as the engine would: block 0 is trash, each
    slot's tokens [0, fill) land at (table[slot, p // bs], p % bs). With
    ``scatter_seed`` the physical block ids are shuffled (fragmented pool,
    as after many alloc/free cycles). ``width`` sets the table width (the
    engine's ``max_seq_len / bs``); ``lookahead`` more blocks per slot are
    allocated past its tokens, their positions wiped (−1) as ``begin_slot``
    leaves them and their K/V NaN, so a kernel that computed with one
    would return NaN."""
    b = len(fills)
    m = width or max(-(-f // bs) for f in fills)
    held = [min(m, -(-f // bs) + lookahead) for f in fills]
    n = sum(held) + 1
    k = jax.random.normal(rng[0], (n, bs, kv, hd)).astype(dtype)
    v = jax.random.normal(rng[1], (n, bs, kv, hd)).astype(dtype)
    order = list(range(1, n))
    if scatter_seed is not None:
        np.random.default_rng(scatter_seed).shuffle(order)
    pos = np.full((n, bs), -1, np.int32)
    bt = np.full((b, m), -1, np.int32)
    it = iter(order)
    for s, fill in enumerate(fills):
        for j in range(held[s]):
            blk = next(it)
            bt[s, j] = blk
            if j * bs >= fill:
                k = k.at[blk].set(jnp.nan)
                v = v.at[blk].set(jnp.nan)
            for o in range(bs):
                p = j * bs + o
                if p < fill:
                    pos[blk, o] = p
    q_pos = jnp.asarray([f - 1 for f in fills], jnp.int32)
    return k, v, jnp.asarray(pos), jnp.asarray(bt), q_pos


@pytest.mark.parametrize("case", CASES, ids=[str(c) for c in CASES])
def test_paged_kernel_matches_oracle(case):
    h, kv, hd, bs, window, fills = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (len(fills), 1, h, hd))
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], kv, hd, bs, fills)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, window=window,
                                 interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, bt,
                                            window=window)
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-4


CHUNK_CASES = [
    # (h, kv, hd, bs, window, fills, chunk)
    (4, 2, 32, 16, None, (40, 64), 8),    # GQA chunk
    (4, 4, 32, 16, None, (26, 64), 5),    # ragged final block
    (8, 2, 64, 32, 16, (96, 40), 8),      # window + GQA g=4
]


@pytest.mark.parametrize("case", CHUNK_CASES,
                         ids=[str(c) for c in CHUNK_CASES])
def test_paged_chunk_queries_match_oracle(case):
    """Chunked prefill through the block-table gather: T-token queries
    whose K/V already sit in their pool blocks."""
    h, kv, hd, bs, window, fills, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(ks[0], (len(fills), chunk, h, hd))
    k, v, pos, bt, _ = _paged_cache(ks[1:], kv, hd, bs, fills)
    q_pos = jnp.asarray([f - chunk for f in fills], jnp.int32)  # chunk start
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, window=window,
                                 interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, bt,
                                            window=window)
    assert out.shape == q.shape
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-4


def test_paged_chunk_matches_gathered_ring_oracle():
    """Chunk attention through tables == the ring oracle over the
    gathered-contiguous equivalent of the same pool."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    bs, fills, chunk = 16, (26, 64), 6
    q = jax.random.normal(ks[0], (2, chunk, 4, 32))
    k, v, pos, bt, _ = _paged_cache(ks[1:], 2, 32, bs, fills)
    q_pos = jnp.asarray([f - chunk for f in fills], jnp.int32)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, interpret=True)
    kc, pc = ref.gather_paged_kv(k, pos, bt)
    vc, _ = ref.gather_paged_kv(v, pos, bt)
    ring = ref.decode_attention_ref(q, kc, vc, q_pos, pc)
    assert float(jnp.max(jnp.abs(out - ring))) < 1e-4


def test_fragmented_pool():
    """Block ids need not be contiguous or ordered — the table is the only
    source of layout truth (the pool state after many alloc/free cycles)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (3, 1, 4, 32))
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], 2, 32, 16, (40, 64, 17),
                                        scatter_seed=7)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, bt)
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-4


def test_freed_slot_is_fully_masked():
    """A freed slot's table is all −1: both kernel and oracle must return
    exactly zero (the engine keeps finished slots in the batch until the
    host reaps them)."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32))
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], 2, 32, 16, (32, 32))
    bt = bt.at[1].set(-1)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, bt)
    assert bool(jnp.all(out[1] == 0))
    assert float(jnp.max(jnp.abs(out - expect))) < 1e-4


def test_matches_ring_kernel_on_same_context():
    """Paged attention over a gathered-contiguous layout must equal the ring
    oracle over the equivalent (B, W, KV, hd) cache."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    bs, fills = 16, (26, 64)
    q = jax.random.normal(ks[0], (2, 1, 4, 32))
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], 2, 32, bs, fills)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, interpret=True)
    kc, pc = ref.gather_paged_kv(k, pos, bt)
    vc, _ = ref.gather_paged_kv(v, pos, bt)
    ring = ref.decode_attention_ref(q, kc, vc, q_pos, pc)
    assert float(jnp.max(jnp.abs(out - ring))) < 1e-4


def test_bf16_pool():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32)).astype(jnp.bfloat16)
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], 2, 32, 16, (40, 64),
                                        dtype=jnp.bfloat16)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, bt)
    assert out.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - expect.astype(jnp.float32)))) < 2e-2


def test_ops_dispatch_wrapper():
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (2, 1, 4, 32))
    k, v, pos, bt, q_pos = _paged_cache(ks[1:], 2, 32, 16, (26, 64))
    a = ops.paged_decode_attn(q, k, v, q_pos, pos, bt, use_kernel=True,
                              interpret=True)
    b = ops.paged_decode_attn(q, k, v, q_pos, pos, bt, use_kernel=False)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-4


LIVE_CASES = [
    # (h, kv, hd, bs, window, fills, chunk, width, lookahead, dtype, freed)
    # engine-wide tables (width = max_seq_len / bs) with look-ahead blocks,
    # contexts from one token to the full table
    (4, 2, 32, 16, None, (1, 100, 256), 1, 16, 2, "float32", ()),
    # a row whose table is all −1 (a freed slot with a stale position)
    (4, 2, 32, 16, None, (40, 64, 17), 1, 16, 1, "float32", (1,)),
    # table width not a multiple of the pages per compute block
    (4, 2, 32, 16, None, (150, 208), 1, 13, 1, "float32", ()),
    # fewer live pages than a compute block holds
    (3, 1, 32, 16, None, (5, 20, 33), 1, 16, 3, "float32", ()),
    # chunk queries at positions 120..135 cross the 128-key block edge
    (4, 2, 32, 16, None, (136, 64), 16, 16, 2, "float32", ()),
    # a window whose first visible page is past the table's start
    (4, 2, 32, 16, 40, (200, 90), 1, 16, 1, "float32", ()),
    (8, 2, 64, 8, 24, (150, 63), 5, 32, 2, "float32", ()),
    # bf16 pools
    (8, 2, 64, 16, None, (1, 129, 256), 1, 16, 2, "bfloat16", ()),
    (9, 3, 64, 16, None, (70, 200), 8, 16, 1, "bfloat16", ()),
]


@pytest.mark.parametrize("case", LIVE_CASES, ids=[str(c) for c in LIVE_CASES])
def test_paged_kernel_visits_live_pages(case):
    """Engine-shaped tables: the kernel reads only each row's live pages,
    several per compute block, and matches the oracle. Look-ahead blocks
    hold NaN, so computing with one would show; the oracle is given the
    table without them, which masks the same keys."""
    h, kv, hd, bs, window, fills, chunk, width, lookahead, dtype, freed = \
        case
    dtype = jnp.dtype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q = jax.random.normal(ks[0], (len(fills), chunk, h, hd)).astype(dtype)
    k, v, pos, bt, _ = _paged_cache(ks[1:], kv, hd, bs, fills, dtype=dtype,
                                    width=width, lookahead=lookahead)
    q_pos = jnp.asarray([f - chunk for f in fills], jnp.int32)
    for row in freed:
        bt = bt.at[row].set(-1)
    live = jnp.where(pos[jnp.maximum(bt, 0)].max(axis=2) >= 0, bt, -1)
    out = paged_decode_attention(q, k, v, q_pos, pos, bt, window=window,
                                 interpret=True)
    expect = ref.paged_decode_attention_ref(q, k, v, q_pos, pos, live,
                                            window=window)
    assert out.shape == q.shape and out.dtype == dtype
    for row in freed:
        assert bool(jnp.all(out[row] == 0))
    gap = jnp.max(jnp.abs(out.astype(jnp.float32)
                          - expect.astype(jnp.float32)))
    assert float(gap) < (2e-2 if dtype == jnp.bfloat16 else 1e-4)


@pytest.mark.parametrize("window", [None, 40])
def test_work_items_fetch_only_live_pages(window):
    """Replay the pipeline over ``_work_items``: an input copies a block at
    the first step and wherever its block index changes. Every block
    copied is one some row's live range ``[lo, hi)`` uses, the steps that
    compute are exactly the live, allocated entries, and a row without
    any takes one step."""
    bs, pages, m = 16, 8, 16
    rng = np.random.default_rng(0)
    last = np.array([0, 17, 130, 255, 90, 40])          # last query positions
    bt = np.full((len(last), m), -1, np.int32)
    ids = iter(rng.permutation(np.arange(1, 200)))
    for r, p in enumerate(last):
        for j in range(min(m, p // bs + 3)):             # look-ahead blocks
            bt[r, j] = next(ids)
    bt[4] = -1                                           # a freed row
    lo, hi = dattn.live_pages(jnp.asarray(last - 3), jnp.asarray(last), bs,
                              m, window)
    count, slot, use, held = dattn._work_items(jnp.asarray(bt), lo, hi,
                                                pages)
    count, slot = int(count[0]), np.asarray(slot)
    use, held = np.asarray(use)[:count], np.asarray(held)[:count]
    lo, hi = np.asarray(lo), np.asarray(hi)
    blocks = np.maximum(-(-(hi - lo) // pages), 1)
    assert count == blocks.sum()
    assert list(slot[:count]) == [r for r in range(len(last))
                                  for _ in range(blocks[r])]
    assert (slot[count:] == -1).all()
    first_item = np.cumsum(blocks) - blocks
    want = {(int(first_item[r]) + (j - lo[r]) // pages, (j - lo[r]) % pages,
             int(bt[r, j]))
            for r in range(len(last)) for j in range(lo[r], hi[r])
            if bt[r, j] >= 0}
    used = {(i, p, int(held[i, p])) for i in range(count)
            for p in range(pages) if use[i, p]}
    assert used == want
    live_blocks = {blk for *_, blk in want}
    assert live_blocks
    for p in range(pages):
        seq = list(held[:, p])
        copied = {seq[0]} | {b for a, b in zip(seq, seq[1:]) if a != b}
        assert copied <= live_blocks
