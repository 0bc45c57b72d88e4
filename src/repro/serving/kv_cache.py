"""Pluggable KV-cache backends for the serving engines.

The cache seam has two levels, both defined here:

**Layouts** (``RingLayout`` / ``PagedLayout``) are stateless, hashable
objects the *model* programs against: ``append`` writes one decode step's
K/V (or MLA latents) into a layer's cache arrays, ``attend`` runs
single-token GQA attention over them, and ``context`` materializes a
per-slot contiguous view for mixers that attend in plain jnp (MLA's
absorbed form). ``attn_decode`` / ``mla_decode`` / ``LM.decode_step`` take a
layout plus an optional ``block_tables`` array and never touch cache-dict
internals directly.

**Backends** (``RingCache`` / ``PagedCache``) are what the *engine* owns:
device cache state, slot admission (``alloc_slot`` → ``prefill_fill``),
completion (``free_slot``) and accounting (``hbm_bytes``). ``RingCache`` is
the original behavior extracted: every slot pins a ``max_seq_len``-wide
ring, so HBM per slot is worst-case. ``PagedCache`` is vLLM-style: one
global pool of fixed-size blocks per layer plus a per-slot block table,
with a host-side free-block allocator — admission *commits* to the
worst-case ``ceil((prompt + budget) / block_size)`` blocks (so decode can
never starve mid-flight: the commitment ledger guarantees every look-ahead
top-up succeeds) but physically allocates lazily: blocks covering the
prompt at admission, then ``reserve_lookahead`` tops the slot's table up
to ``pos + K`` tokens before each K-step decode scan. Blocks the request
never reaches (early EOS, unspent budget tail) are never drawn from the
free list at all, and whatever was drawn returns at ``free_slot``.

Paged conventions (shared by the Pallas kernel, the jnp oracle, and the
engine):

- pool block 0 is a reserved **trash block**, never allocated; writes on
  behalf of free / finished slots land there;
- block-table entries are physical block ids ≥ 1 when allocated and −1
  when not; attention fully masks −1 entries;
- per-token ``pos`` in the pool is −1 until written, and pad positions are
  installed as −1 at prefill, so a slot's visible context is exactly its
  real tokens.

``PagedCache`` also supports **preemption**: ``swap_out`` checkpoints a
slot's drawn blocks (every layer's K/V + per-token positions) to host
memory and releases them through the ordinary ``free_slot`` accounting;
``swap_in`` later draws fresh private blocks, scatters the checkpoint back
byte-for-byte, and re-commits the undrawn budget tail to the ledger — so
an SLO-blocked engine can evict a low-priority request's cache and restore
it token-exactly when pressure clears (``can_resume`` gates the restore
against the uncommitted free list).

Freed prefix blocks are **retained**: a refcount-0 block whose content is
registered in the prefix-hash index stays in the index and parks at the
*back* of the free list (LRU order), so templated traffic shares prompt
blocks across bursts, not just across concurrent requests — a later
admission matching the prefix revives the block from the free list with
its K/V intact. Cached free blocks are reclaimed last (plain free blocks
first, then least-recently-freed cached ones), and eviction simply drops
the index entry before the block is wiped for its new tenant.
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.sharding import kv_head_split


def _pos1d(cur_pos, batch: int) -> jnp.ndarray:
    return jnp.broadcast_to(jnp.asarray(cur_pos, jnp.int32), (batch,))


def _map_kv_dicts(fn, tree, other=None):
    """Apply ``fn`` at each per-block cache dict (the ones holding "pos"),
    preserving the list/tuple nesting the model builds around them."""
    if isinstance(tree, dict):
        if "pos" not in tree:
            raise NotImplementedError(
                f"cache dict without positions (keys={sorted(tree)}) — "
                "paged layout supports attention caches only")
        return fn(tree) if other is None else fn(tree, other)
    if isinstance(tree, (list, tuple)):
        if other is None:
            sub = [_map_kv_dicts(fn, x) for x in tree]
        else:
            sub = [_map_kv_dicts(fn, x, y) for x, y in zip(tree, other)]
        return type(tree)(sub)
    raise NotImplementedError(f"unsupported cache node: {type(tree)}")


# ---------------------------------------------------------------------------
# Layouts: the layer-level contract the attention code programs against
# ---------------------------------------------------------------------------

def _chunk_index(cur_pos, updates, valid, batch: int):
    """Shared append bookkeeping: per-token positions (B, T) for a chunk
    starting at ``cur_pos`` plus the write-validity mask (True = real token;
    False = right-pad / inactive slot, must not land in the cache). ``valid``
    is assumed to be a contiguous prefix per row (chunks are dense)."""
    t = next(iter(updates.values())).shape[1]
    start = _pos1d(cur_pos, batch)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    ok = (jnp.ones((batch, t), bool) if valid is None
          else jnp.broadcast_to(jnp.asarray(valid, bool), (batch, t)))
    return start, pos, ok


@dataclasses.dataclass(frozen=True)
class RingLayout:
    """Per-slot ring: cache arrays are (B, W, ...); token at position ``p``
    lives at slot ``p % W`` and ``pos`` records which position each slot
    currently holds (−1 = empty)."""

    def append(self, cache: Dict[str, jnp.ndarray], updates, cur_pos,
               block_tables=None, valid=None) -> Dict[str, jnp.ndarray]:
        """Write a T-token chunk (T = 1 for decode) at positions
        ``cur_pos + i``. Invalid tokens are routed to ring index ``width``
        — out of bounds, so the scatter drops them (JAX's default scatter
        mode) and the cache is untouched. When a chunk is longer than the
        ring (windowed layers), only each ring slot's newest token is kept
        (the older ones would be overwritten within this same scatter, and
        scatter order with duplicate indices is undefined).

        Scan-carry clean: every index derives from the traced ``cur_pos``
        and the carried cache's static shape — no per-step host constants —
        so engines may ``lax.scan`` K appends with the cache as carry
        (multi-step decode), windowed ring widths included."""
        b, width = cache["pos"].shape
        start, pos, ok = _chunk_index(cur_pos, updates, valid, b)
        length = jnp.sum(ok.astype(jnp.int32), axis=1, keepdims=True)
        keep = ok & (pos + width > start[:, None] + length - 1)
        slot = jnp.where(keep, pos % width, width)       # width = dropped
        rows = jnp.arange(b)[:, None]
        new = {k: cache[k].at[rows, slot].set(u)
               for k, u in updates.items()}
        new["pos"] = cache["pos"].at[rows, slot].set(pos)
        return new

    def attend(self, q, cache, q_pos, block_tables=None, *,
               window: Optional[int], scale: float,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
        from repro.kernels.ops import decode_attn
        return decode_attn(q, cache["k"], cache["v"], q_pos, cache["pos"],
                           window=window, scale=scale, use_kernel=use_kernel,
                           interpret=interpret, heads=kv_head_split())

    def context(self, cache, block_tables=None) -> Dict[str, jnp.ndarray]:
        """Per-slot contiguous view (identity for the ring)."""
        return cache


@dataclasses.dataclass(frozen=True)
class PagedLayout:
    """Global block pool: cache arrays are (N, block_size, ...) shared by
    every slot; ``block_tables`` (B, M) maps a slot's logical block
    ``pos // block_size`` to a physical pool block."""
    block_size: int

    def append(self, cache: Dict[str, jnp.ndarray], updates, cur_pos,
               block_tables=None, valid=None) -> Dict[str, jnp.ndarray]:
        """Write a T-token chunk (T = 1 for decode) at positions
        ``cur_pos + i``. Free / never-admitted slots have no blocks and
        invalid (pad / inactive) tokens must not write: both are parked in
        the trash block (0) with pos −1. Scan-carry clean like the ring:
        all routing is traced (``block_tables`` may be a scan-invariant
        closure constant), so K decode appends scan with the pool as carry
        — the engine's look-ahead reservation guarantees every in-scan
        position is covered by an allocated block."""
        assert block_tables is not None, "paged layout needs block tables"
        b, m = block_tables.shape
        _, pos, ok = _chunk_index(cur_pos, updates, valid, b)
        logical = jnp.clip(pos // self.block_size, 0, m - 1)
        row = jnp.take_along_axis(block_tables, logical, axis=1)   # (B, T)
        ok = ok & (row >= 0)
        phys = jnp.where(ok, row, 0)
        off = jnp.where(ok, pos % self.block_size, 0)
        new = {k: cache[k].at[phys, off].set(u)
               for k, u in updates.items()}
        new["pos"] = cache["pos"].at[phys, off].set(
            jnp.where(ok, pos, -1))
        return new

    def attend(self, q, cache, q_pos, block_tables=None, *,
               window: Optional[int], scale: float,
               use_kernel: Optional[bool] = None,
               interpret: Optional[bool] = None):
        from repro.kernels.ops import paged_decode_attn
        return paged_decode_attn(q, cache["k"], cache["v"], q_pos,
                                 cache["pos"], block_tables, window=window,
                                 scale=scale, use_kernel=use_kernel,
                                 interpret=interpret, heads=kv_head_split())

    def context(self, cache, block_tables=None) -> Dict[str, jnp.ndarray]:
        """Gather each slot's blocks into a contiguous (B, M*bs, ...) view;
        unallocated table entries surface as pos −1 (fully masked)."""
        from repro.kernels.ref import gather_paged_kv
        out = {}
        pos = None
        for key, leaf in cache.items():
            if key == "pos":
                continue
            out[key], pos = gather_paged_kv(leaf, cache["pos"], block_tables)
        out["pos"] = pos
        return out


RING = RingLayout()


# ---------------------------------------------------------------------------
# Backends: the engine-level contract
# ---------------------------------------------------------------------------

class KVCacheBackend:
    """Engine-side cache owner.

    ``init`` returns the device cache state (a dict with "caches" — the
    model's cache pytree — and "tables", the (B, M) block tables or None).
    Admission is two-phase: the host calls ``alloc_slot`` (reserve blocks,
    may refuse), then passes the returned table row into ``prefill_fill``
    *inside* the jitted admit program. ``free_slot`` returns the blocks at
    completion. ``hbm_bytes`` is the device-resident KV footprint.

    Chunked prefill adds a second admission shape: ``begin_slot`` (wipe the
    slot's stale positions and install its table row, once per admission)
    followed by any number of ``slot_view`` → model chunk → ``slot_update``
    round-trips, each traced inside the engine's per-chunk program.
    ``alloc_slot`` may be given the prompt *tokens* instead of a length;
    backends that share prefix cache (``PagedCache``) then report via
    ``shared_prefill_start`` how many leading tokens are already installed.
    """

    layout: Any

    def init(self) -> Dict[str, Any]:
        raise NotImplementedError

    def can_admit(self, prompt, max_new: int) -> bool:
        """``prompt``: length (int) or the token array itself (enables
        prefix-aware accounting in sharing backends)."""
        raise NotImplementedError

    def can_ever_admit(self, prompt_len: int, max_new: int) -> bool:
        """Whether the request could fit with the backend completely idle
        (the *capacity* test, vs ``can_admit``'s availability test). False
        means waiting can never help: the engine terminally rejects the
        request instead of letting it block the queue forever."""
        return True

    def alloc_slot(self, slot: int, prompt, max_new: int) -> np.ndarray:
        """Host-side reservation; returns the slot's block-table row (a
        dummy for backends without tables). ``prompt`` is a length or the
        token array (see ``can_admit``). Must only be called after
        ``can_admit`` said yes."""
        raise NotImplementedError

    def prefill_fill(self, cache_state, one_caches, slot, length, table_row):
        """Install a single-request prefilled cache into ``slot`` (traced
        inside the engine's admit program)."""
        raise NotImplementedError

    def free_slot(self, cache_state, slot: int) -> Dict[str, Any]:
        raise NotImplementedError

    # -- chunked-prefill admission seam --------------------------------------
    def begin_slot(self, cache_state, slot, table_row, shared_blocks):
        """Prepare ``slot`` for incremental (chunked) install: wipe stale
        per-token positions so the previous tenant can't alias into the new
        request's causal mask, and install the table row. ``shared_blocks``
        leading blocks hold live shared-prefix content and are left alone.
        Traced (jit-safe in ``slot``/``table_row``/``shared_blocks``)."""
        raise NotImplementedError

    def slot_view(self, cache_state, slot, ctx=None):
        """(caches_view, tables_view) for running a single-slot model chunk:
        the ring slices the slot's cache line (batch 1); the paged pool is
        global, so the view is the pool plus the slot's (1, M) table row.
        ``ctx`` (static) bounds the visible context to the first ``ctx``
        positions — the chunk only ever attends to positions below its own
        end, so slicing skips the dense attend over the empty cache tail
        (the host-path analog of the TPU kernels' masked-block skip)."""
        raise NotImplementedError

    def slot_update(self, cache_state, slot, view_caches):
        """Write a ``slot_view`` caches pytree back (no-op for the paged
        pool, whose view aliases the global state)."""
        raise NotImplementedError

    def reserve_lookahead(self, slot: int, tokens: int):
        """Top up ``slot``'s physical reservation to cover ``tokens`` total
        tokens (multi-step decode look-ahead: the engine calls this with
        ``pos + K`` before scanning K fused decode steps, so every append
        inside the scan lands in an allocated block). Returns
        ``(new_table_row, previously_covered_entries)`` when blocks were
        added — the engine replays it through the ``begin_slot`` seam,
        which wipes only the new blocks' stale positions — or
        ``(None, 0)`` when the slot is already covered (always, for
        backends like the ring whose slots pin worst-case storage)."""
        return None, 0

    def shared_prefill_start(self, slot: int) -> int:
        """First prompt position the engine must actually compute for
        ``slot`` (> 0 when a shared prefix is already installed)."""
        return 0

    def shared_block_count(self, slot: int) -> int:
        """Leading table entries of ``slot`` whose content is already live
        (shared or copied) — ``begin_slot`` must not wipe them."""
        return 0

    def register_prefix(self, slot: int, prompt) -> None:
        """Called by the engine when ``slot``'s prefill completes: the
        slot's full prompt blocks now hold real K/V and may be shared."""

    def take_pending_copies(self) -> List:
        """Drain (src, dst) physical block copies the allocator scheduled
        (copy-on-write); the engine replays them on device."""
        return []

    def hbm_bytes(self) -> int:
        raise NotImplementedError

    def hbm_bytes_per_slot(self) -> float:
        raise NotImplementedError

    # -- mesh placement (tensor-parallel decode) -----------------------------
    # K/V pools shard their KV-head dim over the mesh's 'model' axis; the
    # block tables, free list and commitment ledger stay host-global. The
    # backend only *accounts* for the split (kv_shards) — placement itself
    # is jax.device_put with the shardings() tree, done by the engine.
    kv_shards: int = 1

    def shardings(self, mesh):
        """NamedSharding tree matching ``init()``'s state pytree."""
        from repro.serving.sharding import cache_shardings
        return cache_shardings(mesh, jax.eval_shape(self.init))

    def note_placement(self, mesh) -> None:
        """Record the KV-head split for per-device accounting (placement
        through ``serving.sharding.cache_pspecs`` refuses a split that
        does not divide the KV heads)."""
        from repro.serving.sharding import model_axis_size
        self.kv_shards = model_axis_size(mesh)

    def hbm_bytes_per_device(self) -> int:
        """Per-device KV footprint (== ``hbm_bytes`` without a mesh)."""
        return self.hbm_bytes()


def _kv_shard_divisor(path, shape, kv_shards: int) -> int:
    """Ways a pool leaf's bytes split across devices: K/V leaves (KV-head
    dim 3 of 5) split ``kv_shards`` ways, everything else is replicated.
    Mirrors ``serving.sharding.cache_pspecs``."""
    name = path[-1].key if hasattr(path[-1], "key") else ""
    if name in ("k", "v") and len(shape) == 5:
        return max(kv_shards, 1)
    return 1


def _cache_proto(lm, params, max_seq_len: int, proto_len: int):
    """Abstract per-request cache structure, as ``prefill`` returns it."""
    return jax.eval_shape(
        lambda p, t: lm.prefill(p, {"tokens": t},
                                cache_width=max_seq_len)[1],
        params, jax.ShapeDtypeStruct((1, proto_len), jnp.int32))


def _path_endswith(path, name: str) -> bool:
    return len(path) > 0 and getattr(path[-1], "key", None) == name


def _prompt_spec(prompt):
    """Normalize the ``prompt`` admission argument: length (int) or token
    array -> (length, tokens_or_None)."""
    if isinstance(prompt, (int, np.integer)):
        return int(prompt), None
    tokens = np.asarray(prompt, np.int32)
    return int(tokens.shape[0]), tokens


class RingCache(KVCacheBackend):
    """The original per-slot ring caches, extracted behind the API: every
    slot owns a full ``max_seq_len``-wide cache line in each layer."""

    def __init__(self, lm, params, *, batch_slots: int, max_seq_len: int,
                 proto_len: int = 16):
        self.layout = RING
        self.batch_slots = batch_slots
        self.max_seq_len = max_seq_len
        self._proto = _cache_proto(lm, params, max_seq_len, proto_len)

    def init(self) -> Dict[str, Any]:
        b = self.batch_slots

        def leaf(path, a):
            shape = (a.shape[0], b) + a.shape[2:]
            if _path_endswith(path, "pos"):
                return jnp.full(shape, -1, a.dtype)      # -1 = empty slot
            return jnp.zeros(shape, a.dtype)

        caches = jax.tree_util.tree_map_with_path(leaf, self._proto)
        return {"caches": caches, "tables": None}

    def can_admit(self, prompt, max_new: int) -> bool:
        return True                       # a granted slot is the only gate

    def alloc_slot(self, slot, prompt, max_new) -> np.ndarray:
        return np.zeros((1,), np.int32)   # no tables: fixed dummy row

    def prefill_fill(self, cache_state, one_caches, slot, length, table_row):
        caches = jax.tree.map(
            lambda g, c: jax.lax.dynamic_update_index_in_dim(
                g, c[:, 0], slot, axis=1),
            cache_state["caches"], one_caches)
        return {"caches": caches, "tables": cache_state["tables"]}

    def free_slot(self, cache_state, slot):
        return cache_state                # rings are reused in place

    # -- chunked-prefill admission seam --------------------------------------
    def begin_slot(self, cache_state, slot, table_row, shared_blocks):
        """Wipe the slot's per-token positions: unlike monolithic admission
        (which overwrites the whole cache line), chunked install only writes
        the chunk's positions, so the previous tenant's stale entries would
        otherwise sit inside the new request's causal mask."""

        def wipe(path, g):
            if _path_endswith(path, "pos"):
                return g.at[:, slot].set(-1)
            return g

        caches = jax.tree_util.tree_map_with_path(wipe,
                                                  cache_state["caches"])
        return {"caches": caches, "tables": cache_state["tables"]}

    def slot_view(self, cache_state, slot, ctx=None):
        """Chunked prefill requires unwindowed layers (engine-validated),
        so every cache line is ``max_seq_len`` wide and position ``p``
        lives at ring index ``p`` — the first ``ctx`` columns are exactly
        the positions below ``ctx``, making the prefix slice exact."""

        def view(g):
            width = g.shape[2] if ctx is None else min(ctx, g.shape[2])
            starts = (0, slot) + (0,) * (g.ndim - 2)
            return jax.lax.dynamic_slice(
                g, starts, (g.shape[0], 1, width) + g.shape[3:])

        return jax.tree.map(view, cache_state["caches"]), None

    def slot_update(self, cache_state, slot, view_caches):
        def upd(g, c):
            starts = (0, slot) + (0,) * (g.ndim - 2)
            return jax.lax.dynamic_update_slice(g, c, starts)

        caches = jax.tree.map(upd, cache_state["caches"], view_caches)
        return {"caches": caches, "tables": cache_state["tables"]}

    def hbm_bytes(self) -> int:
        total = 0
        for leaf in jax.tree.leaves(self._proto):
            n = math.prod((leaf.shape[0], self.batch_slots) + leaf.shape[2:])
            total += n * leaf.dtype.itemsize
        return total

    def hbm_bytes_per_slot(self) -> float:
        return self.hbm_bytes() / self.batch_slots

    def hbm_bytes_per_device(self) -> int:
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._proto)[0]:
            shape = (leaf.shape[0], self.batch_slots) + leaf.shape[2:]
            n = math.prod(shape) // _kv_shard_divisor(
                path, shape, self.kv_shards)
            total += n * leaf.dtype.itemsize
        return total


class HostSwapHandle:
    """Deferred device→host K/V transfer for the swap/checkpoint path.

    ``swap_out(..., defer=True)`` gathers the slot's blocks into a fresh
    device buffer (so the pool can be scribbled over immediately), starts
    the D2H copy asynchronously, and hands the engine this handle instead
    of blocking on ``jax.device_get`` — the transfer then overlaps the
    next scheduler plan on the host. ``resolve()`` (idempotent) completes
    the copy and returns the numpy pytree; every consumer of a swap
    checkpoint's ``caches`` goes through ``resolve_swap_caches``."""

    def __init__(self, dev_caches):
        for leaf in jax.tree.leaves(dev_caches):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self._dev = dev_caches
        self._host = None

    def resolve(self):
        if self._host is None:
            self._host = jax.device_get(self._dev)
            self._dev = None                    # drop the device buffers
        return self._host


def resolve_swap_caches(host_kv):
    """Materialize a swap checkpoint's ``caches`` in place (no-op when the
    transfer was eager or already resolved) and return the numpy pytree."""
    caches = host_kv["caches"]
    if isinstance(caches, HostSwapHandle):
        caches = caches.resolve()
        host_kv["caches"] = caches
    return caches


class PagedCache(KVCacheBackend):
    """Block-table backend: a global pool of ``num_blocks`` blocks of
    ``block_size`` tokens per layer, committed per request at admission and
    returned at completion. Slot count is bounded by live tokens in the
    pool, not by ``batch_slots × max_seq_len``.

    Allocation is **lazy with worst-case commitment**: admission debits the
    full ``ceil((prompt + budget) / block_size)`` from a commitment ledger
    (``can_admit`` checks fresh-worst-case ≤ free − outstanding
    commitments, so a look-ahead top-up can never fail mid-decode — no
    preemption needed), but only draws blocks covering the *prompt* from
    the free list; ``reserve_lookahead`` draws the rest just ahead of the
    decode scan that will write them. Budget a request never reaches
    (early EOS, unspent tail) is released without its blocks ever leaving
    the free list.

    Blocks are **refcounted**: requests whose prompts share a full-block
    prefix point their leading table entries at the same physical blocks
    (``prefix_sharing``), skipping both the HBM and the prefill compute for
    those tokens. A prefix-hash index maps ``tokens[:k*bs]`` (full blocks
    only, registered once the owning request's prefill completes) to the
    pool block holding block ``k-1``. ``free_slot`` decrements; at
    refcount 0 an *indexed* block is retained — it keeps its index entry
    and parks at the LRU tail of the free list, so a later admission
    (same burst or a new one) can revive it with its K/V intact
    (cross-run prefix persistence); unindexed blocks return to the plain
    free list. Reclaim order is plain blocks first, then cached blocks
    least-recently-freed first; eviction drops the index entry.
    If a new request must *write* inside a shared block (its prompt is
    entirely covered by shared blocks, so the engine recomputes the final
    prompt token for its logits), the allocator schedules a copy-on-write:
    a fresh block replaces the shared one in this slot's table and the
    engine replays the device-side copy before the first chunk."""

    def __init__(self, lm, params, *, batch_slots: int, max_seq_len: int,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 proto_len: int = 16, prefix_sharing: bool = True,
                 retain_prefix_blocks: Optional[bool] = None):
        for stage in lm.cfg.stages:
            for bdef in stage.blocks:
                if bdef.mixer not in ("attn", "mla"):
                    raise NotImplementedError(
                        f"paged KV backend supports attention mixers only "
                        f"(got {bdef.mixer!r}); use cache_backend='ring'")
        self.layout = PagedLayout(block_size)
        self.batch_slots = batch_slots
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.prefix_sharing = prefix_sharing
        self.retain_prefix_blocks = (prefix_sharing
                                     if retain_prefix_blocks is None
                                     else retain_prefix_blocks
                                     and prefix_sharing)
        self.blocks_per_slot = -(-max_seq_len // block_size)   # table width M
        if num_blocks is None:
            # default to ring-equivalent capacity (+ the trash block)
            num_blocks = batch_slots * self.blocks_per_slot + 1
        if num_blocks < 2:
            raise ValueError("paged pool needs ≥ 2 blocks (block 0 is trash)")
        self.num_blocks = num_blocks
        self._proto = _cache_proto(lm, params, max_seq_len, proto_len)
        # free blocks, two tiers: plain blocks (no cached content) are
        # reclaimed first; refcount-0 blocks retaining indexed prefix K/V
        # sit in freed order and are reclaimed LRU-first, i.e. last overall
        self._free_plain: List[int] = list(range(1, num_blocks))  # 0 = trash
        self._free_cached: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._slot_blocks: Dict[int, List[int]] = {}
        self._ref: Dict[int, int] = {}                # block -> refcount
        self._index: Dict[bytes, int] = {}            # prefix hash -> block
        self._block_key: Dict[int, bytes] = {}        # reverse index
        self._slot_shared: Dict[int, int] = {}        # slot -> live blocks
        self._slot_start: Dict[int, int] = {}         # slot -> prefill start
        self._slot_cap: Dict[int, int] = {}           # slot -> max entries
        self._slot_gap: Dict[int, int] = {}           # committed, not drawn
        self._gap_total = 0                           # sum of _slot_gap
        self._pending_copies: List = []               # (src, dst) for COW
        # accounting for the bench / capacity planning
        self.admitted = 0
        self.blocks_allocated_total = 0
        self.peak_blocks_in_use = 0
        self.cow_copies = 0
        self.lookahead_topups = 0
        self.retained_block_hits = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.preempt_swap_bytes = 0      # host<->device bytes moved by swaps

    @property
    def _free(self) -> List[int]:
        """All reclaimable blocks, in reclaim order (read-only view kept
        for accounting/tests; mutate the underlying tiers instead)."""
        return self._free_plain + list(self._free_cached)

    # -- device state --------------------------------------------------------
    def init(self) -> Dict[str, Any]:
        n, bs = self.num_blocks, self.block_size

        def pool(d):
            out = {}
            for key, a in d.items():
                # proto leaves are (L, 1, W, ...): swap the per-request
                # (1, W) cache line for the (N, bs) pool
                shape = (a.shape[0], n, bs) + a.shape[3:]
                if key == "pos":
                    shape = (a.shape[0], n, bs)
                    out[key] = jnp.full(shape, -1, a.dtype)
                else:
                    out[key] = jnp.zeros(shape, a.dtype)
            return out

        caches = _map_kv_dicts(pool, self._proto)
        tables = jnp.full((self.batch_slots, self.blocks_per_slot), -1,
                          jnp.int32)
        return {"caches": caches, "tables": tables}

    # -- host-side allocator -------------------------------------------------
    def blocks_needed(self, prompt_len: int, max_new: int) -> int:
        return max(1, -(-(prompt_len + max_new) // self.block_size))

    def _plan(self, prompt, max_new: int):
        """(total_blocks, shared_blocks, fresh_worst, prefill_start) for a
        prospective admission. Sharing matches the longest chain of full
        prompt blocks already registered in the prefix index — refcount-0
        retained blocks included (their K/V survives in the pool until
        eviction); the engine always recomputes at least the final prompt
        token (its logits seed decode), and when that token's block is
        shared the plan commits one extra block for the copy-on-write.
        ``fresh_worst`` is the worst-case fresh-block draw over the
        request's whole lifetime (full budget, no early EOS)."""
        length, tokens = _prompt_spec(prompt)
        total = self.blocks_needed(length, max_new)
        shared = []
        if self.prefix_sharing and tokens is not None:
            bs = self.block_size
            while (len(shared) + 1) * bs <= length:
                blk = self._index.get(tokens[:(len(shared) + 1) * bs]
                                      .tobytes())
                if blk is None:
                    break
                shared.append(blk)
        k = len(shared)
        prefill_start = k * self.block_size
        cow = 0
        if prefill_start >= length:            # fully covered, block-aligned
            prefill_start = length - 1
            cow = 1                            # last block must go private
        return total, shared, total - k + cow, prefill_start

    def _revivals(self, shared) -> int:
        """Shared blocks currently parked refcount-0 in the free list: a
        revival takes them out of the free list without counting as a
        fresh draw."""
        return sum(1 for blk in shared if blk not in self._ref)

    def _available(self) -> int:
        """Free blocks not spoken for by outstanding worst-case
        commitments of already-admitted requests."""
        return (len(self._free_plain) + len(self._free_cached)
                - self._gap_total)

    def can_admit(self, prompt, max_new: int) -> bool:
        _, shared, fresh_worst, _ = self._plan(prompt, max_new)
        return fresh_worst + self._revivals(shared) <= self._available()

    def can_ever_admit(self, prompt_len: int, max_new: int) -> bool:
        # block 0 is the trash block: usable pool is num_blocks - 1
        return self.blocks_needed(prompt_len, max_new) <= self.num_blocks - 1

    def _take_free(self, n: int, exclude=()) -> List[int]:
        """Draw ``n`` blocks: plain free blocks first, then retained
        (cached) blocks least-recently-freed first, evicting their index
        entries. ``exclude`` protects retained blocks the caller is about
        to *revive* as shared entries of the same admission — evicting one
        of those would hand the same physical block out twice. Callers
        stay within the commitment ledger (which counts revivals), so the
        free list always covers the draw."""
        out: List[int] = []
        while self._free_plain and len(out) < n:
            out.append(self._free_plain.pop())
        if len(out) < n:
            for blk in list(self._free_cached):              # LRU eviction
                if len(out) >= n:
                    break
                if blk in exclude:
                    continue
                del self._free_cached[blk]
                key = self._block_key.pop(blk, None)
                if key is not None and self._index.get(key) == blk:
                    del self._index[key]
                out.append(blk)
        assert len(out) == n, "commitment ledger violated: free list short"
        return out

    def _release_block(self, blk: int) -> None:
        """Park a refcount-0 block in the free list: retained (index entry
        kept, LRU tail) when it holds registered prefix K/V, plain
        otherwise."""
        key = self._block_key.get(blk)
        if key is not None and self.retain_prefix_blocks:
            self._free_cached[blk] = None     # most-recent = reclaimed last
            return
        if key is not None:
            del self._block_key[blk]
            if self._index.get(key) == blk:
                del self._index[key]
        self._free_plain.append(blk)

    def alloc_slot(self, slot, prompt, max_new) -> np.ndarray:
        length, _ = _prompt_spec(prompt)
        total, shared, fresh_worst, prefill_start = self._plan(prompt,
                                                               max_new)
        revive = self._revivals(shared)
        if fresh_worst + revive > self._available():
            raise RuntimeError(
                f"paged pool exhausted: need {fresh_worst + revive} blocks, "
                f"{self._available()} available")
        if slot in self._slot_blocks:
            raise RuntimeError(f"slot {slot} already holds blocks")
        k = len(shared)
        cow = 1 if (shared and prefill_start < k * self.block_size) else 0
        # physical draw now: blocks covering the prompt (decode blocks are
        # drawn by reserve_lookahead just ahead of the scan that fills them)
        entries_now = max(1, -(-length // self.block_size))
        fresh_now = cow + max(0, entries_now - k)
        fresh = self._take_free(fresh_now, exclude=set(shared))
        for blk in shared:
            if blk in self._free_cached:      # revive a retained block
                del self._free_cached[blk]
                self.retained_block_hits += 1
            self._ref[blk] = self._ref.get(blk, 0) + 1
        for blk in fresh:
            self._ref[blk] = 1
        blocks = list(shared)
        if cow:
            # copy-on-write: the final prompt token lives in the last shared
            # block; hand this slot a private copy instead
            src = blocks[-1]
            dst = fresh[0]
            blocks[-1] = dst
            self._ref[src] -= 1                # undo the share of that block
            if self._ref[src] == 0:            # was a revived retained block
                del self._ref[src]
                self._release_block(src)
            self._pending_copies.append((src, dst))
            self.cow_copies += 1
            blocks.extend(fresh[1:])
        else:
            blocks.extend(fresh)
        self._slot_blocks[slot] = blocks
        self._slot_shared[slot] = k             # content-live leading blocks
        self._slot_start[slot] = prefill_start
        self._slot_cap[slot] = total
        self._slot_gap[slot] = fresh_worst - fresh_now
        self._gap_total += fresh_worst - fresh_now
        row = np.full((self.blocks_per_slot,), -1, np.int32)
        row[:len(blocks)] = blocks
        self.admitted += 1
        self.blocks_allocated_total += fresh_now
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return row

    def reserve_lookahead(self, slot, tokens: int):
        """Top the slot's table up to cover ``tokens`` total tokens ahead
        of a decode scan. Draws at most the slot's remaining commitment
        (the admission-time worst case), so the ledger guarantees the free
        list can satisfy it; returns ``(row, previously_covered)`` for the
        engine's ``begin_slot`` replay, or ``(None, 0)`` when covered."""
        blocks = self._slot_blocks.get(slot)
        if blocks is None:
            return None, 0
        need = min(max(1, -(-tokens // self.block_size)),
                   self._slot_cap[slot])
        have = len(blocks)
        if need <= have:
            return None, 0
        take = need - have
        assert take <= self._slot_gap[slot], (
            f"look-ahead past slot {slot}'s committed budget "
            f"({take} > {self._slot_gap[slot]})")
        fresh = self._take_free(take)
        for blk in fresh:
            self._ref[blk] = 1
        blocks.extend(fresh)
        self._slot_gap[slot] -= take
        self._gap_total -= take
        self.blocks_allocated_total += take
        self.lookahead_topups += 1
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        row = np.full((self.blocks_per_slot,), -1, np.int32)
        row[:len(blocks)] = blocks
        return row, have

    def shared_prefill_start(self, slot: int) -> int:
        return self._slot_start.get(slot, 0)

    def shared_block_count(self, slot: int) -> int:
        return self._slot_shared.get(slot, 0)

    def register_prefix(self, slot: int, prompt) -> None:
        """Publish the slot's full prompt blocks into the prefix index.
        Called when the slot's prefill *completes* — earlier registration
        would let a concurrent admission share blocks whose K/V hasn't been
        installed yet (pos −1, silently masked: wrong outputs)."""
        if not self.prefix_sharing:
            return
        length, tokens = _prompt_spec(prompt)
        if tokens is None:
            return
        blocks = self._slot_blocks.get(slot)
        if blocks is None:
            return
        bs = self.block_size
        for i in range(length // bs):
            key = tokens[:(i + 1) * bs].tobytes()
            blk = blocks[i]
            if key in self._index or blk in self._block_key:
                continue
            self._index[key] = blk
            self._block_key[blk] = key

    def take_pending_copies(self) -> List:
        copies, self._pending_copies = self._pending_copies, []
        return copies

    def copy_block(self, cache_state, src, dst):
        """Device-side block copy (COW): every layer's pool rows ``src`` →
        ``dst``, per-token positions included. Traced (jit-safe)."""

        def copy(c):
            return {key: leaf.at[:, dst].set(leaf[:, src])
                    for key, leaf in c.items()}

        caches = _map_kv_dicts(copy, cache_state["caches"])
        return {"caches": caches, "tables": cache_state["tables"]}

    @property
    def blocks_in_use(self) -> int:
        """Blocks held by live slots (retained refcount-0 cache blocks are
        reclaimable, so they count as free)."""
        return (self.num_blocks - 1) - len(self._free_plain) \
            - len(self._free_cached)

    @property
    def blocks_committed(self) -> int:
        """Blocks in use plus those admission promised to live slots and
        ``reserve_lookahead`` has not drawn yet."""
        return self.blocks_in_use + self._gap_total

    def reset_stats(self) -> None:
        """Zero the admission accounting (e.g. after bench warm-up) so
        ``hbm_bytes_per_slot`` averages only the measured traffic."""
        self.admitted = 0
        self.blocks_allocated_total = 0
        self.peak_blocks_in_use = self.blocks_in_use
        self.cow_copies = 0
        self.lookahead_topups = 0
        self.retained_block_hits = 0
        self.swap_outs = 0
        self.swap_ins = 0
        self.preempt_swap_bytes = 0

    def free_slot(self, cache_state, slot):
        blocks = self._slot_blocks.pop(slot, None)
        if blocks is None:
            return cache_state
        self._slot_shared.pop(slot, None)
        self._slot_start.pop(slot, None)
        self._slot_cap.pop(slot, None)
        # release the never-drawn commitment (over-reserved look-ahead the
        # request finished without: early EOS / unspent budget tail)
        self._gap_total -= self._slot_gap.pop(slot, 0)
        for blk in blocks:
            self._ref[blk] = self._ref.get(blk, 1) - 1
            if self._ref[blk] > 0:
                continue                      # still shared by another slot
            del self._ref[blk]
            self._release_block(blk)
        tables = cache_state["tables"].at[slot].set(-1)
        return {"caches": cache_state["caches"], "tables": tables}

    # -- preemption: host K/V swap -------------------------------------------
    def _swap_fns(self):
        """Jitted fixed-shape gather/scatter for the swap path: both take a
        full ``blocks_per_slot``-wide index vector (gather pads with the
        trash block — harmless reads; scatter pads with ``num_blocks`` —
        out of bounds, dropped), so each compiles exactly once and a first
        swap landing mid-traffic never pays an XLA compile."""
        if not hasattr(self, "_gather_fn"):
            def gather(caches, idx):
                return _map_kv_dicts(
                    lambda c: {k: jnp.take(leaf, idx, axis=1)
                               for k, leaf in c.items()}, caches)

            def scatter(caches, host, phys):
                def one(c, h):
                    return {k: leaf.at[:, phys].set(h[k])
                            for k, leaf in c.items()}

                return _map_kv_dicts(one, caches, host)

            self._gather_fn = jax.jit(gather)
            # donate the pool: without it every swap_in materializes a
            # second full copy of the paged KV cache — transiently doubling
            # KV HBM in exactly the memory-pressure regime preemption
            # exists to serve (the gather must NOT donate: its input pool
            # stays live)
            self._scatter_fn = jax.jit(scatter, donate_argnums=(0,))
        return self._gather_fn, self._scatter_fn

    def warm_swap(self, cache_state):
        """Pre-compile the swap gather/scatter as no-ops (gather from the
        trash block, scatter fully out of bounds); call while idle."""
        gather_fn, scatter_fn = self._swap_fns()
        m = self.blocks_per_slot
        host = jax.device_get(gather_fn(cache_state["caches"],
                                        jnp.zeros((m,), jnp.int32)))
        caches = scatter_fn(cache_state["caches"], host,
                            jnp.full((m,), self.num_blocks, jnp.int32))
        return {"caches": caches, "tables": cache_state["tables"]}

    def swap_out(self, cache_state, slot, *, defer: bool = False):
        """Checkpoint ``slot``'s drawn blocks to the host and release them:
        gathers every layer's K/V (and per-token positions) for the slot's
        block list into numpy arrays, then returns the blocks through the
        ordinary ``free_slot`` path — refcounts, the commitment ledger and
        prefix retention all behave exactly as if the request completed.
        Shared-prefix blocks are *copied*, not stolen: other holders keep
        them, and the resumed slot gets private replicas at ``swap_in``.
        Returns ``(host_kv, new_cache_state)``; ``host_kv`` is the cache
        pytree restricted to the slot's (padded) block row plus the live
        block count, opaque to the engine.

        With ``defer=True`` the D2H copy is started asynchronously and
        ``host_kv["caches"]`` is a ``HostSwapHandle`` the caller resolves
        later (the gather lands in a fresh device buffer either way, so
        the released blocks may be reused immediately) — the fault-
        recovery rollback uses this to overlap the transfer with the next
        scheduler plan instead of stalling the step loop on it."""
        blocks = self._slot_blocks.get(slot)
        if blocks is None:
            raise RuntimeError(f"slot {slot} holds no blocks to swap out")
        gather_fn, _ = self._swap_fns()
        idx = np.zeros((self.blocks_per_slot,), np.int32)   # pad: trash
        idx[:len(blocks)] = blocks
        gathered = gather_fn(cache_state["caches"], jnp.asarray(idx))
        host = {"n_blocks": len(blocks),
                "caches": (HostSwapHandle(gathered) if defer
                           else jax.device_get(gathered))}
        self.swap_outs += 1
        self.preempt_swap_bytes += len(blocks) * self.block_bytes()
        return host, self.free_slot(cache_state, slot)

    def checkpoint_slot(self, cache_state, slot):
        """Non-destructive host checkpoint of a live slot's drawn blocks —
        ``swap_out``'s wire format without the release (refcounts, ledger
        and table row untouched), so an engine snapshot can persist every
        active slot's K/V while the engine keeps serving. Restores through
        the ordinary ``swap_in`` path on a cold engine."""
        blocks = self._slot_blocks.get(slot)
        if blocks is None:
            raise RuntimeError(f"slot {slot} holds no blocks to checkpoint")
        gather_fn, _ = self._swap_fns()
        idx = np.zeros((self.blocks_per_slot,), np.int32)   # pad: trash
        idx[:len(blocks)] = blocks
        return {"n_blocks": len(blocks),
                "caches": jax.device_get(
                    gather_fn(cache_state["caches"], jnp.asarray(idx)))}

    def available_blocks(self) -> int:
        """Free blocks not spoken for by outstanding commitments (the
        quantity ``can_admit``/``can_resume`` gate on), public for the
        engine's preemption-feasibility check."""
        return self._available()

    def slot_commitment(self, slot: int) -> int:
        """Upper bound on the blocks admission would recover if ``slot``
        were preempted: its drawn blocks plus its undrawn ledger gap
        (shared blocks another slot still refcounts are counted — the
        bound is optimistic, which only risks a preemption that recovers
        less than hoped, never a refused feasible one)."""
        return (len(self._slot_blocks.get(slot, ()))
                + self._slot_gap.get(slot, 0))

    def can_resume(self, prompt_len: int, max_new: int) -> bool:
        """Whether a swapped-out request fits back in: its blocks return as
        *private* (worst-case commitment, no sharing discount), so resume
        demand is the full ``blocks_needed`` against the uncommitted free
        list."""
        return self.blocks_needed(prompt_len, max_new) <= self._available()

    def swap_in(self, cache_state, slot, host_kv, prompt_len: int,
                max_new: int):
        """Restore a swapped-out request into ``slot``: draw fresh private
        blocks for the checkpointed content, scatter the host K/V back
        byte-for-byte, and re-commit the undrawn budget tail to the ledger
        (look-ahead top-ups resume exactly where they left off). Only call
        after ``can_resume`` said yes."""
        total = self.blocks_needed(prompt_len, max_new)
        n_now = host_kv["n_blocks"]
        if total > self._available():
            raise RuntimeError(
                f"paged pool exhausted on resume: need {total} blocks, "
                f"{self._available()} available")
        if slot in self._slot_blocks:
            raise RuntimeError(f"slot {slot} already holds blocks")
        fresh = self._take_free(n_now)
        for blk in fresh:
            self._ref[blk] = 1
        self._slot_blocks[slot] = fresh
        self._slot_shared[slot] = 0
        self._slot_start[slot] = prompt_len
        self._slot_cap[slot] = total
        self._slot_gap[slot] = total - n_now
        self._gap_total += total - n_now
        self.blocks_allocated_total += n_now
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        self.swap_ins += 1
        self.preempt_swap_bytes += n_now * self.block_bytes()
        _, scatter_fn = self._swap_fns()
        phys = np.full((self.blocks_per_slot,), self.num_blocks, np.int32)
        phys[:n_now] = fresh                    # pad: OOB, writes dropped
        caches = scatter_fn(cache_state["caches"],
                            resolve_swap_caches(host_kv),
                            jnp.asarray(phys))
        # whole-array host round-trip: a sliced eager update would compile
        # per slot index (see ServingEngine._edit_state)
        tables = np.array(cache_state["tables"])
        tables[slot] = -1
        tables[slot, :n_now] = fresh
        return {"caches": caches, "tables": jnp.asarray(tables)}

    def assert_invariants(self, cache_state=None) -> None:
        """Allocator accounting invariants (tests call this after runs and
        mid-traffic): block conservation across slots/tiers, ledger
        consistency, and index/retention coherence. With ``cache_state``
        (the live device state) the sweep extends to sharded pools:
        per-shard byte conservation must agree with the host-global
        ledger's view of the pool."""
        held = [b for blocks in self._slot_blocks.values() for b in blocks]
        # every non-trash block is either held by exactly the slots that
        # refcount it, or parked in exactly one free tier
        assert sorted(held + list(self._free_plain)
                      + list(self._free_cached)) == sorted(
            list(range(1, self.num_blocks)) + [
                b for b, r in self._ref.items() for _ in range(r - 1)])
        assert all(r > 0 for r in self._ref.values())
        assert set(self._ref) == set(held)
        # ledger: outstanding commitments never exceed the free list
        assert self._gap_total == sum(self._slot_gap.values())
        assert 0 <= self._gap_total <= (len(self._free_plain)
                                        + len(self._free_cached))
        # per-slot ledger bounds (preemption swaps slots in and out of the
        # pool mid-flight, so check every live slot, not just the sums):
        # drawn blocks never exceed the admission-time worst case, undrawn
        # commitments stay non-negative, and drawn + undrawn covers the
        # worst case (equality modulo the COW block, which draws one block
        # beyond the shared plan)
        for slot, blocks in self._slot_blocks.items():
            cap = self._slot_cap[slot]
            gap = self._slot_gap[slot]
            assert 0 <= gap and cap >= 1
            assert len(blocks) <= cap + 1, (slot, len(blocks), cap)  # +COW
            assert len(blocks) + gap >= cap, (slot, len(blocks), gap, cap)
        # retention: every cached free block is indexed, and the index's
        # reverse map agrees
        for blk in self._free_cached:
            assert self._block_key.get(blk) is not None
        for key, blk in self._index.items():
            assert self._block_key.get(blk) == key
        for blk, key in self._block_key.items():
            assert self._index.get(key) == blk
        if cache_state is not None:
            self._assert_pool_placement(cache_state)

    def _assert_pool_placement(self, cache_state) -> None:
        """Sharded-pool accounting: the device pool must still be the
        ledger's pool (width = ``num_blocks``), every K/V leaf must be
        split exactly ``kv_shards`` ways on its KV-head dim (or replicated
        when not divisible), each device must hold one equal-size shard,
        and the summed per-device bytes must equal
        ``hbm_bytes_per_device()`` — per-shard byte conservation agreeing
        with the host-global ledger."""
        per_dev_total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                cache_state["caches"])[0]:
            if not hasattr(leaf, "sharding"):
                continue
            assert leaf.shape[1] == self.num_blocks, (
                f"pool width {leaf.shape[1]} != ledger's {self.num_blocks}")
            shard = leaf.sharding.shard_shape(leaf.shape)
            shard_elems = math.prod(shard)
            total_elems = math.prod(leaf.shape)
            assert shard_elems and total_elems % shard_elems == 0
            want = _kv_shard_divisor(path, leaf.shape, self.kv_shards)
            assert total_elems // shard_elems == want, (
                f"pool leaf {jax.tree_util.keystr(path)}: split "
                f"{total_elems // shard_elems} ways, ledger expects {want}")
            shard_bytes = shard_elems * leaf.dtype.itemsize
            assert all(s.data.nbytes == shard_bytes
                       for s in leaf.addressable_shards)
            per_dev_total += shard_bytes
        if per_dev_total:
            assert per_dev_total == self.hbm_bytes_per_device(), (
                per_dev_total, self.hbm_bytes_per_device())

    # -- chunked-prefill admission seam --------------------------------------
    def begin_slot(self, cache_state, slot, table_row, shared_blocks):
        """Wipe per-token positions of the row's *fresh* blocks (they may be
        reused from a finished tenant whose stale positions would alias into
        the new request's causal mask) and install the table row. The
        ``shared_blocks`` leading entries hold live shared-prefix (or COW
        copy) content and must be left intact. Singleton delegation to
        ``begin_slots`` — one wipe implementation to keep correct."""
        return self.begin_slots(cache_state,
                                jnp.reshape(slot, (1,)),
                                jnp.reshape(table_row,
                                            (1, self.blocks_per_slot)),
                                jnp.reshape(shared_blocks, (1,)))

    def begin_slots(self, cache_state, slots, table_rows, shared_blocks):
        """Batched ``begin_slot``: apply many slots' table top-ups in one
        traced update (one dispatch when several slots cross a block
        boundary in the same plan, instead of one replay per slot).
        ``slots`` (S,), ``table_rows`` (S, M), ``shared_blocks`` (S,);
        callers pad to a fixed S by *repeating* entries — duplicate rows
        write identical values, so the scatter stays well-defined."""
        n = self.num_blocks
        idx = jnp.arange(self.blocks_per_slot)[None, :]
        wipe = (idx >= shared_blocks[:, None]) & (table_rows >= 0)
        phys = jnp.where(wipe, table_rows, n)         # n = OOB -> dropped

        def clear(c):
            return {key: (leaf.at[:, phys].set(-1) if key == "pos" else leaf)
                    for key, leaf in c.items()}

        caches = _map_kv_dicts(clear, cache_state["caches"])
        tables = cache_state["tables"].at[slots].set(table_rows)
        return {"caches": caches, "tables": tables}

    def slot_view(self, cache_state, slot, ctx=None):
        tables = jax.lax.dynamic_slice_in_dim(cache_state["tables"], slot, 1,
                                              axis=0)
        if ctx is not None:
            # visible context = the leading table entries covering positions
            # below ctx; later entries hold no position the chunk may see
            m = min(-(-ctx // self.block_size), self.blocks_per_slot)
            tables = tables[:, :m]
        return cache_state["caches"], tables

    def slot_update(self, cache_state, slot, view_caches):
        # the view *is* the global pool: chunk writes already landed there
        return {"caches": view_caches, "tables": cache_state["tables"]}

    # -- admission-time install ---------------------------------------------
    def prefill_fill(self, cache_state, one_caches, slot, length, table_row):
        """Scatter a prefilled per-request cache into the slot's blocks.

        Tokens are routed by their *position* (block ``pos // bs``, offset
        ``pos % bs``), so ring-wrapped prefill caches (windowed layers with
        window < bucket) install correctly, and right-pad entries
        (pos ≥ length) are parked in the trash block with pos −1 — unlike
        the ring, the paged cache never exposes pad K/V at all.

        The row's blocks may be reused from a completed request, so their
        per-token positions are wiped to −1 first: a stale position from the
        previous tenant can land inside the new request's causal mask, and
        unlike the ring (which overwrites the whole cache line at admission)
        the paged install only writes the new prompt's prefix."""
        bs = self.block_size
        row_safe = jnp.where(table_row >= 0, table_row, 0)

        def fill(c, o):
            src_pos = o["pos"][0, 0]                      # (W,) layer-0 row
            valid = (src_pos >= 0) & (src_pos < length)
            logical = jnp.clip(src_pos, 0, self.max_seq_len - 1) // bs
            row_phys = jnp.take(table_row, logical)
            phys = jnp.where(valid & (row_phys >= 0), row_phys, 0)
            off = jnp.where(valid, src_pos % bs, 0)
            new = {}
            for key, leaf in c.items():
                if key == "pos":
                    cleared = leaf.at[:, row_safe, :].set(-1)
                    new[key] = cleared.at[:, phys, off].set(
                        jnp.where(valid, src_pos, -1)[None, :])
                else:
                    new[key] = leaf.at[:, phys, off].set(o[key][:, 0])
            return new

        caches = _map_kv_dicts(fill, cache_state["caches"], one_caches)
        tables = cache_state["tables"].at[slot].set(table_row)
        return {"caches": caches, "tables": tables}

    # -- accounting ----------------------------------------------------------
    def block_bytes(self) -> int:
        """Bytes one pool block costs across all layers."""
        total = 0
        for leaf in jax.tree.leaves(self._proto):
            per_tok = math.prod(leaf.shape[:1] + leaf.shape[3:])
            total += per_tok * self.block_size * leaf.dtype.itemsize
        return total

    def block_bytes_per_device(self) -> int:
        """Per-device bytes of one pool block: K/V leaves split their
        KV-head dim ``kv_shards`` ways when divisible; the per-token
        position leaf is replicated on every device."""
        total = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self._proto)[0]:
            per_tok = math.prod(leaf.shape[:1] + leaf.shape[3:])
            per_tok //= _kv_shard_divisor(path, leaf.shape, self.kv_shards)
            total += per_tok * self.block_size * leaf.dtype.itemsize
        return total

    def hbm_bytes(self) -> int:
        return self.block_bytes() * self.num_blocks

    def hbm_bytes_per_device(self) -> int:
        return self.block_bytes_per_device() * self.num_blocks

    def hbm_bytes_per_slot(self) -> float:
        """Average bytes actually *drawn* per admitted request (the ring
        equivalent is a constant ``max_seq_len`` line). Lazy allocation
        makes this live-token-accurate: committed-but-undrawn budget
        blocks (unreached look-ahead) don't count."""
        if self.admitted == 0:
            return float(self.block_bytes() * self.blocks_per_slot)
        return self.block_bytes() * self.blocks_allocated_total / self.admitted


def make_backend(kind, lm, params, *, batch_slots: int, max_seq_len: int,
                 proto_len: int = 16, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_sharing: bool = True) -> KVCacheBackend:
    if isinstance(kind, KVCacheBackend):
        return kind
    if kind == "ring":
        return RingCache(lm, params, batch_slots=batch_slots,
                         max_seq_len=max_seq_len, proto_len=proto_len)
    if kind == "paged":
        return PagedCache(lm, params, batch_slots=batch_slots,
                          max_seq_len=max_seq_len, proto_len=proto_len,
                          block_size=block_size, num_blocks=num_blocks,
                          prefix_sharing=prefix_sharing)
    raise ValueError(f"unknown cache backend {kind!r} "
                     "(expected 'ring' or 'paged')")
