"""Mesh placement for the serving stack (tensor-parallel decode).

What shards, what stays host-global: params split per the decode-mode
``launch/sharding_rules`` (attention/KV heads, MLP and vocab projections on
the mesh's ``model`` axis); KV pools — ring lines ``(L, B, W, KV, hd)`` and
paged pools ``(L, N, bs, KV, hd)`` — split on the KV-head dim (dim 3) over
:data:`KV_HEAD_AXIS`; a mesh whose axis does not divide the KV heads is
refused. Everything the host mutates or reasons about stays replicated:
block tables, position slots, MLA latent caches (no head dim), the free
list and commitment ledger (plain Python on the host already).

This module deliberately imports only ``jax``, ``repro.sharding`` and
``repro.launch.sharding_rules`` so the serving package can pull it in from
``kv_cache``/``engine`` without an import cycle.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.launch import sharding_rules as sr
from repro.sharding import current_mesh

# the mesh axis that splits KV heads: K/V pools here, and the decode
# kernels, which run per shard of it (``kernels.ops``)
KV_HEAD_AXIS = "model"


def model_axis_size(mesh: Optional[Mesh]) -> int:
    """Ways the KV-head mesh axis splits KV heads (1 without a mesh)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(KV_HEAD_AXIS, 1))


def kv_head_split():
    """``(mesh, axis)`` the cached-attention kernels split heads over
    inside the active rules context, or None without a mesh. The pools
    are placed by :func:`cache_pspecs`, which refuses a mesh whose axis
    does not divide the KV heads, so the split always divides."""
    mesh = current_mesh()
    return None if mesh is None else (mesh, KV_HEAD_AXIS)


def serving_rules(mesh: Mesh):
    """Default activation rules for mesh-aware decode: the decode-mode
    logical-axis rules the training dry-runs already validated."""
    return sr.act_rules(mesh, "decode")


def param_shardings(mesh: Mesh, lm):
    """NamedSharding tree for ``lm``'s params under decode-mode rules."""
    abstract, axes = lm.abstract()
    return sr.named(mesh, sr.param_pspecs(mesh, abstract, axes,
                                          mode="decode"))


def init_params(lm, rng, mesh: Optional[Mesh] = None):
    """``lm``'s params from ``rng``, as one jitted program. With a mesh its
    outputs land straight in their decode shardings, so no device ever
    holds the whole tree (a model that needs the mesh could not be built
    on one chip first); without one, on the default device."""
    kw = {} if mesh is None else {"out_shardings": param_shardings(mesh, lm)}
    return jax.jit(lambda r: lm.init(r)[0], **kw)(rng)


def place_params(mesh: Mesh, lm, params):
    """Commit params to the mesh (KV/attention heads, MLP, vocab on
    'model'; output-side embed dims replicated — decode rules)."""
    return jax.device_put(params, param_shardings(mesh, lm))


def _kv_pool_leaf(path, leaf) -> bool:
    """True for the K/V pool leaves both backends store: ring lines
    (L, B, W, KV, hd) and paged pools (L, N, bs, KV, hd). MLA latents
    (``ckv``/``krope``, no head dim) and ``pos`` slots stay replicated."""
    name = path[-1].key if hasattr(path[-1], "key") else ""
    return name in ("k", "v") and getattr(leaf, "ndim", 0) == 5


def cache_pspecs(mesh: Mesh, cache_state):
    """PartitionSpec tree matching a serving cache state pytree: K/V pool
    leaves split dim 3 (KV heads) on :data:`KV_HEAD_AXIS`, everything
    else — tables, pos, latents — replicated (host-global semantics).
    Raises ValueError when the axis does not divide a pool's KV heads:
    replicating that pool would gather it onto every device, and the
    decode kernels could not run per shard."""
    msize = model_axis_size(mesh)

    def spec(path, leaf):
        dims = [None] * leaf.ndim
        if _kv_pool_leaf(path, leaf):
            if leaf.shape[3] % msize:
                raise ValueError(
                    f"mesh axis {KV_HEAD_AXIS!r} of size {msize} does not "
                    f"divide the {leaf.shape[3]} KV heads of cache leaf "
                    f"{jax.tree_util.keystr(path)}; use a {KV_HEAD_AXIS} "
                    f"axis that divides them")
            dims[3] = KV_HEAD_AXIS
        return PS(*dims)

    return jax.tree_util.tree_map_with_path(spec, cache_state)


def cache_shardings(mesh: Mesh, cache_state):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        cache_pspecs(mesh, cache_state),
                        is_leaf=lambda x: isinstance(x, PS))


def place_cache_state(mesh: Mesh, cache_state):
    """Commit a backend's cache state to the mesh."""
    return jax.device_put(cache_state, cache_shardings(mesh, cache_state))


def assert_cache_placement(mesh: Mesh, cache_state) -> None:
    """Placement-coherence sweep: every device-array leaf must carry
    exactly the spec :func:`cache_pspecs` prescribes, split into
    equal-size shards whose bytes conserve the global leaf (one shard
    per device, shard_bytes x distinct_shards == leaf bytes)."""
    expected = cache_shardings(mesh, cache_state)
    ndev = mesh.size

    def check(path, leaf, want):
        if not hasattr(leaf, "sharding"):
            return
        assert leaf.sharding.is_equivalent_to(want, leaf.ndim), (
            f"cache leaf {jax.tree_util.keystr(path)}: sharding "
            f"{leaf.sharding} != expected {want}")
        shard_elems = math.prod(leaf.sharding.shard_shape(leaf.shape))
        total = math.prod(leaf.shape)
        assert shard_elems and total % shard_elems == 0, (
            f"cache leaf {jax.tree_util.keystr(path)}: shard shape "
            f"does not tile the global shape")
        per_dev = [s.data.nbytes for s in leaf.addressable_shards]
        shard_bytes = shard_elems * leaf.dtype.itemsize
        assert len(per_dev) == ndev and \
            all(b == shard_bytes for b in per_dev), (
                f"cache leaf {jax.tree_util.keystr(path)}: expected one "
                f"{shard_bytes}-byte shard per device, got {per_dev}")

    jax.tree_util.tree_map_with_path(check, cache_state, expected)
