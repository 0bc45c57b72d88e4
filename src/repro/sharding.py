"""Logical activation sharding hints.

Model code is mesh-agnostic: it annotates activations with *logical* axis
names via :func:`hint`. The launcher activates a mesh + rule set with
:func:`use_rules`; outside that context hints are no-ops (single-device smoke
tests never touch device state).
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

# logical activation axes
BATCH = "act_batch"
SEQ = "act_seq"
EMBED = "act_embed"
HEADS = "act_heads"
KV = "act_kv"
VOCAB = "act_vocab"
EXPERT = "act_expert"
EXP_SLOT = "act_exp_slot"
MLP = "act_mlp"

_state = threading.local()


def _current():
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, rules: Dict[str, object]):
    """Activate (mesh, logical-axis -> mesh-axis rules) for hints."""
    prev = _current()
    _state.ctx = (mesh, dict(rules))
    try:
        yield
    finally:
        _state.ctx = prev


def resolve(rules: Dict[str, object], axes: Sequence[Optional[str]],
            shape: Optional[Tuple[int, ...]] = None,
            mesh: Optional[Mesh] = None) -> PS:
    """Map logical axes to a PartitionSpec.

    Two pragmatic guards: a sharding is dropped when the dim is not divisible
    by the mesh-axis product (e.g. 9 heads over a 16-way model axis -> the
    projection is replicated on 'model'), and a mesh axis is used at most
    once per spec in logical-axis order (e.g. deepseek expert weights
    (E, D, F): EXPERT wins 'model', so MLP falls back to replicated; mixtral
    (8 experts, non-divisible) instead gives 'model' to MLP — tensor
    parallelism inside each expert)."""
    spec = []
    used = set()
    for i, ax in enumerate(axes):
        mesh_axes = rules.get(ax) if ax is not None else None
        if mesh_axes is None:
            spec.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        mesh_axes = tuple(m for m in mesh_axes if m not in used)
        if not mesh_axes:
            spec.append(None)
            continue
        if shape is not None and mesh is not None:
            size = 1
            for m in mesh_axes:
                size *= mesh.shape[m]
            if shape[i] % size != 0:
                spec.append(None)
                continue
        used.update(mesh_axes)
        spec.append(tuple(mesh_axes) if len(mesh_axes) > 1 else mesh_axes[0])
    return PS(*spec)


def current_mesh() -> Optional[Mesh]:
    """The mesh of the active :func:`use_rules` context (None outside)."""
    ctx = _current()
    return None if ctx is None else ctx[0]


def hint(x, axes: Sequence[Optional[str]]):
    """Constrain activation sharding if a mesh context is active."""
    ctx = _current()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = resolve(rules, axes, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@contextlib.contextmanager
def maybe_rules(mesh: Optional[Mesh], rules: Optional[Dict[str, object]]):
    """:func:`use_rules` when a mesh is given, a literal no-op otherwise.

    The serving/model entry points take ``mesh=None, rules=None`` and wrap
    their bodies in this: with ``mesh=None`` every trace is byte-identical
    to the pre-mesh code path (hints never fire, no new jit arguments), so
    the single-device executable set is provably unchanged."""
    if mesh is None:
        yield
        return
    with use_rules(mesh, rules or {}):
        yield
