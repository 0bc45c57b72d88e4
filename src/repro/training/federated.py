"""Tensor-level federated trainer (ECC training pattern, paper §2).

Mirrors the component-level ``core.patterns.training`` but at mesh scale:
each EC maps to a slice of the ``data`` axis; local steps run independently
per slice (no gradient sync), and every ``sync_every`` steps a FedAvg
all-reduce over the EC axis averages the diverged replicas — the WAN round.
Implemented with ``shard_map`` so the local steps are truly independent (no
cross-EC collectives inside the local phase).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.optim import sgd_init, sgd_update


class FederatedTrainer:
    """FedAvg over the mesh's ``data`` axis (each shard = one EC)."""

    def __init__(self, loss_fn: Callable, mesh: Mesh, *, lr: float = 0.05,
                 local_steps: int = 4, axis: str = "data"):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.lr = lr
        self.local_steps = local_steps
        self.axis = axis
        self._round = self._build()

    def _build(self):
        axis = self.axis
        loss_fn = self.loss_fn
        lr = self.lr
        local_steps = self.local_steps

        def fed_round(params, opt, batch):
            """params are per-EC replicas stacked on a leading axis that is
            sharded over the EC mesh axis; batch likewise."""
            def local(params, opt, batch):
                # strip the leading local axis of size 1 inside the shard
                p = jax.tree.map(lambda x: x[0], params)
                o = jax.tree.map(lambda x: x[0], opt)
                b = jax.tree.map(lambda x: x[0], batch)

                def one_step(carry, xs):
                    p, o = carry
                    loss, g = jax.value_and_grad(loss_fn)(p, b)
                    p, o = sgd_update(p, g, o, lr=lr)
                    return (p, o), loss

                (p, o), losses = jax.lax.scan(
                    one_step, (p, o), None, length=local_steps)
                # FedAvg: all-reduce mean over the EC axis (the WAN round)
                p = jax.tree.map(
                    functools.partial(jax.lax.pmean, axis_name=axis), p)
                mean_loss = jax.lax.pmean(losses[-1], axis_name=axis)
                add = lambda x: x[None]
                return (jax.tree.map(add, p), jax.tree.map(add, o),
                        mean_loss[None])

            spec_leading = PS(self.axis)
            return jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(spec_leading, spec_leading, spec_leading),
                out_specs=(spec_leading, spec_leading, spec_leading),
                check_vma=False,
            )(params, opt, batch)

        return jax.jit(fed_round)

    # -- host API ---------------------------------------------------------------
    def replicate(self, params):
        """Stack one replica per EC along a leading sharded axis."""
        n = self.mesh.shape[self.axis]
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), params)
        sharding = NamedSharding(self.mesh, PS(self.axis))
        return jax.device_put(stacked, sharding)

    def init_opt(self, replicated_params):
        # one optimizer state per EC, every leaf (incl. the scalar step)
        # stacked on the sharded leading axis
        local = jax.tree.map(lambda x: x[0], replicated_params)
        return self.replicate(sgd_init(local))

    def round(self, params, opt, batch):
        """batch: leading axis = num ECs (local datasets, non-IID allowed)."""
        return self._round(params, opt, batch)

    def unreplicate(self, params):
        return jax.tree.map(lambda x: x[0], params)
