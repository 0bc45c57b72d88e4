"""What every family's seeded weights share.

A family (``bench/families/<family>/``) makes its weights from ``--seed``
with the key ``key_for`` gives, in one jitted call on the device, and
hands the program its parameter tree over the same arrays; ``check_tree``
refuses a tree that differs from the program's own.
"""
from __future__ import annotations

import jax
import numpy as np


def key_for(seed: int, stream: int = 0):
    """A PRNG key from any whole number, 64 bits and beyond."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    data = np.zeros(4, np.uint32)
    data[:2] = words
    # the hardware generator: threefry took 24 s for 5.4 GB on a v5e
    return jax.random.wrap_key_data(data, impl="rbg")


def check_tree(params, abstract) -> None:
    """Refuse to serve when the program's tree differs from the one built
    here: a leaf of another name, shape or dtype would be served wrongly."""
    got = jax.tree_util.tree_flatten_with_path(params)[0]
    want = jax.tree_util.tree_flatten_with_path(abstract)[0]
    g = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in got}
    w = {jax.tree_util.keystr(p): (x.shape, x.dtype) for p, x in want}
    if g != w:
        diff = sorted(set(g.items()) ^ set(w.items()))
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter tree: {diff[:6]}")
