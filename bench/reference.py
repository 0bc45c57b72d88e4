"""What every family's plain reference shares.

A family's reference (``bench/families/<family>/reference.py``) imports
nothing of the program, runs in float32 at
``default_matmul_precision("highest")``, and gives the final hidden states
of a whole sequence: once as published and, for the control, once with
every matmul operand rounded to float8 by ``q8``. ``head_gaps`` turns both
into the gaps the check compares, and ``gaps_of`` pads a sequence to its
``bucket``, runs the family's jitted function and keeps the served
positions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_BLOCK = 256     # rows per block of the output head
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def bucket(n: int) -> int:
    """Padded sequence length: the next power of two, at least 256."""
    return max(256, 1 << (int(n) - 1).bit_length())


def q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis of the matmul it feeds)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def head_gaps(h, hc, table, tokens, control: bool):
    """Per-position gaps under the output head ``table`` (vocab, d): for
    the final states ``h``, how far the next token's logit lies below the
    best; and, with ``control``, how far below the best lies the token
    that the float8 states ``hc`` under a float8 head put first. A target
    past the vocabulary reads ``inf``."""
    vocab = table.shape[0]
    targets = jnp.concatenate([tokens[1:], tokens[-1:]])
    nb = h.shape[0] // HEAD_BLOCK

    def head(xs):
        hb, hcb, tb = xs
        e = table.astype(F32)
        logits = hb @ e.T
        best = jnp.max(logits, -1)
        at = jnp.take_along_axis(logits, jnp.clip(tb, 0, vocab - 1)[:, None],
                                 -1)[:, 0]
        served = jnp.where(tb < vocab, best - at, jnp.inf)
        if not control:
            return served, served
        pick = jnp.argmax(q8(hcb, -1) @ q8(e, -1).T, -1)
        ctl = best - jnp.take_along_axis(logits, pick[:, None], -1)[:, 0]
        return served, ctl

    served, ctl = jax.lax.map(head, (h.reshape(nb, HEAD_BLOCK, -1),
                                     hc.reshape(nb, HEAD_BLOCK, -1),
                                     targets.reshape(nb, HEAD_BLOCK)))
    return served.reshape(-1), ctl.reshape(-1)


def gaps_of(fn, static, w, tokens: np.ndarray, first: int, control: bool):
    """Run ``fn(static, control, w, padded_tokens) -> (served, ctl)``, a
    family's jitted reference, over ``tokens`` padded to their bucket, and
    keep the gaps of ``tokens[first:]``: ``(served, ctl)``, ``ctl`` None
    unless ``control``."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    padded = np.zeros(bucket(n), np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        served, ctl = fn(static, control, w, jnp.asarray(padded))
    served = np.asarray(served[first - 1:n - 1], np.float64)
    ctl = np.asarray(ctl[first - 1:n - 1], np.float64) if control else None
    return served, ctl
