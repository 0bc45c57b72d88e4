"""Fused confidence-gate Pallas kernel (the paper's per-item gate at
LM-token scale).

Given a (T, V) logits block, computes in one pass over VMEM tiles:
max-softmax confidence (via streaming max/logsumexp over vocab tiles),
the BP route code (accept/drop/escalate), and per-block route counts —
avoiding the full softmax materialization the naive path pays at
vocab 100k+ x 500k tokens.

TPU mapping: grid (num_token_blocks, num_vocab_blocks); vocab is the
sequential axis with (m, lse) carried in VMEM scratch; the route decision
and counts are emitted on the last vocab step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(logits_ref, conf_ref, routes_ref, counts_ref, m_ref, s_ref, *,
            hi: float, lo: float, num_v: int, vocab: int, block_v: int,
            tokens: int, block_t: int):
    it = pl.program_id(0)
    iv = pl.program_id(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        s_ref[...] = jnp.zeros_like(s_ref)

    x = logits_ref[...].astype(jnp.float32)      # (bt, bv)
    vpos = iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 1)
    x = jnp.where(vpos < vocab, x, NEG_INF)      # vocab-padding mask
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=1, keepdims=True))
    s_ref[...] = (s_ref[...] * jnp.exp(m_prev - m_new)
                  + jnp.sum(jnp.exp(x - m_new), axis=1, keepdims=True))
    m_ref[...] = m_new

    @pl.when(iv == num_v - 1)
    def _flush():
        # conf = exp(m - lse) = 1 / sum(exp(x - m))
        conf = 1.0 / jnp.maximum(s_ref[...], 1e-30)  # (bt, 1)
        conf_ref[...] = conf
        routes = jnp.where(conf >= hi, 0,
                           jnp.where(conf < lo, 1, 2)).astype(jnp.int32)
        routes_ref[...] = routes
        # count only real (non-padded) token rows
        tpos = it * block_t + jax.lax.broadcasted_iota(
            jnp.int32, routes.shape, 0)
        live = tpos < tokens
        counts_ref[0, 0] = jnp.sum(((routes == 0) & live).astype(jnp.int32))
        counts_ref[0, 1] = jnp.sum(((routes == 1) & live).astype(jnp.int32))
        counts_ref[0, 2] = jnp.sum(((routes == 2) & live).astype(jnp.int32))


def cascade_gate(logits, *, hi: float = 0.8, lo: float = 0.1,
                 block_t: int = 256, block_v: int = 2048,
                 interpret: bool = False):
    """logits: (T, V) -> (conf (T,), routes (T,) int32, counts (3,) int32)."""
    t, v = logits.shape
    block_t = min(block_t, t)
    block_v = min(block_v, v)
    pad_t = (-t) % block_t
    pad_v = (-v) % block_v
    if pad_t or pad_v:
        logits = jnp.pad(logits, ((0, pad_t), (0, pad_v)),
                         constant_values=NEG_INF)
    nt = logits.shape[0] // block_t
    nv = logits.shape[1] // block_v

    kernel = functools.partial(_kernel, hi=hi, lo=lo, num_v=nv, vocab=v,
                               block_v=block_v, tokens=t, block_t=block_t)
    conf, routes, counts = pl.pallas_call(
        kernel,
        grid=(nt, nv),
        in_specs=[pl.BlockSpec((block_t, block_v),
                               lambda it, iv: (it, iv))],
        out_specs=[
            pl.BlockSpec((block_t, 1), lambda it, iv: (it, 0)),
            pl.BlockSpec((block_t, 1), lambda it, iv: (it, 0)),
            pl.BlockSpec((1, 3), lambda it, iv: (it, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((logits.shape[0], 1), jnp.float32),
            jax.ShapeDtypeStruct((logits.shape[0], 1), jnp.int32),
            jax.ShapeDtypeStruct((nt, 3), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_t, 1), jnp.float32),
            pltpu.VMEM((block_t, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits)
    return conf[:t, 0], routes[:t, 0], jnp.sum(counts, axis=0)
