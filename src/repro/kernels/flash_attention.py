"""Blockwise (flash) attention Pallas kernel — causal, sliding-window, GQA.

TPU mapping: grid (B, H, num_q_blocks, num_k_blocks); the last axis is the
sequential ("arbitrary") reduction over KV blocks with the streaming-softmax
carry (acc, m, l) held in VMEM scratch. Per-step working set is
``(block_q x head_dim) + 2 x (block_k x head_dim)`` tiles — sized so that
q/k/v/o tiles plus the f32 accumulator fit VMEM (block 128/128 with hd<=256:
< 1 MiB). MXU work is the (block_q x hd) @ (hd x block_k) score matmul and
the (block_q x block_k) @ (block_k x hd) value matmul — both 128-aligned.

GQA folds the query-group into the head grid axis: the k/v index map selects
head ``h // group`` so KV tiles are reused across the group's q heads.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: Optional[int],
            sq: int, sk: int, block_q: int, block_k: int, num_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, :, 0, :]                     # (bq, hd)
    k = k_ref[0, :, 0, :]                     # (bk, hd)
    v = v_ref[0, :, 0, :]

    qpos = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    qpos = qpos + (sk - sq)                   # right-aligned queries
    kpos = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)

    run = True
    if causal:
        # whole block masked out when its first k position is past the last q
        run = (ik * block_k) <= (iq * block_q + block_q - 1 + (sk - sq))
    if window is not None:
        run = jnp.logical_and(
            run, (ik * block_k + block_k - 1)
            > (iq * block_q + (sk - sq) - window))

    @pl.when(run)
    def _compute():
        s = jax.lax.dot_general(
            q.astype(jnp.float32), k.astype(jnp.float32),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        valid = jnp.ones_like(s, dtype=jnp.bool_)
        valid &= kpos < sk                                # tail padding
        if causal:
            valid &= kpos <= qpos
        if window is not None:
            valid &= kpos > (qpos - window)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(ik == num_k - 1)
    def _flush():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q: (B, Sq, H, hd); k, v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    Queries are right-aligned against keys (q position i attends to keys up
    to ``i + Sk - Sq``), matching decode/prefill semantics.
    """
    b, sq, h, hd = q.shape
    sk, kv = k.shape[1], k.shape[2]
    assert h % kv == 0
    g = h // kv
    scale = scale if scale is not None else hd ** -0.5
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq = q.shape[1] // block_q
    nk = k.shape[1] // block_k

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, sq=sq, sk=sk,
        block_q=block_q, block_k=block_k, num_k=nk)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, hd),
                         lambda b_, h_, iq, ik: (b_, iq, h_, 0)),
            pl.BlockSpec((1, block_k, 1, hd),
                         lambda b_, h_, iq, ik, g_=g: (b_, ik, h_ // g_, 0)),
            pl.BlockSpec((1, block_k, 1, hd),
                         lambda b_, h_, iq, ik, g_=g: (b_, ik, h_ // g_, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, hd),
                               lambda b_, h_, iq, ik: (b_, iq, h_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, q.shape[1], h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq]
