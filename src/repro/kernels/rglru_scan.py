"""Blocked RG-LRU linear-recurrence scan Pallas kernel.

Computes h_t = a_t * h_{t-1} + b_t over the time axis.

TPU mapping: grid (B, num_width_blocks, num_time_blocks); the time axis is
sequential with the running state h carried in VMEM scratch. Within a
(block_t x block_w) tile the recurrence is solved with a Hillis–Steele
doubling scan (log2(block_t) shifted elementwise passes) — numerically safe
(only products of a in (0,1], no divisions), VPU-friendly, and keeps the
tile resident in VMEM. block_w is lane-aligned (multiples of 128) so each
pass is a full-width vector op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, carry_ref, *,
            block_t: int, num_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_ref[...] = h0_ref[0]            # (bw,)

    a = a_ref[0].astype(jnp.float32)          # (bt, bw)
    b = b_ref[0].astype(jnp.float32)

    # Hillis–Steele inclusive scan of the affine maps (a, b):
    # compose (a2,b2) o (a1,b1) = (a1*a2, a2*b1 + b2)
    d = 1
    while d < block_t:
        # out-of-range neighbours are the identity map (A=1, B=0)
        a_sh = jnp.pad(a, ((d, 0), (0, 0)), constant_values=1.0)[:block_t]
        b_sh = jnp.pad(b, ((d, 0), (0, 0)))[:block_t]
        b = b + a * b_sh
        a = a * a_sh
        d *= 2
    # fold in the carried state: h_t = A_t * h_carry + B_t
    h = a * carry_ref[...][None, :] + b
    h_ref[0] = h.astype(h_ref.dtype)
    carry_ref[...] = h[block_t - 1]

    @pl.when(it == num_t - 1)
    def _flush():
        hlast_ref[0] = carry_ref[...].astype(hlast_ref.dtype)


def rglru_scan(a, b, h0, *, block_t: int = 128, block_w: int = 128,
               interpret: bool = False):
    """a, b: (B, S, W) f32; h0: (B, W) f32 -> (h (B,S,W), h_last (B,W))."""
    bsz, s, w = a.shape
    block_t = min(block_t, s)
    block_w = min(block_w, w)
    pad_t = (-s) % block_t
    pad_w = (-w) % block_w
    if pad_t or pad_w:
        # pad with identity steps (a=1, b=0) so the carry passes through
        a = jnp.pad(a, ((0, 0), (0, pad_t), (0, pad_w)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad_t), (0, pad_w)))
    if pad_w:
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    nt = a.shape[1] // block_t
    nw = a.shape[2] // block_w

    kernel = functools.partial(_kernel, block_t=block_t, num_t=nt)
    h, hlast = pl.pallas_call(
        kernel,
        grid=(bsz, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w),
                         lambda b_, iw, it: (b_, it, iw)),
            pl.BlockSpec((1, block_t, block_w),
                         lambda b_, iw, it: (b_, it, iw)),
            pl.BlockSpec((1, block_w), lambda b_, iw, it: (b_, iw)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w),
                         lambda b_, iw, it: (b_, it, iw)),
            pl.BlockSpec((1, block_w), lambda b_, iw, it: (b_, iw)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, a.shape[1], a.shape[2]), jnp.float32),
            jax.ShapeDtypeStruct((bsz, a.shape[2]), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return h[:, :s, :w], hlast[:, :w]
