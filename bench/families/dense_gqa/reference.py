"""Plain float32 reference of a dense GQA decoder (Llama / Qwen3 layers).

It imports nothing of the program and reads the published weights that
``weights.py`` beside it made: RMSNorm, rotary embedding (half split),
grouped query attention with an optional per-head RMSNorm of q and k
(Qwen3), a SwiGLU MLP and a tied output head. Every matmul runs at
``default_matmul_precision("highest")`` on weights upcast from bf16 one
layer at a time (a ``lax.scan`` over the stacked layers), so that a
4-billion-parameter model fits beside its own weights.

``gaps`` runs one whole sequence (a prompt and the tokens served after it)
and returns, for each served position, how far below the reference's best
logit the served token lies. With ``control=True`` it also runs the same
forward with every matmul operand rounded to float8 (e4m3, scaled per row
or per output column; ``bench.reference.q8``) and returns, at the same
positions, the gap of the token that the float8 forward puts first: the
control that the comparison must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import F32, gaps_of, head_gaps, q8 as _q8

Q_BLOCK = 512        # query rows per attention block


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freq                  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(arch, quant, x, lw, pos):
    eps, theta = arch["rms_norm_eps"], arch["rope_theta"]
    q8 = _q8 if quant else (lambda a, axis: a)
    lw = {k: v.astype(F32) for k, v in lw.items()}
    S = x.shape[0]
    H, KV = lw["wq"].shape[1], lw["wk"].shape[1]
    hd = lw["wq"].shape[2]
    g = H // KV

    h = q8(_rms(x, lw["attn_norm"], eps), -1)
    q = jnp.einsum("sd,dhk->shk", h, q8(lw["wq"], 0))
    k = jnp.einsum("sd,dhk->shk", h, q8(lw["wk"], 0))
    v = jnp.einsum("sd,dhk->shk", h, q8(lw["wv"], 0))
    if "q_norm" in lw:
        q = _rms(q, lw["q_norm"], eps)
        k = _rms(k, lw["k_norm"], eps)
    q = _rope(q, pos, theta).reshape(S, KV, g, hd)
    k = _rope(k, pos, theta)
    k8, v8 = q8(k, -1), q8(v, 0)

    def attend(qs):
        qb, pb = qs                                         # (Bq,KV,g,hd)
        s = jnp.einsum("qkgd,ckd->kgqc", q8(qb, -1), k8) * hd ** -0.5
        s = jnp.where(pos[None, None, None, :] <= pb[None, None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("kgqc,ckd->qkgd", q8(p, -1), v8)

    nb = S // Q_BLOCK if S >= Q_BLOCK else 1
    qb = q.reshape(nb, S // nb, KV, g, hd)
    o = jax.lax.map(attend, (qb, pos.reshape(nb, S // nb)))
    o = o.reshape(S, H, hd)
    x = x + jnp.einsum("shk,hkd->sd", q8(o.reshape(S, H * hd), -1)
                       .reshape(S, H, hd), q8(lw["wo"], (0, 1)))
    h = q8(_rms(x, lw["mlp_norm"], eps), -1)
    gate = jnp.einsum("sd,df->sf", h, q8(lw["w_gate"], 0))
    up = jnp.einsum("sd,df->sf", h, q8(lw["w_up"], 0))
    m = q8(jax.nn.silu(gate) * up, -1)
    return x + jnp.einsum("sf,fd->sd", m, q8(lw["w_down"], 0))


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "mlp_norm", "w_gate", "w_up", "w_down")


def hidden(arch, quant, w, tokens):
    """Final-norm hidden states of ``tokens`` (S,), in float32; with
    ``quant`` every matmul operand is rounded to float8."""
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=jnp.int32)
    x = w["embed"][tokens].astype(F32)
    stacked = {k: w[k] for k in LAYER_KEYS if k in w}

    def body(x, lw):
        return _layer(arch, quant, x, lw, pos), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _rms(x, w["final_norm"].astype(F32), arch["rms_norm_eps"])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(arch_items, control, w, tokens):
    arch = dict(arch_items)
    table = w["embed"][:arch["vocab_size"]]
    h = hidden(arch, False, w, tokens)
    hc = hidden(arch, True, w, tokens) if control else h
    return head_gaps(h, hc, table, tokens, control)


def arch_key(arch: dict) -> tuple:
    keep = ("vocab_size", "rms_norm_eps", "rope_theta")
    return tuple((k, arch[k]) for k in keep)


def gaps(w: dict, arch: dict, tokens: np.ndarray, first: int,
         control: bool = False):
    """Per-token logit gaps for ``tokens[first:]`` given everything before.

    Returns ``(served, ctl)``: ``served[j]`` is how far the reference's
    logit of ``tokens[first + j]`` lies below its best logit at that
    position; ``ctl[j]`` is the same, at the same position, for the token
    the float8 control puts first there (None unless ``control``)."""
    return gaps_of(_gaps, arch_key(arch), w, tokens, first, control)
