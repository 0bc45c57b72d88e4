"""Seeded weights of a dense GQA decoder, made by the benchmark itself.

``make`` builds every weight in one jitted call on the device, in the
served dtype, from ``--seed``. The names and meanings are the published
architecture's (Llama / Qwen3 decoder layers), stacked over layers:

    embed       (Vp, d)        token embedding, tied output head; rows
                               >= vocab_size (the program pads the vocab to
                               a multiple of 256) are zero
    attn_norm   (L, d)         RMSNorm weight before attention
    wq          (L, d, H, hd)  wk, wv (L, d, KV, hd)   wo (L, H, hd, d)
    q_norm      (L, hd)        k_norm (L, hd)   qk-norm weights (qwen3)
    mlp_norm    (L, d)
    w_gate      (L, d, F)      w_up (L, d, F)   w_down (L, F, d)
    final_norm  (d,)

Matrices are normal with the published ``initializer_range``, but for the
query and key projections, whose deviation is ``QK_SCALE`` times larger;
norm weights are 1 + 0.1 N(0, 1) so that the comparison exercises them.
At ``initializer_range`` a model without qk-norm scores its keys almost
alike (smollm-135m: scores of deviation 0.23), attention averages the
context, the states of all positions converge, and greedy decoding repeats
one token with a wide margin, which no rounding can move: a check on such
tokens could not tell float8 from bfloat16. At three times the deviation
(scores of deviation about 2) the decoded tokens vary, while bfloat16
still follows float32 closely; from four times on, the model amplifies
rounding so far that bfloat16 departs from float32 as much as float8
does. A model with qk-norm (qwen3-4b) normalises q and k, so the scale
leaves its scores as they were.

``program_params`` hands the same arrays to the program as its parameter
tree, without a copy of any matrix. Two conventions of the program are
mapped here and nowhere else: its RMSNorm multiplies by ``1 + scale``
(so ``scale = w - 1``), and it multiplies tied-head logits by
``d_model ** -0.5`` (so the final norm's scale carries ``sqrt(d_model)``).
The reference (``reference.py`` beside this file) reads the published
weights directly.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.weights import key_for

NORM_NOISE = 0.1
QK_SCALE = 3.0


def shapes(arch: dict, padded_vocab: int) -> dict:
    L, d = arch["num_hidden_layers"], arch["hidden_size"]
    H, KV = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch.get("head_dim") or d // H
    F = arch["intermediate_size"]
    out = {
        "embed": (padded_vocab, d),
        "attn_norm": (L, d),
        "wq": (L, d, H, hd), "wk": (L, d, KV, hd), "wv": (L, d, KV, hd),
        "wo": (L, H, hd, d),
        "mlp_norm": (L, d),
        "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d),
        "final_norm": (d,),
    }
    if arch.get("qk_norm"):
        out["q_norm"] = (L, hd)
        out["k_norm"] = (L, hd)
    return out


def make(arch: dict, padded_vocab: int, seed: int, dtype=jnp.bfloat16):
    """All weights, in one jitted call on the default device."""
    spec = shapes(arch, padded_vocab)
    std = float(arch["initializer_range"])
    vocab = arch["vocab_size"]
    names = sorted(spec)

    def build(key):
        keys = jax.random.split(key, len(names))
        out = {}
        for name, k in zip(names, keys):
            shape = spec[name]
            z = jax.random.normal(k, shape, jnp.float32)
            if name.endswith("norm"):
                out[name] = (1.0 + NORM_NOISE * z).astype(dtype)
            elif name == "embed":
                rows = jnp.arange(shape[0])[:, None] < vocab
                out[name] = jnp.where(rows, std * z, 0.0).astype(dtype)
            elif name in ("wq", "wk"):
                out[name] = (QK_SCALE * std * z).astype(dtype)
            else:
                out[name] = (std * z).astype(dtype)
        return out

    return jax.jit(build)(key_for(seed))


def program_params(w: dict, arch: dict) -> dict:
    """The program's parameter tree over the same arrays (see module doc)."""
    d = arch["hidden_size"]
    f32 = jnp.float32
    mixer = {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]}
    if arch.get("qk_norm"):
        mixer["q_scale"] = w["q_norm"].astype(f32) - 1.0
        mixer["k_scale"] = w["k_norm"].astype(f32) - 1.0
    layer = {
        "norm1": {"scale": w["attn_norm"].astype(f32) - 1.0},
        "mixer": mixer,
        "norm2": {"scale": w["mlp_norm"].astype(f32) - 1.0},
        "mlp": {"w_gate": w["w_gate"], "w_up": w["w_up"],
                "w_down": w["w_down"]},
    }
    return {
        "embed": {"table": w["embed"]},
        "stages": [{"b0": layer}],
        "final_norm": {"scale": w["final_norm"].astype(f32) * math.sqrt(d)
                       - 1.0},
    }
