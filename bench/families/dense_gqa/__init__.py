"""The dense GQA decoder family: Llama / Qwen3 layers with a tied head.

A configuration file names it with ``"family": "dense_gqa"``. What the
harness takes from a family:

- ``program_config(arch, dtype=None)``: the program's ``ModelConfig``;
- ``make(arch, padded_vocab, seed, dtype)`` and ``program_params(w, arch)``
  (``weights.py``): seeded weights and the program's tree over them;
- ``gaps(w, arch, tokens, first, control=False)`` (``reference.py``): the
  float32 reference's logit gaps, and its float8 control's;
- ``token_flops(arch, ctx)``, ``prompt_flops(arch, n)`` and
  ``paged_attn_step(arch, ctxs)`` (``flops.py``): what ``mfu`` and
  ``paged_attn_roofline`` count.
"""
from __future__ import annotations

from typing import Optional

from .flops import paged_attn_step, prompt_flops, token_flops  # noqa: F401
from .reference import gaps  # noqa: F401
from .weights import make, program_params  # noqa: F401


def program_config(arch: dict, dtype: Optional[str] = None):
    """The program's ``ModelConfig`` for a configuration file: the preset
    the file names, with every published size taken from the file."""
    from repro.configs import SWIGLU, dense_stages, get_config
    if not arch.get("tie_word_embeddings"):
        raise ValueError("the dense_gqa family's weights assume a tied head")
    n = arch["num_hidden_layers"]
    return get_config(arch["program_config"]).with_overrides(
        num_layers=n, stages=dense_stages(n, mlp=SWIGLU),
        d_model=arch["hidden_size"], num_heads=arch["num_attention_heads"],
        num_kv_heads=arch["num_key_value_heads"],
        head_dim=arch.get("head_dim") or 0, d_ff=arch["intermediate_size"],
        vocab_size=arch["vocab_size"], rope_theta=float(arch["rope_theta"]),
        rms_eps=float(arch["rms_norm_eps"]),
        use_qk_norm=bool(arch.get("qk_norm")), tie_embeddings=True,
        param_dtype=dtype or arch["served_dtype"])
