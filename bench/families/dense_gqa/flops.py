"""Operations and bytes of a dense GQA decoder, from its published sizes.

These count what the algorithm needs, not what an implementation pads or
recomputes, so a later kernel or layout changes the time and never the
count.
"""
from __future__ import annotations

KV_BYTES = 2          # bf16 K and V
POS_BYTES = 4         # int32 position per cached token


def dims(arch: dict):
    d, H = arch["hidden_size"], arch["num_attention_heads"]
    hd = arch.get("head_dim") or d // H
    return (arch["num_hidden_layers"], d, H, arch["num_key_value_heads"], hd,
            arch["intermediate_size"], arch["vocab_size"])


def matmul_params(arch: dict) -> int:
    """Weights that multiply every token: the layers' projections and MLP,
    and the tied output head."""
    L, d, H, KV, hd, F, V = dims(arch)
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * F
    return L * per_layer + V * d


def attn_flops(arch: dict, ctx: int) -> int:
    """q.k and p.v of one token over ``ctx`` cached positions, all layers."""
    L, _, H, _, hd, _, _ = dims(arch)
    return 4 * L * H * hd * ctx


def token_flops(arch: dict, ctx: int) -> int:
    """Model operations of one token that attends over ``ctx`` positions."""
    return 2 * matmul_params(arch) + attn_flops(arch, ctx)


def prompt_flops(arch: dict, n: int) -> int:
    """Model operations of a whole ``n``-token prompt (token i attends over
    i + 1 positions)."""
    L, _, H, _, hd, _, _ = dims(arch)
    return 2 * matmul_params(arch) * n + 4 * L * H * hd * n * (n + 1) // 2


def paged_attn_step(arch: dict, ctxs) -> tuple:
    """(operations, bytes) of one decode step's paged-attention calls, one
    a layer, each for one decoded token per slot over its live context in
    ``ctxs``: q.k and p.v, and the K, V and positions of every live token
    read once, summed over the layers (every layer calls the kernel)."""
    L, _, H, KV, hd, _, _ = dims(arch)
    live = sum(ctxs)
    flops = 4 * H * hd * live
    nbytes = live * (2 * KV * hd * KV_BYTES + POS_BYTES) \
        + len(ctxs) * 2 * H * hd * KV_BYTES
    return L * flops, L * nbytes
