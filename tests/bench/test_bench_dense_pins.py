"""The dense_gqa family's readings, pinned (``dense_gqa_pins.json``): a
digest of the seeded weights for two seeds, one past 32 bits; the
reference's gaps and its float8 control's for one sequence; and the
operation counts that ``mfu`` and ``paged_attn_roofline`` read, at the
published sizes. They were recorded from the dense code as it stood before
it moved into its family. A change to any of them changes what every run
of a dense_gqa cell makes or measures."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench.harness import arch_of, family_of  # noqa: E402

with open(os.path.join(HERE, "dense_gqa_pins.json")) as f:
    PINS = json.load(f)
# the program's reduced() widths for both configurations, two layers, and
# weights wide enough that the float8 control departs from the reference
SMALL = dict(hidden_size=256, num_attention_heads=4, num_key_value_heads=1,
             head_dim=64, intermediate_size=512, vocab_size=505,
             num_hidden_layers=2, initializer_range=0.2)
PADDED = 512
CONFIGS = sorted(PINS)


def digest(w) -> str:
    h = hashlib.sha256()
    for name in sorted(w):
        a = np.asarray(w[name])
        h.update(f"{name}:{a.shape}:{a.dtype}:".encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_are_pinned(name):
    arch = dict(arch_of(name), **SMALL)
    family = family_of(arch)
    for seed, want in PINS[name]["digests"].items():
        assert digest(family.make(arch, PADDED, int(seed))) == want, seed


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_gaps_are_pinned(name):
    arch = dict(arch_of(name), **SMALL)
    family = family_of(arch)
    w = family.make(arch, PADDED, 7)
    tokens = np.random.default_rng(0).integers(
        0, arch["vocab_size"], size=48).astype(np.int32)
    served, ctl = family.gaps(w, arch, tokens, 16, control=True)
    want = PINS[name]["gaps"]
    # float32 rounding of logits of this size, and no more
    np.testing.assert_allclose(served, want["served"], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ctl, want["control"], rtol=1e-5, atol=1e-5)
    assert np.max(ctl) > 0


@pytest.mark.parametrize("name", CONFIGS)
def test_operation_counts_are_pinned(name):
    arch = arch_of(name)
    family = family_of(arch)
    pins = PINS[name]
    for ctx, want in pins["token_flops"].items():
        assert family.token_flops(arch, int(ctx)) == want, ctx
    for n, want in pins["prompt_flops"].items():
        assert family.prompt_flops(arch, int(n)) == want, n
    for call in pins["paged_attn_step"]:
        assert family.paged_attn_step(arch, call["ctxs"]) == \
            (call["flops"], call["bytes"]), call["ctxs"]
