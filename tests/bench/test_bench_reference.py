"""The dense_gqa family's float32 reference against the program's
``LM.forward``, on the reduced variants of both benchmark configurations,
with weights the family made and mapped into the program's tree."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.harness import arch_of, family_of  # noqa: E402
from bench.weights import check_tree  # noqa: E402
from repro.models.model import LM  # noqa: E402


def small(name, layers=2):
    """A reduced copy of the benchmark configuration ``name``: the
    program's ``reduced()`` widths at ``layers`` layers, as a config dict."""
    cfg = family_of(arch_of(name)).program_config(arch_of(name)).reduced()
    arch = dict(arch_of(name))
    arch.update(hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=64,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size - 7,
                num_hidden_layers=layers)
    return arch


@pytest.mark.parametrize("name", ["qwen3-4b", "smollm-135m"])
def test_reference_matches_program_forward(name):
    arch = small(name)
    family = family_of(arch)
    cfg = family.program_config(arch, dtype="float32")
    lm = LM(cfg)
    w = family.make(arch, cfg.padded_vocab, seed=2**33 + 5,
                    dtype=jnp.float32)
    params = family.program_params(w, arch)
    check_tree(params, lm.abstract()[0])
    tokens = np.random.default_rng(0).integers(
        0, arch["vocab_size"], size=40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        logits = lm.forward(params, {"tokens": jnp.asarray(tokens)[None]})[0]
    logits = np.asarray(logits[0, :, :arch["vocab_size"]], np.float64)
    # every position's gap agrees with the program's own logits
    first = 10
    served, _ = family.gaps(w, arch, tokens, first)
    rows = logits[first - 1:-1]
    want = rows.max(-1) - rows[np.arange(len(rows)), tokens[first:]]
    assert served.shape == (len(tokens) - first,)
    np.testing.assert_allclose(served, want, atol=1e-4 * np.abs(rows).max())
    assert np.max(served) > 0


def test_float8_control_departs_from_reference():
    arch = small("qwen3-4b")
    family = family_of(arch)
    cfg = family.program_config(arch, dtype="float32")
    w = family.make(arch, cfg.padded_vocab, seed=3)
    tokens = np.random.default_rng(1).integers(
        0, arch["vocab_size"], size=300).astype(np.int32)
    served, ctl = family.gaps(w, arch, tokens, 1, control=True)
    assert ctl.shape == served.shape
    assert np.all(ctl >= 0) and np.max(ctl) > 0


def test_weights_depend_on_large_seeds():
    arch = small("smollm-135m")
    family = family_of(arch)
    a = family.make(arch, 512, seed=2**40 + 1)
    b = family.make(arch, 512, seed=2**40 + 1)
    c = family.make(arch, 512, seed=2**40 + 2)
    assert np.array_equal(np.asarray(a["wq"], np.float32),
                          np.asarray(b["wq"], np.float32))
    assert not np.array_equal(np.asarray(a["wq"], np.float32),
                              np.asarray(c["wq"], np.float32))
    assert np.all(np.asarray(a["embed"], np.float32)[arch["vocab_size"]:]
                  == 0)
