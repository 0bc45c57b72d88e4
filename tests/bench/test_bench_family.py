"""A configuration brings its architecture to the harness as new files: a
benchmark root of its own, with a ``BENCHMARK.json``, one configuration and
a family that exists only there (a dense decoder with an untied output
head, which ``dense_gqa`` refuses), runs end to end on the CPU through the
harness as it is: the program's config, the weights, the engine, the
reference comparison with its float8 control, and ``mfu``."""
import copy
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

UNTIED = '''"""A dense GQA decoder with an untied output head."""
import functools

import jax
import jax.numpy as jnp

from bench.families.dense_gqa import flops as dense_flops
from bench.families.dense_gqa import program_config as tied_config
from bench.families.dense_gqa import reference as dense_ref
from bench.families.dense_gqa import weights as dense_weights
from bench.reference import gaps_of, head_gaps
from bench.weights import key_for

token_flops = dense_flops.token_flops
prompt_flops = dense_flops.prompt_flops
paged_attn_step = dense_flops.paged_attn_step


def program_config(arch, dtype=None):
    tied = dict(arch, tie_word_embeddings=True)
    return tied_config(tied, dtype).with_overrides(tie_embeddings=False)


def make(arch, padded_vocab, seed, dtype=jnp.bfloat16):
    w = dict(dense_weights.make(arch, padded_vocab, seed, dtype))
    rows = jnp.arange(padded_vocab)[:, None] < arch["vocab_size"]
    z = jax.random.normal(key_for(seed, 1),
                          (padded_vocab, arch["hidden_size"]))
    w["head"] = jnp.where(rows, arch["initializer_range"] * z,
                          0.0).astype(dtype)
    return w


def program_params(w, arch):
    p = dense_weights.program_params(w, arch)
    # an untied head: the program neither scales the logits nor the norm
    p["final_norm"] = {"scale": w["final_norm"].astype(jnp.float32) - 1.0}
    p["unembed"] = {"table": w["head"]}
    return p


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(arch_items, control, w, tokens):
    arch = dict(arch_items)
    h = dense_ref.hidden(arch, False, w, tokens)
    hc = dense_ref.hidden(arch, True, w, tokens) if control else h
    return head_gaps(h, hc, w["head"][:arch["vocab_size"]], tokens, control)


def gaps(w, arch, tokens, first, control=False):
    return gaps_of(_gaps, dense_ref.arch_key(arch), w, tokens, first,
                   control)
'''

CONFIG = "tiny-untied"
CELL_NAME = CONFIG + ".decode"
CELL = {
    "engine": {"batch_slots": 4, "max_seq_len": 128, "cache_backend": "paged",
               "num_pool_blocks": 33, "chunk_tokens": 32},
    "check_requests": 6,
    "limits": {"max_logit_gap": 1e-3, "min_tokens_compared": 8},
}
MIX = {"loop": "closed", "clients_per_slot": 1.5, "block": 8,
       "prompt": {"dist": "uniform", "min": 8, "max": 60},
       "output": {"dist": "uniform", "min": 16, "max": 40}}
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def write_root(root, family="dense_untied"):
    """A benchmark root with one configuration, one cell and ``UNTIED`` as
    the family ``dense_untied``."""
    bench = copy.deepcopy(harness.benchmark())
    bench["configs"] = [{"name": CONFIG, "source": "test",
                         "file": f"bench/configs/{CONFIG}.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": CELL_NAME, "config": CONFIG,
                           "traffic": "tiny", "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL_NAME]
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    arch = dict(harness.arch_of("qwen3-4b"), name=CONFIG, family=family,
                hidden_size=256, num_attention_heads=4,
                num_key_value_heads=1, head_dim=64, intermediate_size=512,
                vocab_size=2000, num_hidden_layers=2,
                tie_word_embeddings=False, served_dtype="float32",
                initializer_range=0.2)
    (root / "bench" / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(arch))
    fam = root / "bench" / "families" / "dense_untied"
    fam.mkdir(parents=True)
    (fam / "__init__.py").write_text(UNTIED)


def test_a_family_of_its_own_root_runs_end_to_end(tmp_path):
    write_root(tmp_path)
    root = str(tmp_path)
    arch = harness.arch_of(CONFIG, root)
    family = harness.family_of(arch, root)
    assert family is harness.family_of(arch, root)
    assert not family.program_config(arch).tie_embeddings
    dense = harness.family_of(harness.arch_of("qwen3-4b"))
    with pytest.raises(ValueError, match="tied head"):
        dense.program_config(arch)

    res = harness.run_cell(
        CELL_NAME, 2**35 + 3, 1.5, True, process_start=0.0,
        log=lambda m: None, out_dir=str(tmp_path / "out"), cell=CELL,
        mix=MIX, device=DEVICE, control=True, root=root)
    assert res["correct"], res["checks"]
    assert res["checks"]["served_tokens_compared"]["value"] >= 8
    # the family's float8 control fails the same comparison
    assert not res["control"]["correct"], res["control"]["checks"]
    assert res["metrics"]["mfu"]["value"] > 0


def test_an_unknown_family_names_the_file_and_the_family(tmp_path):
    write_root(tmp_path, family="no_such_family")
    root = str(tmp_path)
    arch = harness.arch_of(CONFIG, root)
    with pytest.raises(LookupError, match=re.escape(
            f"'bench/configs/{CONFIG}.json' names family 'no_such_family'")):
        harness.family_of(arch, root)
    with pytest.raises(LookupError, match="no_such_family"):
        harness.run_cell(CELL_NAME, 1, 1.0, False, process_start=0.0,
                         log=lambda m: None, out_dir=str(tmp_path),
                         cell=CELL, mix=MIX, device=DEVICE, root=root)


SIZES = re.compile(r"hidden_size|intermediate_size|num_hidden_layers|"
                   r"num_attention_heads|num_key_value_heads|head_dim|"
                   r"tie_word_embeddings|qk_norm|wq\b|w_gate")


@pytest.mark.parametrize("path", ["bench/harness.py", "bench/control.py"] + [
    "bench/metrics/" + f
    for f in sorted(os.listdir(os.path.join(ROOT, "bench", "metrics")))
    if f.endswith(".py")])
def test_the_harness_and_readers_name_no_architecture(path):
    with open(os.path.join(ROOT, path)) as f:
        found = SIZES.findall(f.read())
    assert not found, f"{path} names {sorted(set(found))}"
