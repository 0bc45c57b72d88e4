"""A whole benchmark run at a tiny size on the CPU, past the harness's look
for a chip: the served streams pass the comparison, and fail it when the
timed path is broken underneath (a token altered where it is produced; a
cache append that leaves the state unchanged)."""
import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.harness import arch_of, family_of  # noqa: E402

TINY = "tiny.decode"


def tiny_arch():
    cfg = family_of(arch_of("qwen3-4b")).program_config(
        arch_of("qwen3-4b")).reduced()
    arch = dict(arch_of("qwen3-4b"))
    arch.update(hidden_size=cfg.d_model, num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=64,
                intermediate_size=cfg.d_ff, vocab_size=2000,
                num_hidden_layers=2, served_dtype="float32",
                # weights large enough that attention moves the argmax, so
                # a broken cache shows in a short run's sample
                initializer_range=0.2)
    return arch


def tiny_bench(loop="closed"):
    bench = copy.deepcopy(harness.benchmark())
    bench["workloads"].append({"name": TINY, "config": "qwen3-4b",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(TINY)
    return bench


CELL = {
    "engine": {"batch_slots": 4, "max_seq_len": 128, "cache_backend": "paged",
               "num_pool_blocks": 33, "chunk_tokens": 32},
    "check_requests": 6,
    "limits": {"max_logit_gap": 1e-3, "min_tokens_compared": 8},
}
MIXES = {
    "closed": {"loop": "closed", "clients_per_slot": 1.5, "block": 8,
               "prompt": {"dist": "uniform", "min": 8, "max": 60},
               "output": {"dist": "uniform", "min": 16, "max": 40}},
    "open": {"loop": "open", "arrivals": "poisson", "rate_per_s": 20.0,
             "block": 8,
             "prompt": {"dist": "uniform", "min": 8, "max": 60},
             "output": {"dist": "uniform", "min": 4, "max": 12}},
}


def run(loop="closed", seconds=1.5, seed=2**35 + 11, tmp=None):
    logs = []
    res = harness.run_cell(
        TINY, seed, seconds, False, process_start=0.0, log=logs.append,
        out_dir=str(tmp), bench=tiny_bench(), arch=tiny_arch(), cell=CELL,
        mix=MIXES[loop],
        device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    return res, logs


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_tiny_run_is_correct(loop, tmp_path):
    res, logs = run(loop, tmp=tmp_path)
    assert list(res)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(res)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["checks"]["bad_streams"]["value"] == 0
    assert "setup_s" in res["metrics"]
    assert res["metrics"]["output_tok_s"]["value"] > 0
    assert any("compiles inside the window" in m for m in logs)
    assert res["facts"]["window_compiles"] == 0


@pytest.fixture
def fresh_traces():
    """A fault is planted by patching a function that jitted programs
    trace; traces cached by earlier tests would bypass it, and traces of
    the fault must not reach later tests."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_altered_token_is_caught(monkeypatch, tmp_path, fresh_traces):
    from repro.serving import engine as engine_mod
    real = engine_mod.sample_logits_keyed

    def altered(keys, logits, temp):
        tok = real(keys, logits, temp)
        return (tok + 1) % 2000          # the sampled token, changed
    monkeypatch.setattr(engine_mod, "sample_logits_keyed", altered)
    res, _ = run(tmp=tmp_path)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_unchanged_cache_is_caught(monkeypatch, tmp_path, fresh_traces):
    from repro.serving.kv_cache import PagedLayout

    calls = []

    def dropped(self, cache, new, start, block_tables, valid=None):
        calls.append(1)
        return cache                     # the append leaves state unchanged
    monkeypatch.setattr(PagedLayout, "append", dropped)
    res, _ = run(tmp=tmp_path)
    assert calls, "the fault was never traced"
    assert not res["correct"], (res["checks"], res["facts"])


def test_trace_covers_only_the_window(monkeypatch, tmp_path):
    # the window opens after the opening prefill; the traced steps are
    # those from start_share of the window on, never the prefill before it
    seen = {}
    real = harness.metric_reader

    def spy(name, root=harness.BENCH):
        read = real(name, root)

        def keep(run):
            seen["run"] = run
            return read(run)
        return keep
    monkeypatch.setattr(harness, "metric_reader", spy)
    cell = dict(CELL, trace={"start_share": 0.4, "seconds": 0.3})
    res = harness.run_cell(
        TINY, 3, 1.5, True, process_start=0.0, log=lambda m: None,
        out_dir=str(tmp_path), bench=tiny_bench(), arch=tiny_arch(),
        cell=cell, mix=MIXES["closed"],
        device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    assert res["correct"], res["checks"]
    run = seen["run"]
    traced = [s for s in run.steps if s.traced]
    assert traced and any(not s.traced for s in run.steps
                          if s.t0 < run.start)
    assert all(s.t0 >= run.start + 0.4 * 1.5 for s in traced)
    assert sum(len(s.decode_ctx) for s in traced) > 0


def test_refuses_cpu_and_unknown_device():
    sys.path.insert(0, os.path.join(ROOT, "bench"))
    import run as entry
    with pytest.raises(SystemExit, match="not a TPU"):
        entry.check_device(1)
    with pytest.raises(LookupError, match="no peaks"):
        harness.peaks_of("TPU v99")
    assert harness.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_metrics_are_found_by_name(tmp_path):
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new_metric.ms.py").write_text(
        "def read(run):\n    return run.seconds * 2\n")
    read = harness.metric_reader("new_metric.ms", root=str(tmp_path))

    class R:
        seconds = 3.0
    assert read(R()) == 6.0
    bench = tiny_bench()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    assert all(os.path.exists(os.path.join(ROOT, c["file"]))
               for c in bench["configs"])
    for w in harness.benchmark()["workloads"]:
        assert harness.cell_of(w["name"])["engine"]["cache_backend"] == \
            "paged"


def test_gap_of_a_served_stream_uses_the_tokens_given(tmp_path):
    arch = tiny_arch()
    family = family_of(arch)
    cfg = family.program_config(arch)
    ws = family.make(arch, cfg.padded_vocab, 1, dtype=jnp.float32)
    rec = harness.Rec(0, 0.0, 5, 3, np.arange(5, dtype=np.int32))
    rec.tokens, rec.status, rec.output = [1, 2], "done", np.array([1, 2])
    checks = harness.compare(family, ws, arch, [rec], CELL)
    assert checks["bad_streams"]["value"] == 1   # 2 tokens of 3 served


def test_float8_control_fails_the_limit(tmp_path):
    logs = []
    res = harness.run_cell(
        TINY, 5, 1.5, False, process_start=0.0, log=logs.append,
        out_dir=str(tmp_path), bench=tiny_bench(), arch=tiny_arch(),
        cell=CELL, mix=MIXES["closed"],
        device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1},
        control=True)
    c, ctl = res["checks"], res["control"]
    assert res["correct"]
    # the control, read at the served positions the check reads and put
    # through the same comparison, comes out not correct
    assert not ctl["correct"], ctl["checks"]
    gap = ctl["checks"]["max_logit_gap"]["value"]
    assert gap > c["max_logit_gap"]["limit"]
    assert gap > 3 * c["max_logit_gap"]["value"]
    assert ctl["checks"]["served_tokens_compared"]["value"] == \
        c["served_tokens_compared"]["value"]


def test_window_opens_on_streaming_slots(monkeypatch, tmp_path):
    # every slot streams an answer caught part-way when the
    # window opens, and only answers finished inside the window are
    # compared
    seen = {}
    real = harness.finished_in_window

    def spy(recs, start, end):
        seen.update(recs=recs, start=start, end=end)
        return real(recs, start, end)
    monkeypatch.setattr(harness, "finished_in_window", spy)
    res, _ = run("closed", tmp=tmp_path)
    assert res["correct"], res["checks"]
    recs, start, end = seen["recs"], seen["start"], seen["end"]
    slots = CELL["engine"]["batch_slots"]
    assert sum(r.first is not None and r.first < start for r in recs) \
        >= slots
    assert all(r.submit < start for r in recs[:slots])
    done = real(recs, start, end)
    assert res["facts"]["requests_done"] == len(done) > 0
    assert all(start <= r.deliveries[-1][0] < end for r in done)
    assert res["facts"]["compared_tokens_in_window"] > 0
