"""The benchmark's trace reduction: on a hand-made trace with known
answers, and on a short trace recorded on a TPU v5e (trimmed, committed as
``bench/testdata/trace_smollm_decode.json.gz``)."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import trace  # noqa: E402

RECORDED = os.path.join(ROOT, "bench", "testdata",
                        "trace_smollm_decode.json.gz")


def made():
    # host: two engine steps, [0, 100) and [150, 300) ns
    # device: a step program [10, 90) holding a while loop [10, 80) with
    # the paged kernel [20, 50) and a fusion [50, 70) nested in it, and a
    # chunk program [160, 260) with one op [170, 250)
    return {
        "spans": [[0, 100], [150, 300]],
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["_step_impl", 10, 90], ["_chunk_impl", 160, 260]],
            "ops": [["while.1", 10, 80], ["paged_decode_attn.9", 20, 50],
                    ["fusion.3", 50, 70], ["copy.2", 80, 90],
                    ["fusion.7", 170, 250]],
        }],
    }


def test_reduction_of_a_known_trace():
    t = trace.reduce(made())
    assert t.window_s == pytest.approx(300e-9)
    assert t.busy_s == pytest.approx(160e-9)       # [10,90) + [170,250)
    assert t.program_busy("_step_impl") == pytest.approx(80e-9)
    assert t.program_busy("_chunk_impl") == pytest.approx(80e-9)
    assert t.decode_kernel_s() == pytest.approx(30e-9)
    assert t.ops["_step_impl/while.1"] == pytest.approx(20e-9)   # self
    gaps = dict()
    for label, s in t.gaps:
        gaps[label] = gaps.get(label, 0.0) + s
    # idle: [0,10) and [90,100) inside step 1, [100,150) between,
    # [150,170) and [250,300) inside step 2
    assert gaps[trace.INSIDE] == pytest.approx(90e-9)
    assert gaps[trace.BETWEEN] == pytest.approx(50e-9)
    b = t.breakdown()
    assert b["device_ops"][0] == ["_chunk_impl/fusion.7",
                                  pytest.approx(80e-9)]
    assert len(b["idle_gaps"]) <= 10 and len(b["device_ops"]) <= 10


def test_no_spans_no_summary():
    ex = made()
    ex["spans"] = []
    assert trace.reduce(ex) is None


def test_op_and_program_names():
    assert trace.op_name("%paged_decode_attn.9 = bf16[8,1,9,64] "
                         "custom-call(...)") == "paged_decode_attn.9"
    assert trace.program_name("jit__step_impl(4548604250773982941)") == \
        "_step_impl"
    assert trace.PAGED_KERNEL.match("paged_decode_attn.7")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="recorded trace not present")
def test_reduction_of_a_recorded_trace():
    t = trace.reduce(trace.load_extract(RECORDED))
    assert 0 < t.busy_s < t.window_s
    assert t.program_busy("_step_impl") > 0
    assert 0 < t.decode_kernel_s() < t.program_busy("_step_impl")
    assert sum(t.programs.values()) <= t.busy_s * 1.0001
    assert sum(v for v in t.ops.values()) == pytest.approx(t.busy_s,
                                                           rel=0.05)
    b = t.breakdown()
    assert any(name.endswith("paged_decode_attn.9") or "paged" in name
               for name, _ in b["device_ops"])
