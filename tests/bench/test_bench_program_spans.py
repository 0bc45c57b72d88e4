"""The readers of the program's own spans and gauges (``host_gap_ms.decode``,
``plan_ms.decode``, ``gateway_ms.decode``, ``kv_live_share``) in a tiny
traced run on the CPU, and on a program that has no span recorder."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from test_bench_harness import (CELL, MIXES, TINY, tiny_arch,  # noqa: E402
                                tiny_bench)

NEW = ("host_gap_ms.decode", "plan_ms.decode", "gateway_ms.decode",
       "kv_live_share")


def test_traced_run_reports_the_program_spans(monkeypatch, tmp_path):
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
        TINY, 2**33 + 5, 1.5, True, process_start=0.0, log=lambda m: None,
        out_dir=str(tmp_path), bench=tiny_bench(), arch=tiny_arch(),
        cell=cell, mix=MIXES["closed"],
        device={"platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(NEW) <= set(m), sorted(m)
    assert 0 < m["kv_live_share"] <= 100
    assert 0 < m["plan_ms.decode"] <= m["engine_step_ms.decode"]
    assert m["host_gap_ms.decode"] > 0 and m["gateway_ms.decode"] > 0
    # the traced steps are left out of the program's numbers
    from bench import program_spans
    run = seen["run"]
    kept = program_spans.window_steps(run)
    traced = [s for s in run.steps if s.traced]
    assert traced and kept
    assert not any(a <= s.t0 <= b for s in kept
                   for a, b in ((t.t0, t.t1) for t in traced))


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_reads_nothing(monkeypatch, name):
    # a program from before the span recorder has no repro.serving.tracing
    import repro.serving
    monkeypatch.delattr(repro.serving, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.serving.tracing", None)
    with pytest.raises(ImportError):
        from repro.serving import tracing  # noqa: F401

    class Run:
        start, end, steps = 0.0, float("inf"), []
    assert harness.metric_reader(name)(Run()) is None
