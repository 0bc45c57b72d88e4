"""The serving loop's host spans (``repro.serving.tracing``): a bounded ring,
spans nested inside ``engine.step``, the KV gauges against the allocator,
the totals ``metrics()`` reports, the same spans in a profiler trace, and
the program names the benchmark's trace reduction keys on."""
import gc
import glob
import os
import re
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig, dense_stages
from repro.models.model import LM
from repro.serving import ServingEngine, tracing

BLOCK = 8
PROMPTS = (5, 17, 30, 9)
MAX_NEW = 12


@pytest.fixture(scope="module")
def lm_params():
    cfg = ModelConfig(
        name="tiny", family="dense", source="t", num_layers=2, d_model=48,
        num_heads=4, num_kv_heads=2, head_dim=12, d_ff=96, vocab_size=128,
        stages=dense_stages(2), param_dtype="float32")
    lm = LM(cfg, kv_chunk=8)
    return lm, lm.init(jax.random.PRNGKey(0))[0]


@pytest.fixture(scope="module")
def engine(lm_params):
    lm, params = lm_params
    eng = ServingEngine(lm, params, batch_slots=4, max_seq_len=64,
                        cache_backend="paged", block_size=BLOCK,
                        num_pool_blocks=40, chunk_tokens=32)
    eng.warm_compile()
    return eng


def submit(eng, seed=0, max_new=MAX_NEW):
    rng = np.random.default_rng(seed)
    return [eng.submit(rng.integers(0, 100, n), max_new_tokens=max_new)
            for n in PROMPTS]


def drain(eng, **kw):
    """Serve one wave to its end; the engine steps it took."""
    submit(eng, **kw)
    t0 = time.perf_counter()
    eng.run()
    return tracing.steps(t0, time.perf_counter())


def test_ring_is_bounded_and_counts_what_it_drops():
    rec = tracing.Recorder(size=8)
    for i in range(20):
        rec.record("a" if i % 2 else "b", 1000 * i, 1000 * i + 10, i)
    assert [r.step for r in rec.records()] == list(range(12, 20))
    assert rec.dropped() == 12
    assert rec.totals() == {"a": {"count": 10, "s": pytest.approx(1e-7)},
                            "b": {"count": 10, "s": pytest.approx(1e-7)}}
    # a window that began before the oldest record kept cannot be read
    assert rec.steps(0.0, 1.0) is None
    assert rec.steps(19e-6, 1.0) == []


def test_ring_survives_a_write_from_inside_a_write():
    # a gc callback can fire while a record is being written
    rec = tracing.Recorder(size=4)
    inner = []

    class Name(str):
        def __hash__(self):
            if not inner:
                inner.append(1)
                rec.record("inner", 0, 1, 0)
            return str.__hash__(self)
    rec.record(Name("outer"), 0, 5, 1)
    assert sorted(r.name for r in rec.records()) == ["inner", "outer"]
    assert rec.totals()["outer"]["count"] == 1


def test_ring_loses_no_record_under_contention():
    rec = tracing.Recorder(size=1 << 12)
    threads, per = 16, 500
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def write(i):
            for j in range(per):
                rec.record(f"t{i % 4}", j, j + 1, i)
        workers = [threading.Thread(target=write, args=(i,))
                   for i in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(before)
    tot = rec.totals()
    assert sum(v["count"] for v in tot.values()) == threads * per
    assert rec.dropped() == threads * per - rec.size
    assert all(r is not None for r in rec.records())
    assert len(rec.records()) == rec.size


def test_spans_nest_inside_engine_step(engine):
    steps = drain(engine)
    assert steps
    names = {"engine.plan", "engine.admit", "engine.chunk", "engine.reserve",
             "engine.dispatch", "engine.sync", "engine.pull",
             "engine.finish"}
    inside = sum(len(s.spans) for s in steps)
    start, end = steps[0].t0, steps[-1].t1
    loose = [r for r in tracing.records() if r.name in names
             and start <= r.t0_ns * 1e-9 <= end]
    assert len(loose) <= inside        # every engine span lies in a step
    plain = 0
    for s in steps:
        own = [n for n, _, _ in s.spans]
        assert own.count("engine.sync") == (1 if "engine.dispatch" in own
                                            else 0)
        assert own.count("engine.plan") == 1
        for n, a, b in s.spans:
            assert s.t0 <= a <= b <= s.t1
        if "engine.dispatch" in own and not {"engine.admit",
                                             "engine.chunk"} & set(own):
            plain += 1
            assert len(s.spans) + 1 <= 12
    assert plain > 0
    admits = [a for s in steps for n, a, _ in s.spans if n == "engine.admit"]
    assert len(admits) == len(PROMPTS)
    # admission runs inside the scheduler's plan
    for s in steps:
        plans = [(a, b) for n, a, b in s.spans if n == "engine.plan"]
        for n, a, b in s.spans:
            if n == "engine.admit":
                assert any(p0 <= a and b <= p1 for p0, p1 in plans)


def test_kv_gauges_match_the_allocator(engine):
    rng = np.random.default_rng(3)
    max_new = 10
    for n in PROMPTS:
        engine.submit(rng.integers(0, 100, n), max_new_tokens=max_new)
    backend = engine.backend
    seen = 0
    while engine.pending:
        t0 = time.perf_counter()
        engine.step()
        g = tracing.steps(t0, time.perf_counter())[-1].gauges
        assert g["kv_blocks_committed"] == (backend.blocks_in_use
                                            + backend._gap_total)
        steps = np.asarray(engine._state["steps"])
        prompts = {s: len(r.prompt) for s, r in engine._slots.items()}
        assert g["slots_decoding"] == len(prompts)
        if engine._prefilling:
            continue
        # no shared prefixes: each request holds its whole worst case
        assert g["kv_blocks_committed"] == sum(
            -(-(p + max_new) // BLOCK) for p in prompts.values())
        assert g["kv_blocks_live"] == sum(
            -(-(p + int(steps[s])) // BLOCK) for s, p in prompts.items())
        seen += bool(prompts)
    assert seen > 0
    assert tracing.steps(t0, time.perf_counter())[-1].gauges == {
        "kv_blocks_committed": 0, "kv_blocks_live": 0, "slots_decoding": 0,
        "attn_pages_visited": 0}


def test_attn_pages_visited_counts_each_decoding_slots_live_pages(engine):
    rng = np.random.default_rng(4)
    for n in PROMPTS:
        engine.submit(rng.integers(0, 100, n), max_new_tokens=MAX_NEW)
    grid = engine.batch_slots * engine.backend.blocks_per_slot
    seen = 0
    while engine.pending:
        t0 = time.perf_counter()
        engine.step()
        g = tracing.steps(t0, time.perf_counter())[-1].gauges
        steps = np.asarray(engine._state["steps"])
        # the position of each decoding slot's latest token
        pos = [len(r.prompt) + int(steps[s]) - 1
               for s, r in engine._slots.items()]
        assert g["attn_pages_visited"] == sum(-(-(p + 1) // BLOCK)
                                              for p in pos)
        assert g["attn_pages_visited"] <= grid
        seen += bool(pos)
    assert seen > 0


def test_attn_pages_visited_reads_zero_on_the_ring(lm_params):
    lm, params = lm_params
    eng = ServingEngine(lm, params, batch_slots=4, max_seq_len=64,
                        cache_backend="ring")
    steps = drain(eng)
    assert steps and all(s.gauges["attn_pages_visited"] == 0 for s in steps)


def test_metrics_report_the_ring_totals(engine):
    drain(engine, seed=1)
    gc.disable()                      # a collection would add a host.gc
    try:
        phases = engine.metrics()["host_phases"]
        assert phases == tracing.totals()
    finally:
        gc.enable()
    for name in ("engine.step", "engine.plan", "engine.sync"):
        assert phases[name]["count"] > 0 and phases[name]["s"] > 0


def test_spans_land_in_the_profiler_trace(engine, tmp_path):
    submit(engine, seed=2, max_new=4)
    jax.profiler.start_trace(str(tmp_path))
    t0 = time.perf_counter()
    try:
        engine.run()
    finally:
        jax.profiler.stop_trace()
    t1 = time.perf_counter()
    mine = sorted((r.t0_ns, r.name, r.t1_ns - r.t0_ns)
                  for r in tracing.records()
                  if r.name.startswith("engine.")
                  and t0 <= r.t0_ns * 1e-9 <= t1)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    theirs = sorted((e.start_ns, e.name, e.end_ns - e.start_ns)
                    for p in pd.planes if p.name.startswith("/host")
                    for line in p.lines for e in line.events
                    if e.name.startswith("engine."))
    assert mine and [n for _, n, _ in theirs] == [n for _, n, _ in mine]
    for (_, name, d_mine), (_, _, d_theirs) in zip(mine, theirs):
        assert abs(d_mine - d_theirs) <= 50_000, name


@pytest.mark.parametrize("program,module", [
    ("step", "jit__step_impl"), ("scan", "jit__scan_impl")])
def test_decode_programs_keep_their_names(engine, program, module):
    # the benchmark's trace reduction finds the decode programs and the
    # paged kernel by these names
    args = (engine.params, engine._cache_state, engine._state,
            engine._base_key)
    if program == "step":
        lowered = engine._step_fn.lower(*args)
    else:
        lowered = engine._scan_fn.lower(*args, 2)
    text = lowered.as_text(dialect="hlo")
    assert text.startswith(f"HloModule {module},")
    assert re.search(r"\bpaged_decode_attn\.\d+\b", text)
