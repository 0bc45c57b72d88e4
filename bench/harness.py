"""One run of one benchmark cell, end to end.

``BENCHMARK.json`` names each cell's configuration and traffic mix. The
harness finds everything else by those names:

- ``bench/configs/<config>.json``: the published configuration, as run,
  with the ``family`` that knows its architecture;
- ``bench/families/<family>/``: everything that depends on the layer
  equations (see ``family_of``): the program's config, the seeded weights
  and the program's tree over them, the float32 reference and its float8
  control, and the operation counts ``mfu`` and the kernel rooflines read;
- ``bench/traffic/<mix>.json``: the traffic mix (``bench/load.py``);
- ``bench/cells/<cell>.json``: what the deployment states to the engine
  (slots, ``max_seq_len``, KV backend and pool, prefill chunk) and the
  limits of the correctness check;
- ``bench/metrics/<metric>.py``: one reader per metric (``read(run)``).

A run: make the family's weights from the seed on the device, build
``ServingEngine`` behind ``ServingGateway`` (through ``StepProxy``, the
benchmark's span around each engine step), warm every program the cell
uses, drive the seeded traffic for the window, then compare a sample of
the served streams with the family's float32 reference. The families,
configurations and ``BENCHMARK.json`` are found under the benchmark root
a run is given; this file and the metric readers name no architecture's
sizes.
"""
from __future__ import annotations

import asyncio
import dataclasses
import gc
import importlib.util
import json
import math
import os
import sys
import time
import types
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import load

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
GROUP_S = 200e-6      # tokens yielded closer than this came in one delivery
FIRST_TOKEN_WAIT_S = 60.0


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------

def benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _entry(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}")


def arch_of(config: str, root: str = ROOT) -> dict:
    entry = _entry(benchmark(root)["configs"], config, "configuration")
    with open(os.path.join(root, entry["file"])) as f:
        return json.load(f)


def cell_of(workload: str) -> dict:
    with open(os.path.join(BENCH, "cells", f"{workload}.json")) as f:
        return json.load(f)


def peaks_of(kind: str) -> dict:
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise LookupError(f"no peaks for device kind {kind!r}; the table "
                          f"(bench/peaks.json) knows {sorted(table)}")
    return table[kind]


def metric_reader(name: str, root: str = BENCH) -> Callable:
    """The ``read(run)`` function of ``<root>/metrics/<name>.py``."""
    path = os.path.join(root, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, workload: str, trace: bool) -> List[dict]:
    """The metrics a run of ``workload`` reports: its end-to-end metrics,
    or with ``trace`` its per-layer ones."""
    items = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in items
            if "workloads" not in m or workload in m["workloads"]]


FAMILY_API = ("program_config", "make", "program_params", "gaps",
              "token_flops", "prompt_flops")


def family_of(arch: dict, root: str = ROOT) -> types.ModuleType:
    """The family package ``<root>/bench/families/<family>/`` that the
    configuration ``arch`` names, loaded once per directory. It gives
    ``FAMILY_API`` and, where its layers call the paged GQA decode kernel,
    ``paged_attn_step(arch, ctxs) -> (flops, bytes)``."""
    name = arch.get("family")
    path = os.path.join(os.path.abspath(root), "bench", "families",
                        str(name))
    init = os.path.join(path, "__init__.py")
    if not name or not os.path.isfile(init):
        files = [c["file"] for c in benchmark(root)["configs"]
                 if c["name"] == arch.get("name")]
        raise LookupError(
            f"{files[0] if files else arch.get('name')!r} names family "
            f"{name!r}, but there is no {init}")
    modname = "bench_family_" + "".join(
        c if c.isalnum() else "_" for c in path)
    mod = sys.modules.get(modname)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            modname, init, submodule_search_locations=[path])
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    missing = [f for f in FAMILY_API if not callable(getattr(mod, f, None))]
    if missing:
        raise LookupError(f"family {name!r} ({init}) lacks {missing}")
    return mod


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    """One request as its client saw it (host ``perf_counter`` seconds)."""
    index: int
    due: float
    prompt_len: int
    max_new: int
    prompt: np.ndarray
    submit: float = 0.0
    first: Optional[float] = None
    deliveries: List[list] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = "queued"
    output: Optional[np.ndarray] = None
    cut: bool = False                     # the client left the stream


@dataclasses.dataclass
class Step:
    """The benchmark's span around one ``engine.step`` call, with the live
    context of every token the step decoded, and the token-slots the
    scheduler planned in it against the useful ones (prompt tokens planned
    plus tokens streamed)."""
    t0: float
    t1: float
    decode_ctx: List[int]
    traced: bool = False
    planned: int = 0
    useful: int = 0


@dataclasses.dataclass
class Run:
    workload: str
    arch: dict
    family: types.ModuleType
    cell: dict
    mix: dict
    seconds: float
    start: float
    end: float
    setup_s: float
    requests: List[Rec]
    steps: List[Step]
    peaks: dict
    trace: Optional[object] = None

    def in_window(self) -> List[Rec]:
        return [r for r in self.requests if r.due < self.end]


def percentile(values, q: float) -> Optional[float]:
    """Nearest-rank ``q``-th percentile of all ``values``."""
    v = sorted(values)
    if not v:
        return None
    return v[max(0, math.ceil(q / 100.0 * len(v)) - 1)]


# ---------------------------------------------------------------------------
# the benchmark's span around each engine step
# ---------------------------------------------------------------------------

class StepProxy:
    """Stands between ``ServingGateway`` and ``ServingEngine``: times each
    ``step`` on the host clock (and, when tracing, as a
    ``TraceAnnotation`` on the profiler's clock), and reads the engine's
    per-step token tap to know the context each decoded token attended
    over. Everything else passes through to the engine."""

    def __init__(self, engine, tracer=None):
        d = self.__dict__
        d["_eng"] = engine
        d["_tracer"] = tracer
        d["_prompt_len"] = {}
        d["_emitted"] = {}
        d["_ctx"] = []
        d["steps"] = []

    def __getattr__(self, name):
        return getattr(self._eng, name)

    def __setattr__(self, name, value):
        if name != "on_tokens":
            setattr(self._eng, name, value)
            return

        def tap(events, _inner=value):
            for rid, arr in events:
                n0 = self._emitted.get(rid, 0)
                p = self._prompt_len.get(rid, 0)
                self._ctx.extend(p + j + 1 for j in range(n0, n0 + len(arr)))
                self._emitted[rid] = n0 + len(arr)
            if _inner is not None:
                _inner(events)
        self._eng.on_tokens = tap

    def make_request(self, prompt, *a, **kw):
        r = self._eng.make_request(prompt, *a, **kw)
        self._prompt_len[r.request_id] = len(r.prompt)
        return r

    def step(self):
        tracer, eng = self._tracer, self._eng
        traced = tracer is not None and tracer.before_step()
        self.__dict__["_ctx"] = []
        planned, prompt = eng.planned_token_slots, eng.useful_prefill_tokens
        t0 = time.perf_counter()
        if traced:
            import jax
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                eng.step()
        else:
            eng.step()
        t1 = time.perf_counter()
        self.steps.append(Step(
            t0, t1, self._ctx, traced, eng.planned_token_slots - planned,
            eng.useful_prefill_tokens - prompt + len(self._ctx)))
        if traced:
            tracer.after_step()


class Tracer:
    """Traces ``seconds`` of device work from ``start`` (host clock),
    starting and stopping at engine-step boundaries."""

    def __init__(self, out_dir: str, start: float, seconds: float):
        self.out_dir, self.start, self.seconds = out_dir, start, seconds
        self.state = "pending"        # -> "on" -> "done"
        self.t_on = self.t_off = None

    def before_step(self) -> bool:
        import jax
        if self.state == "pending" and time.perf_counter() >= self.start:
            jax.profiler.start_trace(self.out_dir)
            self.state, self.t_on = "on", time.perf_counter()
        return self.state == "on"

    def after_step(self) -> None:
        import jax
        if time.perf_counter() - self.t_on >= self.seconds:
            jax.profiler.stop_trace()
            self.state, self.t_off = "done", time.perf_counter()

    def close(self) -> None:
        if self.state == "on":
            import jax
            jax.profiler.stop_trace()
            self.state, self.t_off = "done", time.perf_counter()


class CompileCounter:
    """Counts executables built (compiled or loaded from the persistent
    cache) and traces, per phase, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: Dict[str, Dict[str, int]] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _bump(self, what: str) -> None:
        c = self.counts.setdefault(self.phase, {})
        c[what] = c.get(what, 0) + 1

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self._bump("executables")
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self._bump("traces")

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self._bump("cache_hits")

    def get(self, phase: str, what: str) -> int:
        return self.counts.get(phase, {}).get(what, 0)


# ---------------------------------------------------------------------------
# driving the traffic
# ---------------------------------------------------------------------------

async def _consume(handle, rec: Rec, stop: Callable[[Rec, float], bool],
                   on_first: Callable[[], None] = lambda: None):
    """Read one stream, stamping each token; leave it (which cancels the
    request) once ``stop`` says so. ``on_first`` runs at its first token."""
    last = None
    async for tok in handle.stream():
        now = time.perf_counter()
        if rec.first is None:
            rec.first = now
            on_first()
        if last is not None and now - last < GROUP_S and rec.deliveries:
            rec.deliveries[-1][1] += 1
        else:
            rec.deliveries.append([now, 1])
        last = now
        rec.tokens.append(tok)
        if stop(rec, now):
            rec.cut = True       # left early: its stream is not compared
            break
    r = await handle.result()
    rec.status = r.status
    rec.output = None if r.output is None else np.asarray(r.output)


async def drive(gateway, plan, mix: dict, slots: int, seconds: float,
                on_start: Callable[[float], None]) -> List[Rec]:
    """Offer ``plan`` through ``gateway`` for ``seconds``: a closed loop of
    ``clients_per_slot × slots`` clients, or an open loop of requests due
    at their scheduled offsets. Returns every request sent.

    A closed loop sends each client's first request (those the schedule
    caught part-way) before the window, and opens the window once every
    slot streams; its clients leave their streams at the close. An open
    loop reads each request due in the window to its first token (at most
    a minute past the close)."""
    recs: List[Rec] = []
    win = {"end": math.inf}

    def open_window() -> float:
        start = time.perf_counter()
        on_start(start)
        win["end"] = start + seconds
        return start

    async def send(p: load.Planned, due: float) -> Optional[tuple]:
        rec = Rec(p.index, due, len(p.prompt), p.max_new, p.prompt)
        recs.append(rec)
        h = await gateway.submit(p.prompt, max_new_tokens=p.max_new,
                                 temperature=p.temperature)
        rec.submit = time.perf_counter()
        return h, rec

    if mix["loop"] == "closed":
        n = load.clients(mix, slots)
        streaming = asyncio.Event()
        need = [min(n, slots)]

        def on_first():
            need[0] -= 1
            if need[0] <= 0:
                streaming.set()

        def stop(rec, now):
            return now >= win["end"]

        async def client(first):
            await _consume(*first, stop, on_first)
            while time.perf_counter() < win["end"]:
                h, rec = await send(next(plan), time.perf_counter())
                await _consume(h, rec, stop)

        firsts = [await send(next(plan), time.perf_counter())
                  for _ in range(n)]
        tasks = [asyncio.ensure_future(client(f)) for f in firsts]
        await asyncio.wait_for(streaming.wait(), FIRST_TOKEN_WAIT_S)
        open_window()
        await asyncio.gather(*tasks)
    else:
        def stop(rec, now):
            # a request due in the window is read to its first token (at
            # most a minute past the close); the rest are cut at the close
            end = win["end"]
            return now >= end and (rec.due >= end or rec.first is not None
                                   or now >= end + FIRST_TOKEN_WAIT_S)

        tasks = []
        start = open_window()
        for p in plan:
            due = start + p.due_s
            if due >= win["end"]:
                break
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            h, rec = await send(p, due)
            tasks.append(asyncio.ensure_future(_consume(h, rec, stop)))
        await asyncio.gather(*tasks)
    return recs


async def warm_traffic(engine, mix: dict, arch: dict, cell: dict,
                       seed: int, n: int = 2, max_new: int = 40) -> None:
    """Serve ``n`` requests of the mix through a gateway before the window,
    each decoding past a KV block boundary: the engine's host path runs
    small programs that ``warm_compile`` does not build (its per-slot
    ``begin_slot``, the eager conversions of the look-ahead reservation),
    and they must not compile in the window."""
    from repro.serving import ServingGateway
    plan = load.schedule(dict(mix, loop="closed"), vocab=arch["vocab_size"],
                         seed=seed + 1,
                         max_seq_len=cell["engine"]["max_seq_len"])
    async with ServingGateway(engine) as gw:
        handles = [await gw.submit(next(plan).prompt, max_new_tokens=max_new)
                   for _ in range(n)]
        for h in handles:
            async for _ in h.stream():
                pass


# ---------------------------------------------------------------------------
# the correctness comparison
# ---------------------------------------------------------------------------

def finished_in_window(recs: List[Rec], start: float, end: float
                       ) -> List[Rec]:
    """Requests served whole, whose last token came inside the window."""
    return [r for r in recs if r.status == "done" and not r.cut
            and r.deliveries and start <= r.deliveries[-1][0] < end]


def sample(recs: List[Rec], n: int, seed: int) -> List[Rec]:
    """``n`` of ``recs``: the one with the most positions, and the rest
    drawn from the seed."""
    if not recs:
        return []
    longest = max(recs, key=lambda r: (r.prompt_len + len(r.tokens),
                                       r.index))
    rest = [r for r in recs if r is not longest]
    rng = load.rng(seed, 2)
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def compare(family, w, arch: dict, recs: List[Rec], cell: dict,
            control: bool = False):
    """The numbers the check compares, each beside its limit, from the
    family's reference. With ``control``, returns ``(checks,
    control_checks)``: the second holds the same numbers for the float8
    control put in the program's place, read at the same served positions:
    the gap of the token it puts first."""
    bad = 0
    worst = ctl_worst = 0.0
    served = 0
    for r in recs:
        toks = np.asarray(r.tokens, np.int32)
        if (len(toks) != r.max_new or r.output is None
                or not np.array_equal(toks, r.output)
                or np.any(toks >= arch["vocab_size"])):
            bad += 1
            continue
        seq = np.concatenate([r.prompt, toks])
        gaps, ctl = family.gaps(w, arch, seq, r.prompt_len, control)
        worst = max(worst, float(np.max(gaps)))
        if control:
            ctl_worst = max(ctl_worst, float(np.max(ctl)))
        served += len(toks)
    limits = cell["limits"]

    def checks(gap, bad_streams):
        return {
            "max_logit_gap": {"value": gap,
                              "limit": limits["max_logit_gap"]},
            "bad_streams": {"value": bad_streams, "limit": 0},
            "served_tokens_compared": {
                "value": served, "limit": limits["min_tokens_compared"]},
        }
    if control:
        return checks(worst, bad), checks(ctl_worst, 0)
    return checks(worst, bad)


def passed(checks: dict) -> bool:
    c = checks
    return (c["max_logit_gap"]["value"] <= c["max_logit_gap"]["limit"]
            and c["bad_streams"]["value"] <= 0
            and c["served_tokens_compared"]["value"]
            >= c["served_tokens_compared"]["limit"])


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def build(family, arch: dict, cell: dict, seed: int,
          log: Callable[[str], None]):
    """The family's weights from the seed, the program's parameter tree
    over them, and the engine the cell states. Returns (weights, lm,
    engine, facts)."""
    import jax
    from bench.weights import check_tree
    from repro.models.model import LM
    from repro.serving import ServingEngine

    cfg = family.program_config(arch)
    lm = LM(cfg)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    w = jax.block_until_ready(family.make(arch, cfg.padded_vocab, seed,
                                          dtype=cfg.param_dtype))
    params = family.program_params(w, arch)
    check_tree(params, lm.abstract()[0])
    facts = {"weights_s": time.perf_counter() - t0,
             "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(w))}
    before = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    eng_kw = dict(cell["engine"])
    engine = ServingEngine(lm, params, **eng_kw)
    jax.block_until_ready(jax.tree.leaves(engine._cache_state))
    after = (dev.memory_stats() or {}).get("bytes_in_use", 0)
    kv = sum(x.nbytes for x in jax.tree.leaves(engine._cache_state))
    facts.update(pool_logical_bytes=kv, pool_measured_bytes=after - before,
                 bytes_in_use_after_pool=after)
    log(f"weights {facts['weight_bytes']} B in {facts['weights_s']:.3f} s; "
        f"KV pool {kv} B logical, {after - before} B measured "
        f"(bytes_in_use {after})")
    return w, lm, engine, facts


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             process_start: float, log: Callable[[str], None],
             out_dir: str, bench: Optional[dict] = None,
             arch: Optional[dict] = None, cell: Optional[dict] = None,
             mix: Optional[dict] = None, device: Optional[dict] = None,
             control: bool = False, root: str = ROOT) -> dict:
    """Run ``workload`` once and return the result line (a dict).
    ``BENCHMARK.json``, the configuration and its family are read under
    ``root`` where not given."""
    import jax
    from repro.serving import ServingGateway

    bench = bench or benchmark(root)
    entry = _entry(bench["workloads"], workload, "workload")
    arch = arch or arch_of(entry["config"], root)
    family = family_of(arch, root)
    cell = cell or cell_of(workload)
    mix = mix or load.load_mix(entry["traffic"])
    dev = jax.devices()[0]
    device = device or {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(jax.devices())}
    peaks = peaks_of(device["kind"])
    counter = CompileCounter()

    w, lm, engine, facts = build(family, arch, cell, seed, log)
    t0 = time.perf_counter()
    engine.warm_compile()
    facts["warm_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    asyncio.run(warm_traffic(engine, mix, arch, cell, seed))
    facts["warm_traffic_s"] = time.perf_counter() - t0
    facts["warm_executables"] = counter.get("setup", "executables")
    facts["warm_cache_hits"] = counter.get("setup", "cache_hits")
    log(f"warm_compile {facts['warm_s']:.3f} s, warm-up requests "
        f"{facts['warm_traffic_s']:.3f} s; executables built in set-up "
        f"{facts['warm_executables']}, of them from the persistent cache "
        f"{facts['warm_cache_hits']}")

    tracer = None
    if trace:
        tr = cell.get("trace", {})
        # the window's opening sets the start; steps before it (the
        # opening prefill) are never traced
        tracer = Tracer(os.path.join(out_dir, "trace"), math.inf,
                        float(tr.get("seconds", 2.0)))
    proxy = StepProxy(engine, tracer)
    slots = cell["engine"]["batch_slots"]
    caught = load.clients(mix, slots) if mix["loop"] == "closed" else 0
    plan = load.schedule(mix, vocab=arch["vocab_size"], seed=seed,
                         max_seq_len=cell["engine"]["max_seq_len"],
                         in_progress=caught)
    window = {}

    def on_start(t):
        window["start"] = t
        counter.phase = "window"
        if tracer is not None:
            tracer.start = t + seconds * float(
                cell.get("trace", {}).get("start_share", 0.4))

    async def main():
        gw = ServingGateway(proxy)
        await gw.start()
        recs = await drive(gw, plan, mix, slots, seconds, on_start)
        counter.phase = "after"
        await gw.drain()
        return recs

    recs = asyncio.run(main())
    if tracer is not None:
        tracer.close()
    start = window["start"]
    setup_s = start - process_start
    late = [r.submit - r.due for r in recs if r.due < start + seconds]
    facts["generator_late_p50_s"] = percentile(late, 50)
    facts["generator_late_max_s"] = max(late) if late else None
    log(f"generator lateness over {len(late)} requests: p50 "
        f"{facts['generator_late_p50_s']} s, max "
        f"{facts['generator_late_max_s']} s")
    log(f"compiles inside the window: executables "
        f"{counter.get('window', 'executables')}, traces "
        f"{counter.get('window', 'traces')}")
    stats = dev.memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    facts.update(preemptions=engine.preemptions,
                 window_compiles=counter.get("window", "executables"),
                 window_traces=counter.get("window", "traces"))

    run = Run(workload, arch, family, cell, mix, seconds, start,
              start + seconds, setup_s, recs, proxy.steps, peaks)
    if tracer is not None and tracer.t_on is not None:
        from bench import trace as trace_mod
        run.trace = trace_mod.reduce_dir(
            tracer.out_dir, save=os.path.join(out_dir, "trace.json.gz"))
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s

    # free the engine and its pool before the reference runs
    del proxy, engine
    gc.collect()
    log(f"bytes_in_use before the reference "
        f"{(dev.memory_stats() or {}).get('bytes_in_use')}")

    t0 = time.perf_counter()
    done = finished_in_window(recs, run.start, run.end)
    checked = sample(done, cell["check_requests"], seed)
    checks = compare(family, w, arch, checked, cell, control)
    if control:
        checks, ctl_checks = checks
    facts["requests_done"] = len(done)
    facts["compared_tokens_in_window"] = sum(
        k for r in checked for t, k in r.deliveries if t >= run.start)
    log(f"reference over {len(checked)} of the {len(done)} requests "
        f"finished in the window, in {time.perf_counter() - t0:.3f} s; "
        f"of the tokens compared {facts['compared_tokens_in_window']} "
        f"came inside the window")

    metrics = {}
    for m in metrics_for(bench, workload, trace):
        v = metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    window_recs = run.in_window()
    failed = sum(r.status in ("failed", "rejected") for r in window_recs)
    facts["steps"] = len(run.steps)
    result = {"facts": facts, "correct": passed(checks),
              "attempted": len(window_recs),
              "failed": failed, "metrics": metrics, "device": device}
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    if control:
        result["control"] = {"correct": passed(ctl_checks),
                             "checks": ctl_checks}
    result["checks"] = checks
    return result
