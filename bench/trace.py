"""From a profiler trace to the benchmark's device numbers.

``extract`` reads one ``.xplane.pb`` with ``jax.profiler.ProfileData``
(nothing but JAX) and keeps what the reduction needs, as plain lists:
the benchmark's ``bench.engine_step`` host spans, and for each TPU its
``XLA Modules`` events (one per jitted program run, named
``jit_<function>(<id>)``) and ``XLA Ops`` events (named by their HLO text,
``%paged_decode_attn.9 = ...``; a scan over layers shows as a ``%while``
op that contains the layer's ops). ``reduce`` then computes:

- the traced window: from the first to the last engine-step span;
- busy time: the union of the op intervals in the window, per chip, and
  its mean over chips;
- device time per jitted program: the union of the intervals of the ops
  that its module events contain;
- op self time (an op's time less that of the ops nested in it);
- the paged decode-attention kernel's time in each program;
- the idle gaps, each split by whether the host was inside an engine step
  (the engine's own host work) or between steps (the gateway loop,
  clients and the executor hop) while the device waited.

``breakdown`` gives the ten ops with the most self time and the idle time
by label, for the result line.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

STEP_SPAN = "bench.engine_step"
DECODE_PROGRAMS = ("_step_impl", "_scan_impl")
PAGED_KERNEL = re.compile(r"^paged_decode_attn(\.\d+)?$")
INSIDE, BETWEEN = "host inside engine.step", "host between engine steps"


def op_name(hlo: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def program_name(module: str) -> str:
    """``jit__step_impl(12)`` -> ``_step_impl``."""
    name = re.sub(r"\(\d+\)$", "", module)
    return name[4:] if name.startswith("jit_") else name


def extract(pd) -> dict:
    """The parts of a ``ProfileData`` that ``reduce`` reads."""
    out = {"spans": [], "devices": []}
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out["spans"] += [[int(e.start_ns), int(e.end_ns)]
                                 for e in line.events if e.name == STEP_SPAN]
        elif re.match(r"/device:TPU:\d+$", plane.name):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = [[program_name(e.name), int(e.start_ns),
                                       int(e.end_ns)] for e in line.events]
                elif line.name == "XLA Ops":
                    dev["ops"] = [[op_name(e.name), int(e.start_ns),
                                   int(e.end_ns)] for e in line.events]
            out["devices"].append(dev)
    out["spans"].sort()
    return out


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _length(iv) -> int:
    return sum(b - a for a, b in iv)


def _self_times(ops) -> Dict[str, int]:
    """Each op's duration less that of the ops nested in it (``ops``
    sorted by start). An op that only partly overlaps the one before it
    is its sibling, not its child."""
    out: Dict[str, int] = collections.defaultdict(int)
    stack: List[tuple] = []           # (name, end) of the enclosing ops
    for name, a, b in ops:
        while stack and (stack[-1][1] <= a or stack[-1][1] < b):
            stack.pop()
        out[name] += b - a
        if stack:
            out[stack[-1][0]] -= b - a
        stack.append((name, b))
    return out


def _overlap(spans, a: int, b: int) -> int:
    """Time of [a, b) covered by the (sorted, disjoint) ``spans``."""
    i = max(0, bisect.bisect_right(spans, (a, a)) - 1)
    t = 0
    while i < len(spans) and spans[i][0] < b:
        t += max(0, min(b, spans[i][1]) - max(a, spans[i][0]))
        i += 1
    return t


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    programs: Dict[str, float]          # program -> device busy seconds
    ops: Dict[str, float]               # "program/op" -> self seconds
    kernel: Dict[str, float]            # "program/op" -> paged kernel s
    gaps: List[Tuple[str, float]]       # (host label, idle seconds)

    def program_busy(self, name: str) -> float:
        return self.programs.get(name, 0.0)

    def decode_kernel_s(self) -> float:
        return sum(v for k, v in self.kernel.items()
                   if k.split("/")[0] in DECODE_PROGRAMS)

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:10]
        by_label: Dict[str, List[float]] = collections.defaultdict(list)
        for label, s in self.gaps:
            by_label[label].append(s)
        rows = sorted(((f"{label}, all {len(v)} gaps", sum(v))
                       for label, v in by_label.items()),
                      key=lambda kv: -kv[1])
        longest = sorted(self.gaps, key=lambda g: -g[1])
        rows += [(f"{label}, one gap", s)
                 for label, s in longest[:10 - len(rows)]]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in rows[:10]]}


def reduce(ex: dict) -> Optional[TraceSummary]:
    spans = [tuple(s) for s in ex["spans"]]
    devices = [d for d in ex["devices"] if d["ops"]]
    if not spans or not devices:
        return None
    lo, hi = spans[0][0], spans[-1][1]
    busy_total = 0
    programs: Dict[str, int] = collections.defaultdict(int)
    ops: Dict[str, int] = collections.defaultdict(int)
    kernel: Dict[str, int] = collections.defaultdict(int)
    gaps: List[Tuple[str, float]] = []
    for dev in devices:
        mods = sorted((a, b, n) for n, a, b in dev["modules"])
        starts = [m[0] for m in mods]
        inside = sorted((max(a, lo), min(b, hi), n) for n, a, b in dev["ops"]
                        if b > lo and a < hi)
        per_prog: Dict[str, list] = collections.defaultdict(list)
        named = []
        for a, b, n in inside:
            i = bisect.bisect_right(starts, a) - 1
            prog = mods[i][2] if i >= 0 and mods[i][1] >= a else "?"
            per_prog[prog].append((a, b))
            named.append((f"{prog}/{n}", a, b))
            if PAGED_KERNEL.match(n):
                kernel[f"{prog}/{n}"] += b - a
        for k, v in _self_times(named).items():
            ops[k] += v
        busy = _union([(a, b) for a, b, _ in inside])
        busy_total += _length(busy)
        for prog, iv in per_prog.items():
            programs[prog] += _length(_union(iv))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                host = _overlap(spans, a, b)
                gaps += [(label, t * 1e-9) for label, t in
                         ((INSIDE, host), (BETWEEN, b - a - host)) if t > 0]
    n = len(devices)
    per = 1e-9 / n
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * per, chips=n,
        programs={k: v * per for k, v in programs.items()},
        ops={k: v * per for k, v in ops.items()},
        kernel={k: v * per for k, v in kernel.items()},
        gaps=gaps)


def load_extract(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def reduce_dir(trace_dir: str, save: Optional[str] = None
               ) -> Optional[TraceSummary]:
    """Reduce the trace written under ``trace_dir``, then delete it.
    ``save``: also write the extraction there (gzipped JSON)."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    try:
        if not files:
            return None
        ex = extract(jax.profiler.ProfileData.from_file(files[-1]))
        if save:
            with gzip.open(save, "wt") as f:
                json.dump(ex, f)
        return reduce(ex)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
