"""Run one benchmark cell once, on the chip it is started on.

    python3 bench/run.py --workload qwen3-4b.decode --seed 7 --seconds 10 \
        --trace 0

Refuses to run unless JAX finds a TPU (and as many chips as the cell asks
for) whose kind is in ``bench/peaks.json``. Keeps JAX's persistent
compilation cache in ``.jax_cache/`` of the checkout. Prints set-up facts,
the generator's lateness and the compared numbers on standard error, and
as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), then ``checks``, each compared number beside its limit.
"""
from __future__ import annotations

import time

T_IMPORT = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def process_start() -> float:
    """When this process started, on the ``time.perf_counter`` clock
    (from /proc on Linux, else the first import of this file)."""
    return time.perf_counter() - (time.time() - _start_wall())


def _start_wall() -> float:
    """Wall-clock time this process started (Linux), else first import."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime"))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return T_IMPORT


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_device(chips: int):
    """The device facts, or an exit without a result when JAX finds no TPU,
    too few chips, or a kind the peaks table does not know."""
    import jax
    from bench.harness import peaks_of
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench: JAX found {dev.platform}, not a TPU; "
                         f"refusing to measure anywhere else")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    try:
        peaks_of(dev.device_kind)
    except LookupError as e:
        raise SystemExit(f"bench: {e}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t0 = process_start()

    # the compile cache lives in the checkout, at one fixed path
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    from repro.serving import enable_compile_cache

    bench = harness.benchmark()
    entry = harness._entry(bench["workloads"], args.workload, "workload")
    device = check_device(int(entry["chips"]))
    log(f"device {device}, compile cache {enable_compile_cache()}")
    out_dir = os.path.join(ROOT, "bench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)
    result = harness.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        process_start=t0, log=log, out_dir=out_dir, bench=bench,
        device=device)
    print(json.dumps({"setup_and_window": result.pop("facts")}), flush=True)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    log(f"correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
