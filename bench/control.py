"""Readings that set a cell's correctness limit, on the chip.

    python3 bench/control.py --workload qwen3-4b.decode --seconds 10 \
        --seeds 1 2 3 ...

For each seed, in one process: a run of the cell at its own load for a
short window (the timed path as ``bench/run.py`` drives it), then, on the
sampled requests, the program's widest logit gap under the float32
reference of the configuration's family (the lower reading) and, at the
same served positions, the widest gap of the tokens that the family's
float8 control puts first (the upper reading), with whether the control,
put through the same comparison, comes out correct (it must not). The
benchmark's own runs never run the control. Prints one JSON line per seed
and a summary line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "bench"), os.path.join(ROOT,
                                                                    "src")]
    import run as entry
    from bench import harness
    from repro.serving import enable_compile_cache

    bench = harness.benchmark()
    chips = harness._entry(bench["workloads"], args.workload,
                           "workload")["chips"]
    device = entry.check_device(int(chips))
    enable_compile_cache()
    lower, upper = [], []
    for seed in args.seeds:
        res = harness.run_cell(
            args.workload, seed, args.seconds, False, process_start=0.0,
            log=entry.log, out_dir=os.path.join(ROOT, "bench_out", "control"),
            bench=bench, device=device, control=True)
        c, ctl = res["checks"], res["control"]
        lower.append(c["max_logit_gap"]["value"])
        upper.append(ctl["checks"]["max_logit_gap"]["value"])
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "control_correct": ctl["correct"],
                          "program": lower[-1], "control": upper[-1],
                          "tokens": c["served_tokens_compared"]["value"],
                          "bad_streams": c["bad_streams"]["value"],
                          "facts": res["facts"],
                          "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower_reading": max(lower),
                      "upper_reading": min(upper)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
