"""Production serving launcher: open-loop traffic through the async
gateway — streamed tokens, backpressure, SLO classes — on the dense
engine or the ACE edge/cloud cascade with --cascade.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --no-reduced
    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --cascade
    PYTHONPATH=src python -m repro.launch.serve --rate 40 --policy shed

Arrivals are an open-loop Poisson process (``--rate`` req/s, independent
of service rate), each request streamed as its tokens land; under
``--rate`` beyond capacity the gateway's bounded queue and backpressure
policy decide who waits, who is shed, and who is refused.

Durability (--supervise): the gateway runs with a write-ahead request
journal, periodic engine snapshots, and a wall-clock watchdog on every
dispatch. Two demo fault modes exercise the recovery ladder end to end:

    --hang-demo    a dispatch stalls briefly — the watchdog times out,
                   the late step is rolled back through the retry path
                   (note_hang), and service continues in-process
    --wedge-demo   a dispatch stalls past the grace window — the driver
                   raises EngineWedgedError, and the supervisor restarts
                   from snapshot + journal; recovered requests finish
                   token-exact, crash-lost ones are replayed
"""
from __future__ import annotations

import argparse
import asyncio
import os
import tempfile
from collections import Counter

import jax
import numpy as np

from repro.cascade.ecc_infer import CascadeLM, edge_variant
from repro.cascade.gate import make_thresholds
from repro.configs import get_config
from repro.core.monitoring import MonitoringService
from repro.launch.mesh import make_host_mesh
from repro.models.model import LM
from repro.serving import (CascadeServingEngine, EngineWedgedError,
                           FaultPlan, RequestJournal, ServingEngine,
                           ServingGateway, enable_compile_cache, init_params,
                           recover_engine)


def _mesh_from_args(args):
    """--mesh N -> a (data, model) host mesh with an N-way model axis
    (tensor-parallel decode: params and KV pools shard over KV heads)."""
    ways = int(args.mesh or 1)
    if ways <= 1:
        return None
    n = len(jax.devices())
    if n % ways != 0:
        raise SystemExit(
            f"--mesh {ways} needs a device count divisible by {ways} "
            f"(found {n}; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={ways} before launch)")
    return make_host_mesh(model=ways)


def _build_engine(cfg, args, fault_plan=None):
    mesh = _mesh_from_args(args)
    if args.cascade:
        edge_cfg = edge_variant(cfg, layers=1)
        cloud, edge = LM(cfg, kv_chunk=32), LM(edge_cfg, kv_chunk=32)
        cp = init_params(cloud, jax.random.PRNGKey(0), mesh)
        ep = init_params(edge, jax.random.PRNGKey(1), mesh)
        cascade = CascadeLM(edge, cloud,
                            thresholds=make_thresholds(hi=0.01, lo=0.001))
        return CascadeServingEngine(cascade, ep, cp, batch_slots=4,
                                    max_seq_len=96, fault_plan=fault_plan,
                                    mesh=mesh)
    lm = LM(cfg, kv_chunk=32)
    params = init_params(lm, jax.random.PRNGKey(0), mesh)
    return ServingEngine(lm, params, batch_slots=4, max_seq_len=96,
                         fault_plan=fault_plan, mesh=mesh)


async def _client(gw: ServingGateway, prompt, max_new: int,
                  priority: int, deadline_s, quiet: bool) -> dict:
    """One open-loop client: submit, consume the stream, report."""
    h = await gw.submit(prompt, max_new_tokens=max_new, priority=priority,
                        deadline_s=deadline_s)
    toks = []
    async for t in h.stream():
        toks.append(t)
    r = await h.result()
    if not quiet:
        route = getattr(r, "route", "")
        extra = f" route={route}" if route else ""
        print(f"req {r.request_id}: status={r.status}{extra} "
              f"tokens={toks} ttft={r.ttft_s * 1e3:.0f}ms "
              f"latency={r.latency_s * 1e3:.0f}ms")
    return {"status": r.status, "streamed": len(toks)}


def _demo_fault_plan(args):
    """The two watchdog demos differ only in stall length relative to the
    watchdog deadline: a hang completes late (in-process rollback via
    note_hang), a wedge never completes within grace (supervised
    restart)."""
    if args.wedge_demo:
        return FaultPlan(hang=[2],
                         hang_s=args.step_timeout * (1.0 + args.hang_grace)
                         + 2.0)
    if args.hang_demo:
        return FaultPlan(hang=[2], hang_s=args.step_timeout * 1.5)
    return None


async def _serve(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    rng = np.random.default_rng(0)
    monitor = MonitoringService()

    journal = None
    gw_kw = {}
    if args.compile_cache:
        # persistent executable cache at a fixed path: a supervised
        # restart-from-snapshot (or a rerun) replays warm_compile from disk
        print(f"compile cache: {enable_compile_cache()}")
    if args.supervise:
        state_dir = args.state_dir or tempfile.mkdtemp(
            prefix="repro_serve_")
        journal = RequestJournal(os.path.join(state_dir, "journal.jsonl"))
        gw_kw = dict(journal=journal,
                     snapshot_dir=os.path.join(state_dir, "snapshots"),
                     snapshot_every=args.snapshot_every,
                     step_timeout_s=args.step_timeout,
                     hang_grace=args.hang_grace)
        print(f"supervised: state in {state_dir}")
    eng = _build_engine(cfg, args, fault_plan=_demo_fault_plan(args))

    results, wedged = [], None
    gw = ServingGateway(eng, max_queue=args.max_queue,
                        policy=args.policy, **gw_kw)
    try:
        async with gw:
            clients = []
            for i in range(args.requests):
                prompt = rng.integers(0, min(1000, cfg.vocab_size),
                                      size=4 + i % 5)
                priority = i % 2 if args.classes > 1 else 0
                clients.append(asyncio.create_task(_client(
                    gw, prompt, args.max_new, priority,
                    args.deadline if priority else None, args.quiet)))
                # open loop: exponential inter-arrivals at --rate req/s,
                # drawn independently of how fast the engine is serving
                await asyncio.sleep(float(rng.exponential(1.0 / args.rate)))
            results = await asyncio.gather(*clients)
    except EngineWedgedError as e:
        wedged = e
        monitor.record_hang("serve", detail=str(e))

    by_status = Counter(res["status"] for res in results)
    print(f"served {len(results)} arrivals at {args.rate:.0f} req/s: "
          f"{dict(by_status)}  gateway={gw.stats()}")

    if wedged is not None:
        if not args.supervise:
            raise wedged
        # supervised restart: the wedged engine's thread is a write-off —
        # recover a *fresh* engine from the last snapshot + journal and
        # drain the surviving work synchronously (token-exact resumes;
        # crash-lost acknowledged submits restart from their prompts)
        print(f"engine wedged ({wedged}); restarting from snapshot")
        eng2 = _build_engine(cfg, args)
        info = recover_engine(eng2, snapshot_dir=gw_kw["snapshot_dir"],
                              journal=journal)
        monitor.record_restart("serve", info)
        monitor.record_journal("serve", info["replayed"])
        done = eng2.run()
        statuses = Counter(r.status for r in done.values())
        print(f"recovered {info['restored']} + replayed "
              f"{info['replayed']}; post-restart drain: {dict(statuses)}")
        print(f"durability: {monitor.durability_counters()}")
    if journal is not None:
        journal.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the config's reduced float32 variant; "
                         "--no-reduced serves the published widths")
    ap.add_argument("--cascade", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="offered load, requests/s (open loop)")
    ap.add_argument("--policy", default="block",
                    choices=["block", "reject", "shed",
                             "reject-overload", "shed-lowest-class"])
    ap.add_argument("--max-queue", type=int, default=16)
    ap.add_argument("--classes", type=int, default=2,
                    help="SLO classes to alternate arrivals over")
    ap.add_argument("--deadline", type=float, default=None,
                    help="relative deadline (s) for class-1 arrivals")
    ap.add_argument("--quiet", action="store_true")
    # durability (ISSUE 9)
    ap.add_argument("--supervise", action="store_true",
                    help="journal + periodic snapshots + watchdog; on "
                         "EngineWedgedError, restart from snapshot")
    ap.add_argument("--state-dir", default=None,
                    help="journal/snapshot directory (default: tmpdir)")
    # mesh-aware serving (ISSUE 10)
    ap.add_argument("--mesh", type=int, default=1,
                    help="tensor-parallel ways on the 'model' mesh axis "
                         "(must divide the device count and the KV heads; "
                         "on CPU export "
                         "XLA_FLAGS=--xla_force_host_platform_device_count"
                         "=N first). 1 = single-device (default)")
    ap.add_argument("--compile-cache", action="store_true",
                    help="persist compiled executables in "
                         "$JAX_COMPILATION_CACHE_DIR (default: .jax_cache "
                         "in the checkout) so restarts skip recompilation")
    ap.add_argument("--step-timeout", type=float, default=5.0,
                    help="watchdog wall-clock deadline per dispatch (s)")
    ap.add_argument("--hang-grace", type=float, default=1.0,
                    help="grace window as a multiple of --step-timeout")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="engine steps between periodic snapshots")
    ap.add_argument("--hang-demo", action="store_true",
                    help="inject a recoverable dispatch stall")
    ap.add_argument("--wedge-demo", action="store_true",
                    help="inject a stall past grace (supervised restart)")
    args = ap.parse_args()
    if args.hang_demo or args.wedge_demo:
        args.supervise = True
    asyncio.run(_serve(args))


if __name__ == "__main__":
    main()
