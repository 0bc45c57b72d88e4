"""Smoke run of the serving stack on a TPU, through its normal entry points.

    python chip_smoke.py            # one chip: smollm-135m at full width
    python chip_smoke.py --mesh 4   # four chips: starcoder2-7b, sharded

One chip. smollm-135m at its published widths (30 layers, d_model 576,
9 heads, 3 KV heads, bf16) with seeded random weights:

1. ``kernels``: the ring and paged decode kernels, run natively at the
   served shapes (one decode token and one prefill chunk), against their
   ``repro.kernels.ref`` oracles computed in float32 at highest precision.
2. ``ring`` / ``paged``: a few requests of a few hundred prompt tokens
   served through ``ServingGateway`` -> ``ServingEngine`` with chunked
   prefill, on each KV backend. Every request must end ``done`` with its
   full token count, and the engine's decode step must hold the Pallas
   kernels (``tpu_custom_call``).
3. ``cascade``: the same requests through ``CascadeServingEngine`` (ACE's
   edge/cloud cascade) with smollm-135m as the cloud model.

Four chips (``--mesh 4``), and nothing else: starcoder2-7b (20.2 GB of
bf16 weights as this repo builds it, with a gated GELU MLP: more than one
v5e's 16 GB) initialised straight into its tensor-parallel shardings and
served on a 4-way ``model`` axis; then the same architecture cut to a few
layers, run with ``mesh=None`` and on the mesh in one process: its
prefill logits must agree, and so must its decode-step logits (the decode
kernels, per KV-head shard on the mesh) wherever both sides decoded the
same token history.

The script refuses to run anywhere but on a TPU. Every phase that fails
makes it exit nonzero. The numbers it prints are smoke output, not
benchmark numbers. Its last line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.base import Stage  # noqa: E402
from repro.cascade.ecc_infer import CascadeLM, edge_variant  # noqa: E402
from repro.cascade.gate import (confidence_from_logits,  # noqa: E402
                                make_thresholds)
from repro.kernels.decode_attention import (  # noqa: E402
    decode_attention, paged_decode_attention)
from repro.kernels.ref import (decode_attention_ref,  # noqa: E402
                               paged_decode_attention_ref)
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models.model import LM  # noqa: E402
from repro.serving import (CascadeServingEngine, ServingEngine,  # noqa: E402
                           ServingGateway, assert_cache_placement,
                           enable_compile_cache, init_params, serving_rules)

SLOTS = 4
MAX_SEQ = 512
CHUNK = 128
BLOCK_SIZE = 16
# prompts of 256 and 320 tokens: chunks (128, 128) and (128, 128, 64), so
# few chunk programs compile
PROMPT_LENS = (256, 320, 256, 320)
MAX_NEW = 32
# kernel vs float32 oracle: the kernel's output is bf16 and its p @ v
# product takes p in bf16, so |error| stays near bf16 rounding of O(1)
KERNEL_ATOL = 2e-2
# mesh vs single-device prefill and decode-step logits, relative to the
# largest |logit|: bf16 matmuls whose partial sums are reduced in a
# different order
LOGITS_RTOL = 2e-2
CUT_LAYERS = 2


def say(phase: str, msg: str) -> None:
    print(f"[smoke {phase}] {msg}", flush=True)


def device_memory() -> list:
    return [d.memory_stats() or {} for d in jax.devices()]


def memory_report(phase: str) -> list:
    """Per-device bytes in use (smoke output, not a benchmark number)."""
    stats = device_memory()
    for d, m in zip(jax.devices(), stats):
        say(phase, f"{d}: bytes_in_use={m.get('bytes_in_use')} "
                   f"peak_bytes_in_use={m.get('peak_bytes_in_use')} "
                   f"bytes_limit={m.get('bytes_limit')}")
    return stats


def prompts(vocab: int, lens=PROMPT_LENS, seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# kernels vs oracles
# ---------------------------------------------------------------------------

def _kernel_inputs(rng, cfg, t: int):
    """Random bf16 q/k/v and cache geometry at the served shapes: each slot
    holds a random-length prefix, the rest is empty (−1)."""
    h, kv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    lens = rng.integers(t, MAX_SEQ + 1, size=SLOTS)
    q = rng.standard_normal((SLOTS, t, h, hd))
    k = rng.standard_normal((SLOTS, MAX_SEQ, kv, hd))
    v = rng.standard_normal((SLOTS, MAX_SEQ, kv, hd))
    k_pos = np.where(np.arange(MAX_SEQ)[None] < lens[:, None],
                     np.arange(MAX_SEQ)[None], -1)
    q_pos = lens - t                         # the chunk ends the prefix
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)  # noqa: E731
    return (bf(q), bf(k), bf(v), jnp.asarray(q_pos, jnp.int32),
            jnp.asarray(k_pos, jnp.int32), lens)


def _paged_inputs(rng, k, v, k_pos, lens):
    """Scatter each slot's ring line into shuffled pool blocks; table
    entries past a slot's length are −1 holes, and blocks no table names
    hold garbage positions that must stay invisible."""
    m = MAX_SEQ // BLOCK_SIZE
    n = SLOTS * m + 1                             # block 0: trash
    kv, hd = k.shape[2], k.shape[3]
    perm = rng.permutation(np.arange(1, n))
    tables = np.full((SLOTS, m), -1, np.int32)
    pk = np.asarray(rng.standard_normal((n, BLOCK_SIZE, kv, hd)), np.float32)
    pv = np.asarray(rng.standard_normal((n, BLOCK_SIZE, kv, hd)), np.float32)
    ppos = rng.integers(0, MAX_SEQ, size=(n, BLOCK_SIZE)).astype(np.int32)
    kf, vf = np.asarray(k, np.float32), np.asarray(v, np.float32)
    kp = np.asarray(k_pos)
    for b in range(SLOTS):
        for j in range(-(-int(lens[b]) // BLOCK_SIZE)):
            blk = perm[b * m + j]
            tables[b, j] = blk
            sl = slice(j * BLOCK_SIZE, (j + 1) * BLOCK_SIZE)
            pk[blk], pv[blk], ppos[blk] = kf[b, sl], vf[b, sl], kp[b, sl]
    bf = lambda x: jnp.asarray(x, jnp.bfloat16)  # noqa: E731
    return bf(pk), bf(pv), jnp.asarray(ppos), jnp.asarray(tables)


def check_kernels(cfg, seed: int = 0) -> None:
    rng = np.random.default_rng(seed)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    for t in (1, CHUNK):
        q, k, v, q_pos, k_pos, lens = _kernel_inputs(rng, cfg, t)
        pk, pv, ppos, tables = _paged_inputs(rng, k, v, k_pos, lens)
        runs = {
            "ring": (jax.jit(decode_attention)(q, k, v, q_pos, k_pos),
                     decode_attention_ref, (f32(q), f32(k), f32(v), q_pos,
                                            k_pos)),
            "paged": (jax.jit(paged_decode_attention)(q, pk, pv, q_pos, ppos,
                                                      tables),
                      paged_decode_attention_ref,
                      (f32(q), f32(pk), f32(pv), q_pos, ppos, tables)),
        }
        for name, (out, oracle, args) in runs.items():
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(oracle)(*args)
            err = float(jnp.max(jnp.abs(f32(out) - ref)))
            assert out.shape == ref.shape, (name, out.shape, ref.shape)
            assert np.isfinite(err), f"{name} kernel T={t}: non-finite"
            say("kernels", f"{name} T={t} q={tuple(q.shape)}: max |kernel - "
                           f"oracle| = {err:.3e} (tolerance {KERNEL_ATOL})")
            assert err <= KERNEL_ATOL, (
                f"{name} decode kernel at T={t} differs from its oracle by "
                f"{err:.3e} > {KERNEL_ATOL}")


# ---------------------------------------------------------------------------
# serving through the gateway
# ---------------------------------------------------------------------------

def serve(engine, batch: list, max_new: int = MAX_NEW) -> list:
    """Submit ``batch`` through a ``ServingGateway``, consume every stream,
    and check each request ends ``done`` with ``max_new`` tokens."""

    async def client(gw, prompt):
        h = await gw.submit(prompt, max_new_tokens=max_new)
        streamed = [t async for t in h.stream()]
        return await h.result(), streamed

    async def main():
        async with ServingGateway(engine, max_queue=len(batch)) as gw:
            return await asyncio.gather(*(client(gw, p) for p in batch))

    results = asyncio.run(main())
    for r, streamed in results:
        assert r.status == "done", (r.request_id, r.status)
        assert len(r.output) == max_new and len(streamed) == max_new, (
            r.request_id, len(r.output), len(streamed))
    return [r for r, _ in results]


def decode_step_has_kernel(engine) -> bool:
    lowered = engine._step_fn.lower(engine.params, engine._cache_state,
                                    engine._state, engine._base_key)
    return "tpu_custom_call" in lowered.as_text()


def serve_phase(name: str, engine, batch: list) -> None:
    t0 = time.perf_counter()
    done = serve(engine, batch)
    wall = time.perf_counter() - t0
    tokens = sum(len(r.output) for r in done)
    say(name, f"{len(done)} requests done, {tokens} tokens streamed in "
              f"{wall:.1f}s wall, compiles included (smoke output)")
    targets = [engine] if isinstance(engine, ServingEngine) else \
        [engine.edge_engine, engine.cloud_engine]
    for eng in targets:
        assert decode_step_has_kernel(eng), \
            f"{name}: decode step of {eng.lm.cfg.name} has no Pallas kernel"
    say(name, "decode step holds tpu_custom_call")


def single_chip(cfg) -> None:
    t0 = time.perf_counter()
    check_kernels(cfg)
    say("kernels", f"{time.perf_counter() - t0:.1f}s including compiles")

    t0 = time.perf_counter()
    lm = LM(cfg)
    params = jax.block_until_ready(init_params(lm, jax.random.PRNGKey(0)))
    n = sum(x.size for x in jax.tree.leaves(params))
    say("setup", f"{cfg.name}: {cfg.num_layers} layers, d_model "
                 f"{cfg.d_model}, {n} params ({cfg.param_dtype}) built in "
                 f"{time.perf_counter() - t0:.1f}s")
    batch = prompts(cfg.vocab_size)
    for backend in ("ring", "paged"):
        eng = ServingEngine(lm, params, batch_slots=SLOTS,
                            max_seq_len=MAX_SEQ, cache_backend=backend,
                            block_size=BLOCK_SIZE, chunk_tokens=CHUNK)
        serve_phase(backend, eng, batch)
        del eng

    edge = LM(edge_variant(cfg, layers=1))
    edge_params = init_params(edge, jax.random.PRNGKey(1))
    # accept the most confident half at the edge and escalate the rest to
    # the full-width cloud model; never drop (a drop answers no tokens)
    conf = [float(confidence_from_logits(
        edge.forward(edge_params, {"tokens": jnp.asarray(p)[None]})[0][:, -1]
    )[0]) for p in batch]
    hi = 0.5 * (min(conf) + max(conf))
    cascade = CascadeLM(edge, lm, thresholds=make_thresholds(hi=hi, lo=0.0))
    eng = CascadeServingEngine(cascade, edge_params, params,
                               batch_slots=SLOTS, max_seq_len=MAX_SEQ,
                               cache_backend="paged", block_size=BLOCK_SIZE,
                               chunk_tokens=CHUNK)
    serve_phase("cascade", eng, batch)
    m = eng.metrics
    say("cascade", f"routes: accepted at the edge {m.accepted}, escalated "
                   f"to the cloud {m.escalated}, dropped {m.dropped}")
    assert m.accepted >= 1 and m.escalated >= 1 and m.dropped == 0, \
        "the cascade did not exercise both legs"
    memory_report("memory")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def depth_cut(cfg, layers: int):
    """The same architecture with its first ``layers`` layers."""
    blocks = cfg.stages[0].blocks
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-{layers}l", num_layers=layers,
        stages=(Stage(blocks=blocks, repeat=layers // len(blocks)),))


def _device_bytes(tree) -> dict:
    per = {}
    for leaf in jax.tree.leaves(tree):
        for s in leaf.addressable_shards:
            per[s.device] = per.get(s.device, 0) + s.data.nbytes
    return per


def mesh_phase(cfg, ways: int) -> None:
    assert len(jax.devices()) == ways, \
        f"--mesh {ways} needs {ways} devices, found {len(jax.devices())}"
    mesh = make_host_mesh(model=ways)
    lm = LM(cfg)
    abstract, _ = lm.abstract()
    total = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(abstract))
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(lm, jax.random.PRNGKey(0), mesh))
    per_dev = _device_bytes(params)
    peaks = [m["peak_bytes_in_use"] for m in device_memory()]
    say("mesh", f"{cfg.name}: {total} param bytes, initialised sharded in "
                f"{time.perf_counter() - t0:.1f}s; per-device param bytes "
                f"{sorted(per_dev.values())}; per-device peak {peaks}")
    assert len(per_dev) == ways and max(per_dev.values()) <= 0.3 * total, \
        "parameters are not split over the mesh"
    assert max(peaks) < 0.5 * total, \
        "a device held most of the parameter tree during init"

    eng = ServingEngine(lm, params, batch_slots=SLOTS, max_seq_len=MAX_SEQ,
                        cache_backend="paged", block_size=BLOCK_SIZE,
                        chunk_tokens=CHUNK, mesh=mesh)
    assert_cache_placement(mesh, eng._cache_state)
    say("mesh", "KV pool placement holds (K/V split on KV heads)")
    # one prompt length: the 32-layer chunk programs compile once each
    batch = prompts(cfg.vocab_size, lens=(PROMPT_LENS[0],) * SLOTS)
    serve_phase("mesh", eng, batch)
    in_use = [m["bytes_in_use"] for m in memory_report("mesh")]
    share = [b / sum(in_use) for b in in_use]
    assert all(abs(s - 1 / ways) <= 0.05 for s in share), \
        f"per-device bytes in use are not even: shares {share}"
    del eng, params

    cut = LM(depth_cut(cfg, CUT_LAYERS))
    p_one = init_params(cut, jax.random.PRNGKey(0))
    tokens = jnp.asarray(batch[0])[None]
    ref = jax.jit(lambda p, t: cut.forward(p, {"tokens": t})[0])(
        p_one, tokens).astype(jnp.float32)
    rules = serving_rules(mesh)
    p_mesh = init_params(cut, jax.random.PRNGKey(0), mesh)
    got = jax.jit(lambda p, t: cut.forward(p, {"tokens": t}, mesh=mesh,
                                           rules=rules)[0])(
        p_mesh, tokens).astype(jnp.float32)
    err = float(jnp.max(jnp.abs(got - ref)) / jnp.max(jnp.abs(ref)))
    say("compare", f"{cut.cfg.name} prefill logits, mesh {ways} vs one "
                   f"device: max |diff| / max |logit| = {err:.3e} "
                   f"(tolerance {LOGITS_RTOL})")
    assert err <= LOGITS_RTOL, "mesh prefill logits disagree"

    engines = [ServingEngine(cut, p_one, batch_slots=SLOTS,
                             max_seq_len=MAX_SEQ, cache_backend="paged",
                             block_size=BLOCK_SIZE, chunk_tokens=CHUNK,
                             mesh=m) for m in (None, mesh)]
    err, compared, first_diff = decode_logits_agree(*engines, batch)
    say("compare", f"{cut.cfg.name} decode-step logits, mesh {ways} vs one "
                   f"device, over {compared} steps with equal histories: "
                   f"max |diff| / max |logit| = {err:.3e} (tolerance "
                   f"{LOGITS_RTOL})")
    say("compare", f"first differing greedy token per stream (of "
                   f"{MAX_NEW}; None = equal throughout): {first_diff}")
    assert compared >= 1, "no decode step had equal histories on both sides"
    assert err <= LOGITS_RTOL, "mesh decode-step logits disagree"


def decode_logits_agree(single, meshed, batch):
    """Serve ``batch`` greedily on two engines (same model and params,
    without and with a mesh) in lockstep. After each step, a slot that
    decoded on both with equal token histories was fed the same inputs, so
    its fresh logits (``last``, from the cached-attention kernels) must
    agree. Returns (max |diff| / max |logit| over those steps, how many,
    each stream's first differing token index or None)."""
    for eng in (single, meshed):
        for p in batch:
            eng.submit(p, max_new_tokens=MAX_NEW)
    err, compared = 0.0, 0
    prev = np.zeros(SLOTS, np.int64)
    while single.pending or meshed.pending:
        single.step()
        meshed.step()
        a, b = single._state, meshed._state
        steps = np.asarray(a["steps"])
        assert np.array_equal(steps, np.asarray(b["steps"])), \
            "the two engines scheduled differently"
        out_a, out_b = np.asarray(a["out"]), np.asarray(b["out"])
        for s in np.flatnonzero(steps > prev):
            if np.array_equal(out_a[s, :steps[s]], out_b[s, :steps[s]]):
                la = np.asarray(a["last"][s])
                lb = np.asarray(b["last"][s])
                err = max(err, float(np.max(np.abs(la - lb))
                                     / np.max(np.abs(la))))
                compared += 1
        prev = steps
    outs = [single.take_done(), meshed.take_done()]
    first_diff = []
    for rid in sorted(outs[0]):
        x, y = outs[0][rid].output, outs[1][rid].output
        assert outs[0][rid].status == outs[1][rid].status == "done"
        diff = np.flatnonzero(x != y)
        first_diff.append(int(diff[0]) if diff.size else None)
    return err, compared, first_diff


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mesh", type=int, choices=(4,), default=None,
                    help="run only the four-chip tensor-parallel phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); refusing to "
              f"run anywhere else", file=sys.stderr)
        print(json.dumps({"ok": False, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}))
        return 1
    say("device", f"{dev.device_kind} x{len(jax.devices())}, jax "
                  f"{jax.__version__}, compile cache {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.mesh:
        mesh_phase(get_config("starcoder2-7b"), args.mesh)
    else:
        single_chip(get_config("smollm-135m"))
    say("done", f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
