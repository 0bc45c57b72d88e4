"""Mesh-aware serving: tensor-parallel decode on forced host devices.

The exactness matrix runs in a subprocess (``xla_force_host_platform_
device_count`` must be set before ``import jax``; conftest already imported
it): ring/paged × greedy/sampled × multi-step × speculative engines on a
4-device mesh must stream token-for-token identically to the single-device
engine on the same trace — faults included — and snapshots taken on a mesh
must restore token-exact both onto a mesh and onto ``mesh=None``. The
decode kernels, forced on in interpret mode, must match too when they run
per KV-head shard inside ``shard_map``.

A second, smaller subprocess pins placement: ``init_params`` on a mesh
equals the single-device init, and a mesh whose ``model`` axis does not
divide the KV heads is refused.

The in-process test pins the other half of the contract: ``mesh=None``
compiles exactly the warm executable set (no new variants post-warm), so
the mesh seam costs the single-device path nothing.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig, dense_stages
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import LM
    from repro.serving import FaultPlan, ServingEngine

    def model(layers=2, seed=0):
        cfg = ModelConfig(
            name="shard-test", family="dense", source="test",
            num_layers=layers, d_model=64, num_heads=4, num_kv_heads=4,
            head_dim=16, d_ff=128, vocab_size=256,
            stages=dense_stages(layers), param_dtype="float32")
        lm = LM(cfg, kv_chunk=32)
        params, _ = lm.init(jax.random.PRNGKey(seed))
        return lm, params

    LM_T, P_T = model()
    LM_D, P_D = model(layers=1, seed=1)
    MESH = make_host_mesh(model=4)
    RNG = np.random.default_rng(0)
    REQS = [(RNG.integers(0, 256, size=4 + i % 7), 5 + i % 4,
             0.0 if i % 2 else 0.8) for i in range(6)]

    def mk(mesh, backend, *, spec=False, k=1, faults=None):
        kw = dict(draft_model=LM_D, draft_params=P_D,
                  speculative_tokens=3) if spec else {}
        return ServingEngine(LM_T, P_T, batch_slots=3, max_seq_len=64,
                             cache_backend=backend, mesh=mesh, seed=0,
                             max_decode_steps=k, fault_plan=faults, **kw)

    def run(eng):
        ids = [eng.submit(p, max_new_tokens=m, temperature=t)
               for p, m, t in REQS]
        done = eng.run()
        eng.assert_invariants()
        return {i: done[i].output.tolist() for i in ids
                if done[i].status == "done"}

    results = {}
    # exactness matrix: backends x sampling x decode horizon
    for backend in ("ring", "paged"):
        for k in (1, 4):
            key = f"{backend}_k{k}"
            results[key] = run(mk(None, backend, k=k)) == \\
                run(mk(MESH, backend, k=k))
    # speculative (draft + target both on the mesh)
    results["speculative"] = run(mk(None, "paged", spec=True)) == \\
        run(mk(MESH, "paged", spec=True))
    # faults: same seeded plan both sides; survivors must match
    results["faults"] = \\
        run(mk(None, "paged", faults=FaultPlan(seed=3, step=[1],
                                               swap_out=[0]))) == \\
        run(mk(MESH, "paged", faults=FaultPlan(seed=3, step=[1],
                                               swap_out=[0])))

    # snapshot-on-mesh -> restore-on-mesh and restore-on-mesh=None
    base = run(mk(None, "paged"))
    donor = mk(MESH, "paged")
    for p, m, t in REQS:
        donor.submit(p, max_new_tokens=m, temperature=t)
    for _ in range(4):
        donor.step()
    snap = donor.snapshot()
    for name, tmesh in (("restore_on_mesh", MESH),
                        ("restore_on_none", None)):
        cold = mk(tmesh, "paged")
        cold.restore(snap)
        done = cold.run()
        cold.assert_invariants()
        out = {r.request_id: r.output.tolist() for r in done.values()}
        results[name] = out == base

    # per-device accounting: sharded pool pays 1/4 of the K/V bytes
    eng = mk(MESH, "paged")
    kv = eng.backend
    results["hbm_per_device_shrinks"] = (
        kv.kv_shards == 4
        and kv.hbm_bytes_per_device() < kv.hbm_bytes()
        and kv.block_bytes_per_device() * kv.num_blocks
        == kv.hbm_bytes_per_device())

    # on a TPU the decode kernels run per KV-head shard inside shard_map:
    # take that path here (kernels in interpret mode), held to mesh=None
    from repro.kernels import decode_attention as da
    from repro.kernels import ops
    ops._on_tpu = lambda: True
    ops._da_kernel = lambda *a, interpret=None, **k: \
        da.decode_attention(*a, interpret=True, **k)
    ops._pda_kernel = lambda *a, interpret=None, **k: \
        da.paged_decode_attention(*a, interpret=True, **k)
    for backend in ("ring", "paged"):
        results[f"kernel_per_shard_{backend}"] = \
            run(mk(None, backend)) == run(mk(MESH, backend))
    print(json.dumps(results))
""")


PLACEMENT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import json
    import jax
    import numpy as np

    from repro.configs.base import ModelConfig, dense_stages
    from repro.launch.mesh import make_host_mesh
    from repro.models.model import LM
    from repro.serving import ServingEngine, init_params
    from repro.serving.sharding import param_shardings

    def model(kv):
        return LM(ModelConfig(
            name="place-test", family="dense", source="test", num_layers=2,
            d_model=64, num_heads=4, num_kv_heads=kv, head_dim=16,
            d_ff=128, vocab_size=256, stages=dense_stages(2),
            param_dtype="bfloat16"), kv_chunk=32)

    MESH = make_host_mesh(model=4)
    RNG = jax.random.PRNGKey(0)
    lm = model(4)
    single = jax.tree.leaves(init_params(lm, RNG))
    sharded = jax.tree.leaves(init_params(lm, RNG, MESH))
    want = jax.tree.leaves(param_shardings(MESH, lm))
    results = {
        "init_equal": all(np.array_equal(np.asarray(a), np.asarray(b))
                          for a, b in zip(single, sharded)),
        "init_placed": len(sharded) == len(want) and all(
            x.sharding.is_equivalent_to(w, x.ndim)
            for x, w in zip(sharded, want)),
        "init_split": any(x.sharding.shard_shape(x.shape) != x.shape
                          for x in sharded),
    }
    # 2 KV heads on a 4-way axis: the engine refuses the mesh
    lm2 = model(2)
    p2 = init_params(lm2, RNG)
    for backend in ("ring", "paged"):
        try:
            ServingEngine(lm2, p2, batch_slots=2, max_seq_len=32,
                          cache_backend=backend, mesh=MESH)
            results[f"refuses_{backend}"] = False
        except ValueError as e:
            results[f"refuses_{backend}"] = "does not divide" in str(e)
    print(json.dumps(results))
""")


def _run_forced_devices(script: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_serving_exactness_matrix():
    rec = _run_forced_devices(SCRIPT)
    assert all(rec.values()), rec


@pytest.fixture(scope="module")
def placement():
    return _run_forced_devices(PLACEMENT)


def test_init_params_mesh_matches_single_device(placement):
    """One jitted init: on a mesh its leaves land in their decode
    shardings (some split) and equal the no-mesh init bit for bit."""
    assert placement["init_equal"], placement
    assert placement["init_placed"], placement
    assert placement["init_split"], placement


@pytest.mark.parametrize("backend", ["ring", "paged"])
def test_mesh_refuses_indivisible_kv_heads(placement, backend):
    """A model axis that does not divide the KV heads is refused at
    construction, not served from pools replicated on every device."""
    assert placement[f"refuses_{backend}"], placement


def test_mesh_none_executable_set_unchanged():
    """mesh=None must compile exactly today's executable set: the mesh
    seam adds no jit arguments (mesh/rules ride as trace-time closure
    state), so warm_compile still closes the compile set and a full
    drain adds zero variants."""
    import jax
    import numpy as np
    from repro.configs.base import ModelConfig, dense_stages
    from repro.models.model import LM
    from repro.serving import ServingEngine

    cfg = ModelConfig(
        name="shard-nomesh", family="dense", source="test", num_layers=1,
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, d_ff=64,
        vocab_size=128, stages=dense_stages(1), param_dtype="float32")
    lm = LM(cfg, kv_chunk=16)
    params, _ = lm.init(jax.random.PRNGKey(0))
    eng = ServingEngine(lm, params, batch_slots=2, max_seq_len=32,
                        min_bucket=8, cache_backend="paged", block_size=8,
                        max_decode_steps=4)
    assert eng.mesh is None and eng.rules is None
    eng.warm_compile()
    assert eng.warm_compile_s is not None and eng.warm_compile_s > 0
    # admission and decode executables close at warm_compile
    counts = {name: getattr(eng, name)._cache_size()
              for name in ("_admit_fn", "_step_fn", "_scan_fn")}
    assert counts["_admit_fn"] == len(eng.buckets)
    rng = np.random.default_rng(0)
    for i in range(3):
        eng.submit(rng.integers(0, 128, size=4 + i), max_new_tokens=4,
                   temperature=0.5 * i)
    done = eng.run()
    assert all(r.status == "done" for r in done.values())
    for name, before in counts.items():
        assert getattr(eng, name)._cache_size() == before, name
    # metrics carries the satellite fields
    m = eng.metrics()
    assert m["warm_compile_s"] == eng.warm_compile_s
    assert m["mesh_devices"] == 1
    # per-device accounting degenerates to the global numbers off-mesh
    assert eng.hbm_bytes_per_device() == eng.hbm_bytes()
    eng.assert_invariants()


def test_slots_for_hbm_scaling():
    from repro.serving import slots_for_hbm
    slot = 1000
    per_dev = 8 * slot
    assert slots_for_hbm(per_dev, slot, mesh_size=1) == 8
    assert slots_for_hbm(per_dev, slot, mesh_size=2) == 16
    assert slots_for_hbm(per_dev, slot, mesh_size=4) == 32
    assert slots_for_hbm(per_dev, slot, mesh_size=4, cap=20) == 20
