"""Dry-run machinery on a small forced-device-count mesh (subprocess: the
512-device production sweep lives in results/dryrun; here we prove the
pipeline end-to-end with 8 fake devices so CI stays fast)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as PS

    from repro import sharding as sh
    from repro.configs import get_config
    from repro.launch import sharding_rules as sr
    from repro.launch.dryrun import collective_bytes
    from repro.launch.specs import make_step_fn
    from repro.configs.shapes import InputShape
    from repro.models.model import LM

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = get_config("smollm-135m").reduced()
    lm = LM(cfg, kv_chunk=16)
    shape = InputShape("t", seq_len=32, global_batch=8, mode="train")
    step, abstract_in, axes = make_step_fn(lm, shape)
    pspec = sr.param_pspecs(mesh, abstract_in[0], axes, "train")
    named = lambda t: jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, PS))
    with mesh:
        with sh.use_rules(mesh, sr.act_rules(mesh, "train")):
            jitted = jax.jit(step, in_shardings=(
                named(pspec),
                named(sr.opt_pspecs(mesh, pspec, abstract_in[1])),
                named(sr.batch_pspecs(mesh, abstract_in[2]))))
            lowered = jitted.lower(*abstract_in)
        compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    print(json.dumps({
        "flops": cost.get("flops"),
        "collective_bytes": sum(v["bytes"] for v in coll.values()),
        "mem": compiled.memory_analysis().temp_size_in_bytes,
    }))
""")


@pytest.mark.slow
def test_dryrun_pipeline_on_8_fake_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=420)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["flops"] > 0
    assert rec["collective_bytes"] > 0        # FSDP gathers + grad reduces
    assert rec["mem"] > 0


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes
    hlo = """
      %ag = bf16[64,128]{1,0} all-gather(%x), replica_groups={{0,1}}
      %ar.1 = f32[32]{0} all-reduce(%y), to_apply=%add
      %nothing = f32[2]{0} add(%a, %b)
      %a2a = (f32[8,8]{1,0}) all-to-all(%z)
    """
    out = collective_bytes(hlo)
    assert out["all-gather"]["count"] == 1
    assert out["all-gather"]["bytes"] == 64 * 128 * 2
    assert out["all-reduce"]["bytes"] == 32 * 4
    assert out["all-to-all"]["count"] == 1


def test_production_dryrun_results_if_present():
    """When the 512-device sweep has been run, every (arch x shape x mesh)
    record must exist and carry positive flops."""
    d = os.path.join(ROOT, "results", "dryrun")
    if not os.path.isdir(d) or len(os.listdir(d)) < 80:
        pytest.skip("production dry-run sweep not complete")
    from repro.configs import ASSIGNED_ARCHS
    from repro.configs.shapes import INPUT_SHAPES
    for arch in ASSIGNED_ARCHS:
        for shape in INPUT_SHAPES:
            for mesh in ("pod16x16", "pod2x16x16"):
                path = os.path.join(d, f"{arch}__{shape}__{mesh}.json")
                assert os.path.exists(path), path
                with open(path) as f:
                    rec = json.load(f)
                assert rec["cost"].get("flops", 0) > 0, path
