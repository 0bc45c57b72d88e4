"""Sharding-rule unit tests (mesh-shape logic, no 512 devices needed)."""
import jax
import pytest
from jax.sharding import PartitionSpec as PS

from repro import sharding as sh
from repro.models import param as P


class FakeMesh:
    """Only .shape is consulted by resolve()."""
    def __init__(self, **shape):
        self.shape = shape


RULES = {
    P.EMBED: ("data",),
    P.EMBED_OUT: ("data",),
    P.VOCAB: "model",
    P.HEADS: "model",
    P.MLP: "model",
    P.EXPERT: "model",
    P.STACK: None,
}


def test_resolve_divisibility_guard():
    mesh = FakeMesh(data=16, model=16)
    # 9 heads cannot shard 16 ways -> replicated on that dim
    spec = sh.resolve(RULES, (P.EMBED, P.HEADS, None), shape=(576, 9, 64),
                      mesh=mesh)
    assert spec == PS("data", None, None)
    spec = sh.resolve(RULES, (P.EMBED, P.HEADS, None), shape=(576, 32, 64),
                      mesh=mesh)
    assert spec == PS("data", "model", None)


def test_resolve_no_axis_reuse():
    mesh = FakeMesh(data=16, model=16)
    # deepseek expert weights: EXPERT wins 'model', MLP must not reuse it
    spec = sh.resolve(RULES, (P.EXPERT, P.EMBED, P.MLP),
                      shape=(256, 7168, 2048), mesh=mesh)
    assert spec == PS("model", "data", None)
    # mixtral: EXPERT not divisible -> MLP gets 'model'
    spec = sh.resolve(RULES, (P.EXPERT, P.EMBED, P.MLP),
                      shape=(8, 6144, 16384), mesh=mesh)
    assert spec == PS(None, "data", "model")


def test_resolve_multi_axis():
    mesh = FakeMesh(pod=2, data=16, model=16)
    rules = dict(RULES)
    rules[P.EMBED] = ("pod", "data")
    spec = sh.resolve(rules, (P.VOCAB, P.EMBED), shape=(49152, 576),
                      mesh=mesh)
    assert spec == PS("model", ("pod", "data"))
    # 576 % 32 == 0; a non-divisible dim drops the whole group
    spec = sh.resolve(rules, (P.VOCAB, P.EMBED), shape=(49152, 100),
                      mesh=mesh)
    assert spec == PS("model", None)


def test_decode_param_rules():
    from repro.launch.sharding_rules import param_rules
    mesh = FakeMesh(data=16, model=16)
    train = param_rules(mesh, "train")
    decode = param_rules(mesh, "decode")
    assert train[P.EMBED_OUT] == ("data",)
    assert decode[P.EMBED_OUT] is None
    assert decode[P.EXPERT] == ("data", "model")


def test_hint_noop_without_context():
    import jax.numpy as jnp
    x = jnp.ones((4, 8))
    assert sh.hint(x, (sh.BATCH, None)) is x


def test_hint_applies_constraint_under_mesh():
    import jax.numpy as jnp
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with sh.use_rules(mesh, {sh.BATCH: ("data",)}):
        y = sh.hint(jnp.ones((4, 8)), (sh.BATCH, None))
    assert y.shape == (4, 8)


def test_abstract_params_have_full_axis_coverage():
    """Every parameter leaf carries logical axes of matching rank."""
    from repro.configs import get_config
    from repro.models.model import LM
    lm = LM(get_config("mixtral-8x22b").reduced())
    params, axes = lm.abstract()
    flat_p = jax.tree.leaves(params)
    flat_a = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(flat_p) == len(flat_a)
    for leaf, ax in zip(flat_p, flat_a):
        assert len(ax) == leaf.ndim, (leaf.shape, ax)
