"""Logical-axis sharding resolution: best-effort divisibility, axis-conflict
handling, mesh-absence handling (property-based)."""
import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    DEFAULT_RULES,
    RULE_PROFILES,
    ShardingRules,
    best_effort_spec,
    is_axes_tuple,
    logical_to_sharding,
    shard_constraint,
    tree_shardings,
)


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_host_mesh

    return make_host_mesh()


class FakeMesh:
    """Shape-only stand-in so properties can exercise many mesh shapes
    without building device meshes."""

    def __init__(self, shape: dict):
        self.shape = shape


def test_basic_resolution():
    m = FakeMesh({"data": 16, "model": 16})
    spec = best_effort_spec((128, 256), ("embed", "heads"), m)
    assert spec == P("data", "model")


def test_indivisible_dropped():
    m = FakeMesh({"data": 16, "model": 16})
    # 8 kv heads cannot split 16 ways -> replicated
    spec = best_effort_spec((1024, 8, 128), ("embed", "kv_heads", "head_dim"), m)
    assert spec == P("data", None, None)


def test_tuple_rule_prefix():
    m = FakeMesh({"pod": 2, "data": 16, "model": 16})
    # batch -> ("pod", "data"): 4 rows divide pod(2) and pod*data(32)? 4 % 32
    # != 0, so only the "pod" prefix applies
    spec = best_effort_spec((4, 64), ("batch", None), m)
    assert spec == P("pod", None)
    spec = best_effort_spec((64, 64), ("batch", None), m)
    assert spec == P(("pod", "data"), None)


def test_absent_axis_dropped():
    m = FakeMesh({"data": 16, "model": 16})  # no "pod"
    spec = best_effort_spec((64,), ("batch",), m)
    assert spec == P(("data",)) or spec == P("data")


def test_axis_used_once():
    m = FakeMesh({"data": 4, "model": 4})
    # two dims both wanting "model": only the first gets it
    rules = ShardingRules(rules=(("a", "model"), ("b", "model")))
    spec = best_effort_spec((8, 8), ("a", "b"), m, rules)
    assert spec == P("model", None)


@settings(max_examples=100, deadline=None)
@given(
    dims=st.lists(st.integers(1, 512), min_size=1, max_size=4),
    data=st.sampled_from([1, 2, 4, 8, 16]),
    model=st.sampled_from([1, 2, 4, 8, 16]),
)
def test_always_valid_spec(dims, data, model):
    """Resolved spec always divides: product of assigned axis sizes divides
    the dim — for any shape and any mesh."""
    m = FakeMesh({"data": data, "model": model})
    names = ["embed", "heads", "vocab", "mlp"][: len(dims)]
    spec = best_effort_spec(tuple(dims), tuple(names), m)
    for dim, entry in zip(dims, tuple(spec)):
        if entry is None:
            continue
        axes = (entry,) if isinstance(entry, str) else entry
        prod = int(np.prod([m.shape[a] for a in axes]))
        assert dim % prod == 0


def test_override():
    r = DEFAULT_RULES.override(kv_seq=("data", "model"))
    assert r.get("kv_seq") == ("data", "model")
    assert r.get("heads") == DEFAULT_RULES.get("heads")
    r2 = DEFAULT_RULES.override(brand_new="model")
    assert r2.get("brand_new") == "model"


def test_rule_profiles_membership():
    for name in ("default", "fsdp", "tensor_parallel", "sequence_parallel",
                 "small_model"):
        assert name in RULE_PROFILES, name
        assert isinstance(RULE_PROFILES[name], ShardingRules)
    assert RULE_PROFILES["default"] is DEFAULT_RULES
    # small_model = replicated weights, full DP
    assert RULE_PROFILES["small_model"].get("embed") is None
    assert "model" in RULE_PROFILES["small_model"].get("batch")


def test_logical_to_sharding_no_mesh():
    # mesh=None -> None (jit treats unspecified as replicated); CPU paths
    # use the exact production code with no special-casing
    assert logical_to_sharding((8, 16), ("batch", "embed"), None) is None


def test_logical_to_sharding_real_mesh(mesh):
    sh = logical_to_sharding((8, 16), ("batch", None), mesh)
    assert isinstance(sh, jax.sharding.NamedSharding)
    assert sh.spec == P("data", None)
    scalar = logical_to_sharding((), (), mesh)
    assert scalar.spec == P()


def test_shard_constraint_noop_without_mesh():
    import jax.numpy as jnp

    x = jnp.arange(12.0).reshape(3, 4)
    assert shard_constraint(x, ("batch", "embed")) is x


def test_tree_shardings_and_leaf_predicate(mesh):
    import jax.numpy as jnp

    abs_tree = {"w": jax.ShapeDtypeStruct((8, 4), jnp.float32),
                "b": jax.ShapeDtypeStruct((4,), jnp.float32)}
    axes = {"w": ("embed", "mlp"), "b": (None,)}
    sh = tree_shardings(abs_tree, axes, mesh)
    assert set(sh) == {"w", "b"}
    assert all(isinstance(s, jax.sharding.NamedSharding) for s in sh.values())
    assert is_axes_tuple(("embed", None)) and is_axes_tuple(())
    assert not is_axes_tuple((1, 2))
