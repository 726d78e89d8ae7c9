"""Sharding-rule resolution properties (divisibility fallbacks, FSDP)."""

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.distributed import steps
from repro.launch.mesh import auto_mesh
from repro.distributed.sharding import make_rules
from repro.models.base import Param, resolve_spec, tree_bytes_per_dev


def _mesh():
    return auto_mesh((1, 1), ("data", "model"))


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_resolve_divisibility_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules()
    # kv_heads=2 cannot shard 16 ways -> replicated
    spec = resolve_spec((4096, 2, 128), ("embed", "kv_heads", "head_dim"),
                        mesh, rules)
    assert spec == P(None, None, None)
    # heads=32 shards fine
    spec = resolve_spec((4096, 32, 128), ("embed", "heads", "head_dim"),
                        mesh, rules)
    assert spec == P(None, "model", None)


def test_resolve_no_axis_reuse():
    """Two dims cannot both claim the same mesh axis (experts wins, mlp
    falls back to replication)."""
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules()
    spec = resolve_spec((128, 2048, 768), ("experts", "embed", "mlp"),
                        mesh, rules)
    assert spec == P("model", None, None)
    with_fsdp = make_rules(fsdp=True)
    spec = resolve_spec((128, 2048, 768), ("experts", "embed", "mlp"),
                        mesh, with_fsdp)
    assert spec == P("model", "data", None)


def test_fsdp_pod_composition():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    rules = make_rules(fsdp=True)
    spec = resolve_spec((16384, 53248), ("embed", "mlp"), mesh, rules)
    assert spec == P(("data", "pod"), "model")


def test_seq_override_takes_axis_from_kv():
    mesh = FakeMesh({"data": 16, "model": 16})
    rules = make_rules(**{"seq": "model"})
    spec = resolve_spec((126, 128, 32768, 8, 128),
                        ("layers", "batch", "seq", "kv_heads", None),
                        mesh, rules)
    assert spec[2] == "model"          # seq claimed model
    assert spec[3] is None             # kv falls back


def test_batch_shardings_divisibility():
    mesh = auto_mesh((1, 1), ("data", "model"))
    rules = make_rules()
    tree = {"tokens": jax.ShapeDtypeStruct((1, 8), jnp.int32),
            "big": jax.ShapeDtypeStruct((16, 8), jnp.int32)}
    sh = steps.batch_shardings(tree, mesh, rules)
    assert sh["tokens"].spec == P("data")   # 1 % 1 == 0 on the tiny mesh
    assert sh["big"].spec == P("data")


@settings(max_examples=20, deadline=None)
@given(size=st.integers(1, 64), extent=st.sampled_from([2, 4, 8, 16]))
def test_property_resolution_always_divides(size, extent):
    mesh = FakeMesh({"data": extent, "model": 16})
    rules = make_rules(fsdp=True)
    spec = resolve_spec((size,), ("embed",), mesh, rules)
    if spec[0] is not None:
        assert size % extent == 0


def test_tree_bytes_per_dev():
    mesh = FakeMesh({"data": 4, "model": 8})
    rules = make_rules(fsdp=True)
    tree = {"w": Param((64, 64), ("embed", "mlp"))}   # shards 4 x 8 = 32
    assert tree_bytes_per_dev(tree, mesh, rules, 2) == 64 * 64 * 2 / 32
