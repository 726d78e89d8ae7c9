"""Differential tests for the fused residency-group executor
(DESIGN.md §8): the megakernel must be a *pure perf transform* — fused
== per-layer == ref forward (bitwise for the Pallas pair, 1e-5 vs the
XLA oracle) and gradients, across a topology x dataflow x residency
grid — plus the depth-1 fallback, packed-params rejection and shape
validation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fuse_plan import FusedGroupPlan, build_group
from repro.core.model import ConvLayer
from repro.core.netplan import network_layers, scale_layers
from repro.kernels.trim_conv2d_fused import (fused_group_apply,
                                             reference_chain)
from repro.models import layers as mlayers
from repro.models.base import init_params


def _close(a, b, tol=1e-5):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = float(np.abs(b).max()) + 1e-9
    assert float(np.abs(a - b).max()) / scale < tol


# hand-rolled chains exercising the geometry corners: 'same' stacks with
# an even pool, a strided-valid head with an overlapping (odd) pool and
# a pointwise tail, and a pool-free stack.  Bitwise fused == per-layer
# needs a backend whose dot rows do not depend on the matmul's row
# count: XLA:CPU (interpret mode) picks shape-dependent dot algorithms
# for narrow outputs, so the chains that fuse past their first conv
# emit 64 channels, where its results are row-stable.  Every conv takes
# more folded taps than the fold rule admits (K*K*C_in > 128, > 256 at
# stride 2): a conv whose taps fold runs per layer, folded, and never
# fuses (DESIGN.md §13).
def _chain_same():
    return [ConvLayer("c0", 12, 16, 16, 3, 1, 1),
            ConvLayer("c1", 12, 16, 16, 3, 1, 1),   # pool 2/2 -> 6
            ConvLayer("c2", 6, 16, 8, 3, 1, 1)]


def _chain_strided():
    return [ConvLayer("s0", 17, 16, 64, 5, 2, 0),   # valid -> 7, pool 2/3
            ConvLayer("s1", 3, 64, 64, 1, 1, 0),    # pointwise
            ConvLayer("s2", 3, 64, 64, 3, 1, 1)]


def _chain_nopool():
    return [ConvLayer("p0", 9, 16, 64, 3, 1, 1),
            ConvLayer("p1", 9, 64, 64, 3, 1, 1),
            ConvLayer("p2", 9, 64, 64, 3, 1, 1)]


TOPOLOGIES = {
    "same_pool": _chain_same,
    "strided_valid": _chain_strided,
    "nopool": _chain_nopool,
    "alexnet_x4": lambda: scale_layers(network_layers("alexnet"), 4),
}


def _setup(topo_name, n=2, seed=0):
    topo = TOPOLOGIES[topo_name]()
    params = init_params(mlayers.cnn_params_from_layers(topo),
                         jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(
        (n, topo[0].ifmap, topo[0].ifmap, topo[0].in_channels)),
        jnp.float32)
    return topo, params, x


# ---------------------------------------------------------------------------
# fused_group_apply vs reference_chain (single group, all strip heights)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo_name", ["same_pool", "strided_valid"])
def test_group_apply_bitmatches_reference(topo_name):
    topo, params, x = _setup(topo_name)
    weights = [params[f"conv{i}"]["w"] for i in range(len(topo))]
    biases = [params[f"conv{i}"]["b"] for i in range(len(topo))]
    ref = None
    h_last = build_group(topo, 0, n=x.shape[0]).last.h_pool
    for t in sorted({1, 2, h_last}):
        g = build_group(topo, 0, n=x.shape[0], strip_rows=t)
        y = fused_group_apply(x, weights, biases, group=g)
        if ref is None:
            ref = reference_chain(x, weights, biases, group=g)
            # identical tap order + epilogue: bitwise vs the per-layer
            # Pallas chain, 1e-5 vs the XLA oracle
            assert jnp.array_equal(y, ref), f"strip_rows={t}"
            oracle = reference_chain(x, weights, biases, group=g,
                                     impl="ref")
            _close(y, oracle)
        else:
            assert jnp.array_equal(y, ref), f"strip_rows={t}"


def test_group_apply_gradients_match_reference():
    topo, params, x = _setup("same_pool")
    weights = tuple(params[f"conv{i}"]["w"] for i in range(len(topo)))
    biases = tuple(params[f"conv{i}"]["b"] for i in range(len(topo)))
    g = build_group(topo, 0, n=x.shape[0], strip_rows=2)

    def loss_fused(x_, ws, bs):
        return (fused_group_apply(x_, list(ws), list(bs),
                                  group=g) ** 2).sum()

    def loss_ref(x_, ws, bs):
        return (reference_chain(x_, ws, bs, group=g) ** 2).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, weights, biases)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, weights, biases)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gr)):
        _close(a, b)


# ---------------------------------------------------------------------------
# whole-network: fused == per-layer == ref across the grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topo_name", sorted(TOPOLOGIES))
@pytest.mark.parametrize("dataflow", ["carry", "halo"])
@pytest.mark.parametrize("residency", ["always", "auto", "never"])
def test_network_fused_matches_per_layer(topo_name, dataflow, residency):
    topo, params, x = _setup(topo_name)
    plan = FusedGroupPlan.build(topo, n=x.shape[0], residency=residency,
                                dataflow=dataflow)
    ref = mlayers.cnn_apply_from_layers(params, topo, x)
    fus = mlayers.cnn_apply_from_layers(params, topo, x, fuse_plan=plan)
    assert jnp.array_equal(ref, fus), \
        (topo_name, dataflow, residency,
         [(g.start, g.depth) for g in plan.groups])


@pytest.mark.parametrize("topo_name", ["same_pool", "strided_valid"])
def test_network_fused_matches_xla_oracle(topo_name):
    topo, params, x = _setup(topo_name)
    plan = FusedGroupPlan.build(topo, n=x.shape[0], residency="always")
    assert any(g.fused for g in plan.groups), "grid point never fused"
    fus = mlayers.cnn_apply_from_layers(params, topo, x, fuse_plan=plan)
    oracle = mlayers.cnn_apply_from_layers(params, topo, x, impl="ref")
    _close(fus, oracle)


def test_network_fused_gradients_match_per_layer():
    topo, params, x = _setup("same_pool")
    plan = FusedGroupPlan.build(topo, n=x.shape[0], residency="always")
    assert any(g.fused for g in plan.groups)

    gf = jax.grad(lambda p: (mlayers.cnn_apply_from_layers(
        p, topo, x, fuse_plan=plan) ** 2).sum())(params)
    gr = jax.grad(lambda p: (mlayers.cnn_apply_from_layers(
        p, topo, x) ** 2).sum())(params)
    for a, b in zip(jax.tree_util.tree_leaves(gf),
                    jax.tree_util.tree_leaves(gr)):
        _close(a, b)


# ---------------------------------------------------------------------------
# depth-1 fallback + API validation
# ---------------------------------------------------------------------------

def test_depth1_plan_is_per_layer(monkeypatch):
    """max_depth=1 groups must run the ordinary per-layer engine — the
    megakernel is never invoked and outputs are identical."""
    import repro.models.layers as mod
    topo, params, x = _setup("same_pool")
    plan = FusedGroupPlan.build(topo, n=x.shape[0], max_depth=1)
    assert all(not g.fused for g in plan.groups)
    assert plan.executed_hbm_bytes()["total"] == plan.never_hbm_bytes()

    calls = []
    import repro.kernels.trim_conv2d_fused as fmod
    real = fmod.fused_group_apply
    monkeypatch.setattr(fmod, "fused_group_apply",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    ref = mod.cnn_apply_from_layers(params, topo, x)
    fus = mod.cnn_apply_from_layers(params, topo, x, fuse_plan=plan)
    assert not calls, "depth-1 group dispatched the megakernel"
    assert jnp.array_equal(ref, fus)


def test_fused_rejects_packed_params():
    topo, params, x = _setup("same_pool")
    packed = mlayers.cnn_pack_params(params, topo, n=x.shape[0])
    plan = FusedGroupPlan.build(topo, n=x.shape[0], residency="always")
    assert any(g.fused for g in plan.groups)
    with pytest.raises(ValueError, match="packed"):
        mlayers.cnn_apply_from_layers(packed, topo, x, fuse_plan=plan)


def test_fused_rejects_mesh():
    topo, params, x = _setup("same_pool")
    with pytest.raises(ValueError, match="single-device"):
        mlayers.cnn_apply_from_layers(params, topo, x, fused=True,
                                      rules={"batch": "data"})


def test_group_apply_shape_validation():
    topo, params, x = _setup("same_pool")
    weights = [params[f"conv{i}"]["w"] for i in range(len(topo))]
    biases = [params[f"conv{i}"]["b"] for i in range(len(topo))]
    g = build_group(topo, 0, n=x.shape[0])
    with pytest.raises(ValueError, match="weights"):
        fused_group_apply(x, weights[:-1], biases[:-1], group=g)
    with pytest.raises(ValueError, match="stage-0"):
        fused_group_apply(x[:, :-1], weights, biases, group=g)
    bad = list(weights)
    bad[1] = jnp.zeros((5, 5) + weights[1].shape[2:], x.dtype)
    with pytest.raises(ValueError, match="weight"):
        fused_group_apply(x, bad, biases, group=g)


def test_group_apply_none_biases():
    topo, params, x = _setup("nopool")
    weights = [params[f"conv{i}"]["w"] for i in range(len(topo))]
    zeros = [jnp.zeros_like(params[f"conv{i}"]["b"])
             for i in range(len(topo))]
    g = build_group(topo, 0, n=x.shape[0], strip_rows=3)
    y_none = fused_group_apply(x, weights, [None] * len(topo), group=g)
    y_zero = fused_group_apply(x, weights, zeros, group=g)
    assert jnp.array_equal(y_none, y_zero)
