"""NetworkPlan subsystem tests (DESIGN.md §7): whole-network chaining of
the per-layer ConvPlans — exact reduction to the per-layer sums, golden
Ops/MAcc values for the paper networks, trim-vs-3dtrim ratio
monotonicity, residency semantics, the one-sweep network tuner, and the
end-to-end topology execution path."""

import math

import numpy as np
import pytest

from repro.core import (ConvPlan, NetworkPlan, autotune, network_layers,
                        scale_layers)
from repro.core.model import ConvLayer
from repro.core.netplan import _cached_knobs, infer_pools, pool_between
from repro.core.roofline import network_roofline

APPROX = dict(rel=1e-6)


# ---------------------------------------------------------------------------
# Reduction: the network is exactly the sum of its layers when nothing
# is kept resident (the acceptance invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["vgg16", "alexnet", "mobilenet"])
@pytest.mark.parametrize("mode", ["3dtrim", "trim"])
def test_reduces_to_per_layer_sum(net, mode):
    plan = NetworkPlan.build(net, residency="never", fold_pooling=False)
    agg = plan.hbm_bytes(mode)
    ref = dict(input=0, weights=0, output=0, total=0)
    for s in plan.steps:
        t = s.plan.hbm_bytes(mode)
        for k in ref:
            ref[k] += t[k]
    assert agg["input"] == ref["input"]
    assert agg["weights"] == ref["weights"]
    assert agg["output"] == ref["output"]
    assert agg["total"] == ref["total"]
    assert agg["halo"] == 0
    assert plan.macs == sum(s.plan.macs for s in plan.steps)


def test_sharded_network_reduces_at_one_shard():
    """spatial_shards=1 must match the unsharded plan exactly (halo=0);
    more shards add exactly the per-layer one-way halo bytes."""
    base = NetworkPlan.build("alexnet", residency="never",
                             fold_pooling=False)
    one = NetworkPlan.build("alexnet", residency="never",
                            fold_pooling=False, spatial_shards=1)
    assert one.hbm_bytes() == base.hbm_bytes()
    four = NetworkPlan.build("alexnet", residency="never",
                             fold_pooling=False, spatial_shards=4)
    t = four.hbm_bytes()
    assert t["halo"] == sum(s.plan.halo_bytes_oneway for s in four.steps)
    assert t["halo"] > 0
    # HBM terms are the global problem's — unchanged by sharding
    assert t["input"] == base.hbm_bytes()["input"]
    # Ops/MAcc never counts the wire bytes
    assert four.ops_per_macc("trim") == base.ops_per_macc("trim")


# ---------------------------------------------------------------------------
# Golden Ops/MAcc values — the first VGG-16 layers and the network
# ---------------------------------------------------------------------------

def test_vgg16_arch_golden_values():
    """The paper-accounting goldens (Fig. 6 / SV): per-layer Ops/MAcc of
    both configurations and the per-slice improvement for the first
    VGG-16 layers, plus the whole-network numbers."""
    arch = NetworkPlan.build("vgg16").arch_compare()
    rows = arch["layers"]
    for i in (0, 1):       # conv1 and conv2 share the geometry
        assert rows[i]["ops_per_macc"]["3d-trim"] == \
            pytest.approx(143.79366342939022, **APPROX)
        assert rows[i]["ops_per_macc"]["trim"] == \
            pytest.approx(113.07798488191933, **APPROX)
        assert rows[i]["improvement"] == \
            pytest.approx(3.3380358422225758, **APPROX)
    assert rows[2]["ops_per_macc"]["3d-trim"] == \
        pytest.approx(143.17818642993024, **APPROX)
    assert rows[2]["improvement"] == \
        pytest.approx(3.222106353043754, **APPROX)
    # whole network: the paper's claimed range (up to ~3.4x per layer)
    assert arch["ops_per_macc"]["3d-trim"] == \
        pytest.approx(134.70339520762815, **APPROX)
    assert arch["improvement"] == pytest.approx(3.301313156671815,
                                                **APPROX)
    assert 1.0 < arch["improvement"] < 3.6
    assert all(1.0 < r["improvement"] < 3.6 for r in rows)
    assert max(r["improvement"] for r in rows) == \
        pytest.approx(3.423274253731343, rel=1e-6)


def test_vgg16_plan_golden_values():
    """The execution-engine accounting goldens for the first layers.
    They follow the default strips: conv1 runs 21 strips of 11 rows,
    the tallest whose tile-padded VMEM set fits KERNEL_VMEM_BUDGET."""
    plan = NetworkPlan.build("vgg16")
    assert (plan.steps[0].plan.tile_h, plan.steps[0].plan.g_tiles) \
        == (11, 21)
    cmp = plan.compare()
    rows = cmp["layers"]
    assert rows[0]["ops_per_macc_3dtrim"] == \
        pytest.approx(898.926192031352, **APPROX)
    assert rows[0]["ops_per_macc_trim"] == \
        pytest.approx(788.1262032668866, **APPROX)
    assert rows[0]["improvement"] == \
        pytest.approx(1.140586606948462, **APPROX)
    assert cmp["improvement"] == pytest.approx(1.024112720032531,
                                               **APPROX)


# ---------------------------------------------------------------------------
# trim-vs-3dtrim ratio monotonicity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["vgg16", "alexnet", "mobilenet"])
def test_ratio_at_least_one_everywhere(net):
    """3dtrim never loses: per-layer and network ratios are >= 1, and
    the network ratio is bracketed by the per-layer extremes."""
    cmp = NetworkPlan.build(net).compare()
    ratios = [r["improvement"] for r in cmp["layers"]]
    assert all(r >= 1.0 for r in ratios)
    assert min(ratios) <= cmp["improvement"] <= max(ratios)


def test_ratio_monotone_in_strip_count():
    """Shrinking tile_h adds strips; every extra strip adds K-1 trim
    halo rows, so the 3dtrim/trim Ops/MAcc ratio must grow monotonically
    with the strip count for a fixed layer."""
    layer = network_layers("vgg16")[0]
    x = (1, layer.ifmap, layer.ifmap, layer.in_channels)
    w = (3, 3, layer.in_channels, layer.out_channels)
    prev_ratio, prev_tiles = None, None
    for tile_h in (224, 56, 14, 4):
        p = ConvPlan.build(x, w, pad=layer.padding, tile_h=tile_h)
        ratio = (p.arithmetic_intensity("3dtrim")
                 / p.arithmetic_intensity("trim"))
        if prev_ratio is not None:
            assert p.g_tiles > prev_tiles
            assert ratio > prev_ratio
        prev_ratio, prev_tiles = ratio, p.g_tiles


# ---------------------------------------------------------------------------
# Residency rules
# ---------------------------------------------------------------------------

def test_residency_semantics():
    plan = NetworkPlan.build("vgg16")       # auto
    steps = plan.steps
    # boundary flags are consistent: resident_in mirrors the producer
    assert not steps[0].resident_in
    for a, b in zip(steps, steps[1:]):
        assert b.resident_in == a.resident_out
    # the network output always leaves the accelerator
    assert not steps[-1].resident_out
    # auto keeps the small deep activations, spills the big early ones:
    # conv1's ofmap (224*224*64*4B > budget) must spill
    assert not steps[0].resident_out
    assert any(s.resident_out for s in steps)
    # a resident boundary bills neither the output nor the next input
    for a, b in zip(steps, steps[1:]):
        if a.resident_out:
            assert a.hbm_bytes()["output"] == 0
            assert b.hbm_bytes("trim")["input"] == 0
    # residency can only reduce traffic
    never = NetworkPlan.build("vgg16", residency="never")
    always = NetworkPlan.build("vgg16", residency="always")
    assert plan.hbm_bytes()["total"] <= never.hbm_bytes()["total"]
    assert always.hbm_bytes()["total"] <= plan.hbm_bytes()["total"]
    # and therefore only increase Ops/MAcc
    assert plan.ops_per_macc("trim") >= never.ops_per_macc("trim")
    # OPs are invariant under residency
    assert plan.ops == never.ops == always.ops


def test_pool_inference():
    vgg = network_layers("vgg16")
    assert pool_between(vgg[1], vgg[2]) == (2, 2)      # VGG 2x2/s2
    alex = network_layers("alexnet")
    assert pool_between(alex[0], alex[1]) == (2, 3)    # AlexNet 3x3/s2
    assert infer_pools(vgg)[-1] == (1, 1)
    # pooled output feeds the next layer exactly
    plan = NetworkPlan.build("alexnet")
    for a, b in zip(plan.steps, plan.steps[1:]):
        assert a.out_size == b.layer.ifmap


def test_sub2x_boundary_is_a_stride1_pool():
    """A sub-2x spatial boundary (5 -> 3) resolves to a genuine
    stride-1 overlapping pool (3x3/s1) — planned and executed
    consistently, not silently skipped."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers
    from repro.models.base import init_params
    topo = [ConvLayer("c1", 7, 3, 4, kernel=3, padding=0),   # out 5
            ConvLayer("c2", 3, 4, 6, kernel=3, padding=1)]
    assert pool_between(topo[0], topo[1]) == (1, 3)
    plan = NetworkPlan.build(topo)
    assert plan.steps[0].out_size == 3 == plan.steps[1].layer.ifmap
    p = init_params(layers.cnn_params_from_layers(topo),
                    jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (1, 7, 7, 3)), jnp.float32)
    y_ref = layers.cnn_apply_from_layers(p, topo, x, impl="ref")
    y_pal = layers.cnn_apply_from_layers(p, topo, x, impl="pallas")
    assert y_ref.shape == (1, 3, 3, 6)
    np.testing.assert_allclose(y_pal, y_ref, atol=1e-4)


def test_scale_layers_grouped_channels_stay_valid():
    """Scaled grouped layers must keep groups | cin and groups | cout —
    including depthwise multipliers and non-depthwise groups."""
    topo = [ConvLayer("pw0", 16, 3, 24, kernel=1),
            ConvLayer("dw1", 16, 24, 48, kernel=3, padding=1,
                      groups=24),                       # multiplier 2
            ConvLayer("pw1", 16, 48, 64, kernel=1)]
    scaled = scale_layers(topo, 5)
    NetworkPlan.build(scaled)          # ConvPlan validates divisibility
    for l in scaled:
        assert l.in_channels % l.groups == 0
        assert l.out_channels % l.groups == 0
    dw = scaled[1]
    assert dw.groups == dw.in_channels          # still depthwise


def test_build_rejects_broken_topologies():
    with pytest.raises(ValueError, match="unknown network"):
        NetworkPlan.build("resnet50")
    bad = [ConvLayer("a", 16, 3, 8, kernel=3, padding=1),
           ConvLayer("b", 16, 4, 8, kernel=3, padding=1)]   # 8 != 4
    with pytest.raises(ValueError, match="channels"):
        NetworkPlan.build(bad)
    with pytest.raises(ValueError, match="residency"):
        NetworkPlan.build("vgg16", residency="sometimes")


# ---------------------------------------------------------------------------
# Roofline aggregation
# ---------------------------------------------------------------------------

def test_network_roofline_sums_steps():
    plan = NetworkPlan.build("alexnet", spatial_shards=2)
    terms = network_roofline("alexnet", plan)
    assert terms.flops_per_dev == sum(float(s.plan.flops)
                                      for s in plan.steps)
    assert terms.hbm_bytes_per_dev == \
        pytest.approx(sum(float(s.hbm_bytes()["total"])
                          for s in plan.steps))
    assert terms.coll_bytes_per_dev == \
        pytest.approx(float(plan.hbm_bytes()["halo"]))
    assert terms.step_time_s > 0


# ---------------------------------------------------------------------------
# tune_network: one sweep covers the topology
# ---------------------------------------------------------------------------

def test_tune_network_sweep_and_consumption(tmp_path):
    topo = [ConvLayer("c1", 12, 3, 16, kernel=3, padding=1),
            ConvLayer("c2", 12, 16, 16, kernel=3, padding=1),  # repeat ↓
            ConvLayer("c3", 12, 16, 16, kernel=3, padding=1),
            ConvLayer("big", 12, 16, 16, kernel=9, padding=4)]
    recs = autotune.tune_network(topo)
    assert set(recs) == {"c1", "c2", "c3", "big"}
    # K=9 > MAX_NATIVE_K runs the kernel-tiled path: no cache record
    assert "skipped" in recs["big"]
    assert recs["c1"]["dataflow"] in ("carry", "halo")
    # identical problems are tuned once and share the record verbatim
    assert recs["c2"]["key"] == recs["c3"]["key"]
    assert recs["c2"] is recs["c3"]
    assert recs["c1"]["key"] != recs["c2"]["key"]
    # the records land where ops.conv2d looks them up (kernel-seen
    # shape; c1's 27 lanes of taps fold into a 1x1, DESIGN.md §13)
    knobs = autotune.knobs_for((1, 12, 12, 27), (1, 1, 27, 16), stride=1,
                               pad=0)
    assert knobs is not None
    assert knobs["tile_h"] == recs["c1"]["tile_h"]
    knobs = autotune.knobs_for((1, 14, 14, 16), (3, 3, 16, 16), stride=1,
                               pad=0)
    assert knobs is not None
    assert knobs["tile_h"] == recs["c2"]["tile_h"]
    # ... and where NetworkPlan(use_autotune_cache=True) looks too: it
    # plans the layers as stated, so it reads c2's record (an mxu route)
    # and none for c1, whose folded record describes another problem
    plan = NetworkPlan.build(topo[:3], use_autotune_cache=True)
    assert plan.steps[1].plan.dataflow == recs["c2"]["dataflow"]
    assert plan.steps[1].plan.tile_h == recs["c2"]["tile_h"]
    knobs = dict(n=1, dtype="float32", backend=None, batch_shards=1,
                 spatial_shards=1)
    assert _cached_knobs(topo[0], **knobs) is None
    assert _cached_knobs(topo[1], **knobs)["tile_h"] == recs["c2"]["tile_h"]


def test_tune_network_sharded_namespace():
    topo = [ConvLayer("c1", 12, 3, 4, kernel=3, padding=1)]
    rec = autotune.tune_network(topo, spatial_shards=2)["c1"]
    assert rec["key"].startswith("conv2d_shard:2:b1x2:")
    # the sharded record must not leak into the single-device lookup
    assert autotune.knobs_for((1, 14, 14, 3), (3, 3, 3, 4), stride=1,
                              pad=0) is None
    assert autotune.sharded_knobs_for((1, 14, 14, 3), (3, 3, 3, 4),
                                      spatial_shards=2, stride=1,
                                      pad=0) is not None


# ---------------------------------------------------------------------------
# End-to-end topology execution (the engine the examples run)
# ---------------------------------------------------------------------------

def test_topology_execution_matches_ref():
    """Tune -> pack -> run a small chained topology (VGG-style and
    AlexNet-style pooling boundaries included) on the Pallas path and
    lock it against the pure-jnp reference through the same apply."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers
    from repro.models.base import init_params
    topo = [ConvLayer("c1", 16, 3, 8, kernel=3, padding=1),
            ConvLayer("c2", 16, 8, 8, kernel=3, padding=1),   # pool 2x2
            ConvLayer("c3", 8, 8, 12, kernel=3, padding=1)]
    autotune.tune_network(topo, n=2)
    p = init_params(layers.cnn_params_from_layers(topo, n_classes=5),
                    jax.random.PRNGKey(0))
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (2, 16, 16, 3)), jnp.float32)
    y_ref = layers.cnn_apply_from_layers(p, topo, x, impl="ref")
    y_pal = layers.cnn_apply_from_layers(p, topo, x, impl="pallas")
    pk = layers.cnn_pack_params(p, topo, n=2)
    y_pck = layers.cnn_apply_from_layers(pk, topo, x)
    assert y_ref.shape == (2, 5)
    np.testing.assert_allclose(y_pal, y_ref, atol=1e-4)
    np.testing.assert_allclose(y_pck, y_ref, atol=1e-4)


def test_topology_execution_overlapping_pool():
    """An AlexNet-style boundary (stride-2 conv, overlapping 3x3/s2
    pool) through the kernel path."""
    import jax
    import jax.numpy as jnp
    from repro.models import layers
    from repro.models.base import init_params
    topo = [ConvLayer("a1", 15, 3, 4, kernel=3, stride=2, padding=0),
            ConvLayer("a2", 3, 4, 6, kernel=3, padding=1)]
    assert infer_pools(topo)[0] == (2, 3)
    p = init_params(layers.cnn_params_from_layers(topo),
                    jax.random.PRNGKey(1))
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (1, 15, 15, 3)), jnp.float32)
    y_ref = layers.cnn_apply_from_layers(p, topo, x, impl="ref")
    y_pal = layers.cnn_apply_from_layers(p, topo, x, impl="pallas")
    assert y_ref.shape == (1, 3, 3, 6)
    np.testing.assert_allclose(y_pal, y_ref, atol=1e-4)


def test_non_same_equivalent_padding_fails_loudly():
    """The executor runs the symmetric padding a layer states, so a
    padding that XLA's 'same' would not reproduce (K=5 with pad=1:
    'same' would pad 2) now runs as stated; a topology whose padding
    the execution path cannot run at all (a pad of K or more) must
    raise — in the tuner, the pack path and the apply path — instead
    of silently executing a different network than NetworkPlan
    bills."""
    import jax
    import jax.numpy as jnp
    from repro.core.netplan import layer_kernel_problem
    from repro.models import layers
    from repro.models.base import init_params
    odd = ConvLayer("odd", 16, 3, 8, kernel=5, padding=1)
    assert layer_kernel_problem(odd)[0] == (1, 18, 18, 3)
    bad = ConvLayer("odd", 16, 3, 8, kernel=3, padding=3)
    with pytest.raises(ValueError, match="symmetric pads 0 <= p < K"):
        layer_kernel_problem(bad)
    with pytest.raises(ValueError, match="symmetric pads 0 <= p < K"):
        autotune.tune_network([bad])
    p = init_params(layers.cnn_params_from_layers([bad]),
                    jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="symmetric pads 0 <= p < K"):
        layers.cnn_pack_params(p, [bad])
    x = jnp.zeros((1, 16, 16, 3), jnp.float32)
    with pytest.raises(ValueError, match="symmetric pads 0 <= p < K"):
        layers.cnn_apply_from_layers(p, [bad], x)
    # NetworkPlan still *plans* it (analytical, exact padding), but the
    # cache lookup knows nothing was executable to tune
    NetworkPlan.build([bad], use_autotune_cache=True)
    # built-in topologies are all executable as planned
    for net in ("vgg16", "alexnet", "mobilenet", "mobilenet_v1"):
        for l in network_layers(net):
            layer_kernel_problem(l)


def test_tune_network_rejects_sharded_measure():
    topo = [ConvLayer("c1", 12, 3, 4, kernel=3, padding=1)]
    with pytest.raises(ValueError, match="measure"):
        autotune.tune_network(topo, spatial_shards=2, measure=True)


def test_tune_network_rejects_duplicate_names():
    l = ConvLayer("c1", 12, 4, 4, kernel=3, padding=1)
    with pytest.raises(ValueError, match="duplicate layer name"):
        autotune.tune_network([l, l])


def test_scale_layers_keeps_topology_chainable():
    for net in ("vgg16", "alexnet", "mobilenet"):
        topo = scale_layers(network_layers(net), 16)
        NetworkPlan.build(topo)           # chainability is validated here
        full = network_layers(net)
        assert [l.ifmap for l in topo] == [l.ifmap for l in full]
        assert topo[0].in_channels == full[0].in_channels
        assert all(t.out_channels <= f.out_channels
                   for t, f in zip(topo, full))
        # depthwise layers stay depthwise
        for t, f in zip(topo, full):
            if f.groups == f.in_channels and f.groups > 1:
                assert t.groups == t.in_channels


# ---------------------------------------------------------------------------
# paper_eval plumbing (the artifact CI uploads)
# ---------------------------------------------------------------------------

def test_paper_eval_rows_and_claim():
    import importlib
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    paper_eval = importlib.import_module("benchmarks.paper_eval")
    res = paper_eval.evaluate("alexnet", measured=True)
    rows, summary = res["rows"], res["summary"]
    kinds = {r["kind"] for r in rows}
    assert kinds == {"arch", "plan", "sim"}
    # every row carries the schema columns (DESIGN.md §7)
    assert all("mode" in r and "dataflow" in r for r in rows)
    assert all(r["exact"] for r in rows if r["kind"] == "sim")
    assert summary["arch"]["improvement"] > 1.0
    assert summary["plan"]["improvement"] >= 1.0
    assert summary["arch"]["max_layer_improvement"] == \
        pytest.approx(3.42, abs=0.02)
