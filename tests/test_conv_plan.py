"""ConvPlan subsystem tests: the plan is the single source of truth for
strip/tile/traffic math — the kernel's actual padded layouts and grids must
be byte-identical to the analytical model, for dense, strided, grouped and
depthwise geometries (VGG-16 and MobileNet layers included)."""

import dataclasses
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ConvPlan, mobilenet_layers, vgg16_layers
from repro.core.conv_plan import (KERNEL_VMEM_BUDGET, Conv1dPlan,
                                   vmem_tile_bytes)
from repro.core.roofline import conv_plan_roofline
from repro.kernels import ops, ref
from repro.kernels.trim_conv2d import (hbm_traffic_model, make_plan,
                                       trim_conv2d)

RNG = np.random.default_rng(11)


def _allclose(a, b, tol=2e-3):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = float(np.abs(b).max()) + 1e-6
    assert float(np.abs(a - b).max()) / scale < tol


# ---------------------------------------------------------------------------
# Plan <-> kernel consistency (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [vgg16_layers()[2], vgg16_layers()[7],
                                   mobilenet_layers()[0],   # depthwise 3x3
                                   mobilenet_layers()[1]])  # pointwise 1x1
def test_plan_is_shared_by_kernel_and_model(layer):
    """Kernel grid geometry and analytical HBM bytes come from the SAME
    ConvPlan for VGG-16 and depthwise MobileNet layers."""
    plan = layer.plan()
    # the plan the kernel executes for these arrays is the same object
    groups = layer.groups
    kplan = make_plan(
        (1, layer.ifmap, layer.ifmap, layer.in_channels),
        (layer.kernel, layer.kernel, layer.in_channels // groups,
         layer.out_channels),
        stride=layer.stride, pad=layer.padding, groups=groups)
    assert plan == kplan
    # grid covers the whole problem exactly
    n, g, strips, co = plan.grid
    assert (n, g) == (1, groups)
    assert strips * plan.th_out >= plan.h_out + plan.delta
    assert co * plan.tile_cout >= plan.cout // groups
    # analytical input bytes == the padded array the kernel DMAs, exactly
    t = plan.hbm_bytes("3dtrim")
    assert t["input"] == math.prod(plan.padded_input_shape) \
        * plan.dtype_bytes
    assert t["output"] == plan.n * plan.h_out * plan.w_out * plan.cout \
        * plan.dtype_bytes
    # roofline reads the same plan
    terms = conv_plan_roofline(layer.name, plan)
    assert terms.hbm_bytes_per_dev == t["total"]
    assert terms.flops_per_dev == plan.flops == layer.macs * 2


def test_traffic_equals_actual_padded_bytes():
    """ConvPlan traffic == the byte counts of the arrays the kernel builds:
    run the kernel and check the padded layouts it asserts against."""
    x = jnp.asarray(RNG.standard_normal((2, 17, 13, 4)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 4, 10)) * .3, jnp.float32)
    plan = make_plan(x.shape, w.shape, stride=2, pad=1, tile_h=4,
                     tile_cout=4)
    out = trim_conv2d(x, w, stride=2, pad=1, tile_h=4, tile_cout=4)
    assert out.shape == (plan.n, plan.h_out, plan.w_out, plan.cout)
    t = plan.hbm_bytes("3dtrim")
    # input: padded array fetched strip-by-strip, each strip exactly once
    assert t["input"] == math.prod(plan.padded_input_shape) * 4
    # output: the useful (sliced) result the caller receives
    assert t["output"] == out.size * 4
    # weights: one full (unpadded) weight stream per strip sweep
    assert t["weights"] == w.size * 4 * plan.g_tiles
    # trim mode re-fetches K-1 halo rows per strip after the first
    halo = plan.hbm_bytes("trim")["input"] - t["input"]
    assert halo == (plan.g_tiles - 1) * (plan.kh - 1) * plan.wp \
        * plan.cin * 4 * plan.n
    _allclose(out, ref.conv2d(jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))),
                              w, stride=2, padding="valid"))


def test_legacy_traffic_wrapper_delegates_to_plan():
    a = hbm_traffic_model(1, 224, 224, 64, 64, 3, tile_h=8, mode="3dtrim")
    b = hbm_traffic_model(1, 224, 224, 64, 64, 3, tile_h=8, mode="trim")
    plan = ConvPlan(n=1, h=224, w=224, cin=64, cout=64, kh=3, kw=3,
                    tile_h=8)
    assert a == plan.hbm_bytes("3dtrim")
    assert b == plan.hbm_bytes("trim")
    assert b["input"] > a["input"] and a["overhead_pct"] == 0.0


def test_plan_validation():
    with pytest.raises(ValueError):
        ConvPlan(n=1, h=8, w=8, cin=4, cout=8, kh=3, kw=3, stride=2,
                 tile_h=3)              # tile_h not a stride multiple
    with pytest.raises(ValueError):
        ConvPlan(n=1, h=8, w=8, cin=4, cout=9, kh=3, kw=3, groups=2)
    with pytest.raises(ValueError):
        make_plan((1, 8, 8, 4), (3, 3, 4, 8), groups=2)  # cin mismatch
    with pytest.raises(ValueError):
        ConvPlan(n=1, h=8, w=8, cin=4, cout=8, kh=3, kw=3, tile_h=0)
    with pytest.raises(ValueError):
        ConvPlan(n=1, h=8, w=8, cin=4, cout=8, kh=3, kw=3, tile_cout=0)


# ---------------------------------------------------------------------------
# Oversized-strip canonicalization (tile_h > H_out — DESIGN.md §6 fix):
# instead of padding/billing ever more rows that neither dataflow reads
# (inconsistently between carry and halo), any tile_h beyond the
# full-height strip clamps to it, so both dataflows and every consumer
# see one canonical single-strip plan.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dataflow", ["carry", "halo"])
@pytest.mark.parametrize("stride", [1, 2, 3])
def test_oversized_tile_h_clamps_canonically(dataflow, stride):
    full = ConvPlan(n=1, h=13, w=9, cin=3, cout=5, kh=3, kw=3,
                    stride=stride, dataflow=dataflow,
                    tile_h=((13 - 3) // stride + 1
                            + (3 - 1) // stride) * stride)
    for oversize in (full.tile_h + stride, 10 * full.tile_h, 997 * stride):
        plan = ConvPlan(n=1, h=13, w=9, cin=3, cout=5, kh=3, kw=3,
                        stride=stride, dataflow=dataflow, tile_h=oversize)
        # identical plan: same padding, same grid, same traffic
        assert plan == full
        assert plan.g_tiles == 1
        assert plan.padded_input_shape == full.padded_input_shape
        assert plan.hbm_bytes() == full.hbm_bytes()
    # both dataflows agree on the clamp (the bug class this fixes:
    # carry and halo padded layouts diverging for tile_h > H_out)
    a = ConvPlan(n=1, h=13, w=9, cin=3, cout=5, kh=3, kw=3, stride=stride,
                 dataflow="carry", tile_h=500 * stride)
    b = ConvPlan(n=1, h=13, w=9, cin=3, cout=5, kh=3, kw=3, stride=stride,
                 dataflow="halo", tile_h=500 * stride)
    assert a.tile_h == b.tile_h
    assert a.padded_input_shape == b.padded_input_shape


@pytest.mark.parametrize("dataflow", ["carry", "halo"])
def test_oversized_tile_h_kernel_matches_oracle(dataflow):
    """The kernel executes the clamped plan correctly for tile_h far
    beyond H_out, for both dataflows and stride > 1."""
    x = jnp.asarray(RNG.standard_normal((2, 11, 9, 4)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 4, 6)) * .3, jnp.float32)
    for stride in (1, 2):
        want = ref.conv2d(x, w, stride=stride, padding="valid")
        got = trim_conv2d(x, w, stride=stride, tile_h=1000 * stride,
                          dataflow=dataflow)
        _allclose(got, want)


def test_oversized_tile_go_clamps():
    """WeightGradPlan mirrors the clamp: a cotangent strip taller than
    the whole cotangent is the full-height strip."""
    plan = ConvPlan.build_weight_grad((1, 12, 10, 4), (3, 3, 4, 6),
                                      stride=2, tile_go=999)
    assert plan.tile_go == plan.h_out
    assert plan.go_tiles == 1
    small = ConvPlan.build_weight_grad((1, 12, 10, 4), (3, 3, 4, 6),
                                       stride=2, tile_go=plan.h_out)
    assert plan == small
    with pytest.raises(ValueError):
        ConvPlan.build_weight_grad((1, 12, 10, 4), (3, 3, 4, 6),
                                   tile_go=0)


# ---------------------------------------------------------------------------
# Kernel edge geometry vs the oracle — both dataflows (the halo-vs-carry
# numerical-equivalence acceptance grid)
# ---------------------------------------------------------------------------

DATAFLOWS = ["carry", "halo"]


@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_conv2d_even_kernel_strided(dataflow):
    """stride > 1 with K even exercises the (K-1) % s != 0 row offset."""
    x = jnp.asarray(RNG.standard_normal((1, 18, 15, 5)), jnp.float32)
    for k, s in [(4, 2), (2, 2), (4, 3), (6, 2)]:
        w = jnp.asarray(RNG.standard_normal((k, k, 5, 6)) * .2, jnp.float32)
        _allclose(ops.conv2d(x, w, stride=s, padding="valid",
                             dataflow=dataflow),
                  ref.conv2d(x, w, stride=s, padding="valid"))


@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_conv2d_tile_h_not_dividing_h_out(dataflow):
    """h_out = 14 with tile_h in {3, 4, 5}: bottom strips are ragged."""
    x = jnp.asarray(RNG.standard_normal((1, 16, 10, 4)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 4, 8)) * .3, jnp.float32)
    want = ref.conv2d(x, w, padding="valid")
    for th in (3, 4, 5):
        _allclose(trim_conv2d(x, w, tile_h=th, dataflow=dataflow), want)


@pytest.mark.parametrize("dataflow", DATAFLOWS)
def test_conv2d_cout_not_dividing_tile_cout(dataflow):
    """cout = 10 with tile_cout = 4: the last cout tile is zero-padded."""
    x = jnp.asarray(RNG.standard_normal((1, 12, 9, 3)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 3, 10)) * .3, jnp.float32)
    _allclose(trim_conv2d(x, w, tile_cout=4, dataflow=dataflow),
              ref.conv2d(x, w, padding="valid"))


def test_halo_equals_carry_bitwise_across_geometries():
    """The two dataflows consume identical window contents, so they must
    agree exactly (not just to tolerance) across stride/pad/group edges."""
    for (h, w, cin, cout, k, s, pad, g) in [
            (16, 10, 4, 8, 3, 1, 0, 1), (17, 13, 5, 6, 4, 2, 1, 1),
            (12, 11, 8, 8, 3, 2, 1, 4), (9, 9, 6, 6, 5, 3, 2, 2),
            (8, 8, 4, 4, 1, 1, 0, 1)]:
        x = jnp.asarray(RNG.standard_normal((2, h, w, cin)), jnp.float32)
        wt = jnp.asarray(RNG.standard_normal((k, k, cin // g, cout)) * .3,
                         jnp.float32)
        a = trim_conv2d(x, wt, stride=s, pad=pad, groups=g,
                        dataflow="carry")
        b = trim_conv2d(x, wt, stride=s, pad=pad, groups=g,
                        dataflow="halo")
        assert jnp.array_equal(a, b), (h, w, k, s, pad, g)


def test_halo_plan_geometry_and_traffic():
    """Halo plan: overlapping window block, K-1 extra top rows, and the
    plan's own accounting equals the legacy 'trim' mode."""
    plan = ConvPlan(n=1, h=32, w=32, cin=16, cout=32, kh=3, kw=3,
                    tile_h=8, dataflow="halo")
    assert plan.halo_in_block == (1, 8 + 2, plan.wp, 16)
    assert plan.halo_padded_input_shape == \
        (1, 2 + plan.rows_padded, plan.wp, 16)
    assert plan.traffic_mode == "trim"
    assert plan.hbm_bytes() == plan.hbm_bytes("trim")
    carry = ConvPlan(n=1, h=32, w=32, cin=16, cout=32, kh=3, kw=3,
                     tile_h=8)
    assert carry.traffic_mode == "3dtrim"
    assert carry.hbm_bytes() == carry.hbm_bytes("3dtrim")
    # halo pays (g_tiles - 1) * (K-1) extra rows; carry pays none
    assert plan.hbm_bytes()["input"] > carry.hbm_bytes()["input"]
    assert plan.halo_rows() == (plan.g_tiles - 1) * 2
    assert carry.halo_rows() == 0
    # resident sets differ by the K-1 boundary rows: the halo window
    # block double-buffers them, the carry scratch holds one copy
    assert plan.vmem_resident_bytes - carry.vmem_resident_bytes \
        == vmem_tile_bytes((2, plan.wp, 16), plan.dtype_bytes)
    with pytest.raises(ValueError):
        ConvPlan(n=1, h=8, w=8, cin=4, cout=8, kh=3, kw=3,
                 dataflow="weird")


# ---------------------------------------------------------------------------
# Grouped / depthwise + fused epilogue (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups,cin,cout", [(2, 8, 6), (4, 8, 8),
                                             (8, 8, 8), (8, 8, 16)])
def test_grouped_conv_vs_oracle(groups, cin, cout):
    x = jnp.asarray(RNG.standard_normal((2, 12, 11, cin)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, cin // groups, cout)) * .3,
                    jnp.float32)
    for stride, padding in [(1, "same"), (2, "valid")]:
        _allclose(
            ops.conv2d(x, w, stride=stride, padding=padding,
                       feature_group_count=groups),
            ref.conv2d(x, w, stride=stride, padding=padding,
                       feature_group_count=groups))


def test_depthwise_conv2d_helper():
    x = jnp.asarray(RNG.standard_normal((1, 14, 14, 8)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 1, 8)) * .3, jnp.float32)
    b = jnp.asarray(RNG.standard_normal((8,)), jnp.float32)
    _allclose(ops.depthwise_conv2d(x, w, bias=b, activation="relu"),
              ref.conv2d(x, w, feature_group_count=8, bias=b,
                         activation="relu"))


@pytest.mark.parametrize("activation", [None, "relu", "gelu", "silu"])
def test_fused_epilogue_vs_oracle(activation):
    x = jnp.asarray(RNG.standard_normal((2, 10, 10, 6)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 6, 12)) * .3, jnp.float32)
    b = jnp.asarray(RNG.standard_normal((12,)), jnp.float32)
    _allclose(ops.conv2d(x, w, bias=b, activation=activation),
              ref.conv2d(x, w, bias=b, activation=activation))


def test_fused_epilogue_kernel_tiled_path():
    """K > MAX_NATIVE_K: epilogue applied once after the adder tree."""
    x = jnp.asarray(RNG.standard_normal((1, 30, 30, 3)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((11, 11, 3, 4)) * .1, jnp.float32)
    b = jnp.asarray(RNG.standard_normal((4,)), jnp.float32)
    _allclose(
        ops.conv2d(x, w, stride=4, padding="valid", bias=b,
                   activation="relu"),
        ref.conv2d(x, w, stride=4, padding="valid", bias=b,
                   activation="relu"), tol=5e-3)


# ---------------------------------------------------------------------------
# 1D plan
# ---------------------------------------------------------------------------

def test_vmem_tile_padding():
    """VMEM buffers pad the last two dims to the dtype's (sublane, 128)
    tile: 8 rows of 32-bit, 16 of 16-bit, 32 of 8-bit values."""
    assert vmem_tile_bytes((10, 3), 4) == 16 * 128 * 4
    assert vmem_tile_bytes((10, 3), 2) == 16 * 128 * 2
    assert vmem_tile_bytes((10, 3), 1) == 32 * 128 * 1
    assert vmem_tile_bytes((2, 8, 128), 4) == 2 * 8 * 128 * 4
    assert vmem_tile_bytes((4, 9, 129), 4) == 4 * 16 * 256 * 4


@pytest.mark.parametrize("layer", range(13))
@pytest.mark.parametrize("dtype_bytes", [4, 2])
def test_default_strip_is_tallest_that_fits(layer, dtype_bytes):
    """The default strip of every VGG-16 layer is the tallest whose
    modeled VMEM set fits KERNEL_VMEM_BUDGET (or the full height)."""
    plan = ConvPlan.from_layer(vgg16_layers()[layer],
                               dtype_bytes=dtype_bytes)
    assert plan.vmem_resident_bytes <= KERNEL_VMEM_BUDGET
    full = (plan.h_out + plan.delta) * plan.stride
    if plan.tile_h < full:
        taller = dataclasses.replace(plan, tile_h=plan.tile_h + plan.stride)
        assert taller.vmem_resident_bytes > KERNEL_VMEM_BUDGET


def test_conv1d_plan_geometry():
    plan = Conv1dPlan.build((2, 100, 24), (4, 24))
    assert plan.grid == (2, 1, 1)
    assert plan.length_padded >= 100
    assert plan.carry_shape == (3, 24)
    t = plan.hbm_bytes("3dtrim")
    assert t["input"] == math.prod(plan.padded_input_shape) * 4
    assert plan.hbm_bytes("trim")["total"] >= t["total"]
    assert plan.arithmetic_intensity() > 0
