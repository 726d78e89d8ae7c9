"""Int8 fixed-point inference path (DESIGN.md §11).

Three contracts, in increasing integration order:

* **Bit-exactness.**  The int8 Pallas kernel (interpret mode) must agree
  *bit for bit* with the ``ref.conv2d_quantized`` oracle on every
  geometry: the MXU taps accumulate exactly in int32 and the fused
  epilogue is an exact int32 bias add followed by one correctly-rounded
  f32 multiply — there is no legitimate source of divergence, so the
  test uses ``==``, not allclose.

* **Calibrated accuracy.**  The dequantized int8 output of a VGG-16
  block must sit inside the *analytical* quantization error bound
  derived from the calibration scales (interval arithmetic over the
  rounding half-ulps), not just some empirical tolerance.

* **Guarded demotion.**  The quantized tier chain ``q8 -> pallas ->
  ref`` fails soft through ``testing/faults.py`` like every other conv
  path.

Plus the dtype-plumbing regressions of this sweep: ``dtype_width``,
bf16 plans pricing 2-byte traffic, and the ``conv2d_q8:`` autotune
namespace.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune, guard
from repro.core.conv_plan import ConvPlan, resolve_dtype_bytes
from repro.core.roofline import dtype_width
from repro.kernels import ops, ref
from repro.kernels.trim_conv2d import trim_conv2d
from repro.models import layers as mlayers
from repro.models.base import init_params
from repro.testing import faults

RNG = np.random.default_rng(42)


def _f32(shape, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, jnp.float32)


def _quantize_problem(x, w, bias=None, zero_point=3):
    """Calibrate + quantize one conv problem the way the oracle expects."""
    x_scale = float(jnp.max(jnp.abs(x))) / 127.0
    w_scale = ref.weight_scales_int8(w)
    x_q = ref.quantize_int8(x, x_scale, zero_point)
    w_q = ref.quantize_int8(w, w_scale[None, None, None, :])
    return dict(x_q=x_q, w_q=w_q, x_scale=x_scale, x_zero_point=zero_point,
                w_scale=w_scale, bias=bias)


def _kernel_vs_oracle(n, h, w_, cin, cout, k, stride, groups, padding,
                      dataflow, bias=True):
    """Run the int8 kernel and the oracle on one geometry; return both."""
    x = _f32((n, h, w_, cin))
    w = _f32((k, k, cin // groups, cout), 0.1)
    b = _f32((cout,)) if bias else None
    q = _quantize_problem(x, w, b)
    y_ref = ref.conv2d_quantized(q["x_q"], q["w_q"], x_scale=q["x_scale"],
                                 x_zero_point=q["x_zero_point"],
                                 w_scale=q["w_scale"], bias=b,
                                 stride=stride, padding=padding,
                                 feature_group_count=groups)
    scale, bias_q = ref.dequant_params(q["w_q"], q["w_scale"],
                                       q["x_scale"], q["x_zero_point"], b)
    x_k = q["x_q"]
    if padding == "same":
        ph = ref._same_pads(h, k, stride)
        pw = ref._same_pads(w_, k, stride)
        zp = jnp.asarray(q["x_zero_point"], jnp.int8)
        x_k = jax.lax.pad(x_k, zp, ((0, 0, 0), (*ph, 0), (*pw, 0),
                                    (0, 0, 0)))
    y_k = trim_conv2d(x_k, q["w_q"], bias_q, scale, stride=stride, pad=0,
                      groups=groups, dataflow=dataflow, interpret=True)
    return y_k, y_ref


# ---------------------------------------------------------------------------
# Bit-exactness: kernel == oracle, across the geometry grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dataflow", ["carry", "halo"])
@pytest.mark.parametrize(
    "k,stride,groups,padding",
    [(1, 1, 1, "same"),           # pointwise
     (3, 1, 1, "same"),           # the VGG workhorse
     (3, 2, 1, "same"),           # strided, asymmetric 'same' pads
     (3, 1, 2, "same"),           # grouped
     (5, 1, 2, "same"),           # big taps + grouped
     (3, 2, 2, "valid"),          # strided grouped, no padding
     (1, 1, 1, "valid")])
def test_int8_kernel_bit_exact(k, stride, groups, padding, dataflow):
    y_k, y_ref = _kernel_vs_oracle(2, 13, 11, 8, 12, k, stride, groups,
                                   padding, dataflow)
    assert y_k.dtype == jnp.float32
    assert bool(jnp.all(y_k == y_ref)), \
        float(jnp.max(jnp.abs(y_k - y_ref)))


def test_int8_kernel_bit_exact_no_bias_nonzero_zp():
    """The zero-point correction alone (no real bias) is still exact —
    'same' borders are padded with zp, not 0, so every output position
    sees the position-independent integer correction."""
    for df in ("carry", "halo"):
        y_k, y_ref = _kernel_vs_oracle(1, 12, 12, 8, 16, 3, 1, 1, "same",
                                       df, bias=False)
        assert bool(jnp.all(y_k == y_ref))


def test_int8_route_requires_consistent_arguments():
    x8 = jnp.zeros((1, 8, 8, 8), jnp.int8)
    w8 = jnp.zeros((3, 3, 8, 8), jnp.int8)
    s = jnp.ones((1, 8), jnp.float32)
    with pytest.raises(ValueError, match="int8 route"):
        trim_conv2d(x8, w8, interpret=True)           # int x, no scale
    with pytest.raises(ValueError, match="int8 route"):
        trim_conv2d(x8.astype(jnp.float32), w8.astype(jnp.float32), None,
                    s, interpret=True)                # scale, float x
    with pytest.raises(ValueError, match="integer weights"):
        trim_conv2d(x8, w8.astype(jnp.float32), None, s, interpret=True)
    with pytest.raises(ValueError, match="requantized int32 bias"):
        trim_conv2d(x8, w8, jnp.zeros((8,), jnp.float32), s,
                    interpret=True)


# ---------------------------------------------------------------------------
# The ops dispatch: quantize_conv2d_weights / calibrate_conv2d
# ---------------------------------------------------------------------------

def test_ops_conv2d_quantized_matches_oracle_bit_exact():
    x = _f32((2, 14, 14, 8))
    w = _f32((3, 3, 8, 16), 0.1)
    b = _f32((16,))
    q = _quantize_problem(x, w, b, zero_point=2)
    pk = ops.quantize_conv2d_weights(w, b, x_scale=q["x_scale"],
                                     x_zero_point=2)
    got = ops.conv2d(x, pk, stride=1, padding="same", activation="relu")
    want = ref.conv2d_quantized(q["x_q"], q["w_q"], x_scale=q["x_scale"],
                                x_zero_point=2, w_scale=q["w_scale"],
                                bias=b, stride=1, padding="same",
                                activation="relu")
    assert bool(jnp.all(got == want))
    assert guard.events() == []


def test_quantized_packed_weights_pytree_round_trip():
    w = _f32((3, 3, 8, 16), 0.1)
    pk = ops.quantize_conv2d_weights(w, _f32((16,)), x_scale=0.01,
                                     x_zero_point=1)
    leaves, treedef = jax.tree_util.tree_flatten(pk)
    assert len(leaves) == 5          # w, bias, scale, zero_point, in_scale
    pk2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert bool(jnp.all(pk2.w == pk.w))
    assert bool(jnp.all(pk2.scale == pk.scale))
    assert int(pk2.zero_point) == int(pk.zero_point)
    # padded scale lanes hold 1.0 (NaN-free bias requantization)
    cpp = pk.w.shape[3] // pk.groups
    assert bool(jnp.all(pk.scale.reshape(pk.groups, cpp)[:, 16:] == 1.0))


def test_calibrate_conv2d_jits_and_tracks_f32_within_bound():
    """A VGG-16-block-shaped layer: calibrate on a sample batch, run the
    int8 path under jit, and require the dequantized output to sit
    inside the analytical quantization error bound

        |y_q8 - y_f32| <= (s_w/2) |x| * 1  +  (s_x/2) 1 * |w|
                          + N s_x s_w / 4  +  s_x s_w / 2

    (interval arithmetic over the rounding half-ulps of x, w and the
    requantized bias; every term computable with one more conv)."""
    # conv11 of VGG-16 at 1/16 channel scale: (14, 32, 32), K=3
    x = _f32((1, 14, 14, 32), 0.5)
    p = init_params(mlayers.conv2d_params(3, 32, 32),
                    jax.random.PRNGKey(7))
    y_f32 = mlayers.conv2d_apply(p, x, activation=None)

    pq = mlayers.calibrate_conv2d(p, x)
    assert set(pq) == {"packed"}
    pk = pq["packed"]
    assert pk.w.dtype == jnp.int8 and pk.scale is not None
    y_q8 = jax.jit(
        lambda pt, v: mlayers.conv2d_apply(pt, v, activation=None))(pq, x)
    assert y_q8.shape == y_f32.shape

    s_x = float(pk.input_scale)
    w_scale = ref.weight_scales_int8(p["w"])          # (Cout,)
    ones = jnp.ones_like(p["w"])
    taps = ref.conv2d(jnp.abs(x), ones, padding="same")[..., :1]
    sum_absw = jnp.sum(jnp.abs(p["w"]), axis=(0, 1, 2))
    n_taps = np.prod(p["w"].shape[:3])
    bound = (w_scale / 2) * taps + (s_x / 2) * sum_absw \
        + n_taps * s_x * w_scale / 4 + s_x * w_scale / 2
    err = jnp.abs(y_q8 - y_f32)
    assert bool(jnp.all(err <= bound + 1e-6)), \
        (float(jnp.max(err - bound)),)
    # and the bound is meaningful: quantization error is actually small
    assert float(jnp.max(err)) / (float(jnp.max(jnp.abs(y_f32))) + 1e-6) \
        < 0.05


def test_quantized_grouped_valid_via_ops():
    x = _f32((1, 13, 13, 8))
    w = _f32((3, 3, 4, 8), 0.1)
    pk = ops.quantize_conv2d_weights(
        w, None, x_scale=float(jnp.max(jnp.abs(x))) / 127.0,
        x_zero_point=0, groups=2)
    got = ops.conv2d(x, pk, stride=2, padding="valid")
    q = _quantize_problem(x, w, zero_point=0)
    want = ref.conv2d_quantized(q["x_q"], q["w_q"], x_scale=q["x_scale"],
                                x_zero_point=0, w_scale=q["w_scale"],
                                stride=2, padding="valid",
                                feature_group_count=2)
    assert bool(jnp.all(got == want))


# ---------------------------------------------------------------------------
# Guarded demotion: q8 -> pallas -> ref (DESIGN.md §9 / §11)
# ---------------------------------------------------------------------------

def _quantized_layer():
    x = _f32((1, 12, 12, 8))
    w = _f32((3, 3, 8, 12), 0.1)
    b = _f32((12,))
    pk = ops.quantize_conv2d_weights(
        w, b, x_scale=float(jnp.max(jnp.abs(x))) / 127.0, x_zero_point=2)
    q = _quantize_problem(x, w, b, zero_point=2)
    oracle = ref.conv2d_quantized(
        q["x_q"], q["w_q"], x_scale=q["x_scale"], x_zero_point=2,
        w_scale=q["w_scale"], bias=b, stride=1, padding="same")
    return x, pk, oracle


@pytest.mark.guard_events
def test_q8_failure_demotes_to_f32_pallas():
    x, pk, oracle = _quantized_layer()
    with faults.lowering_failure("q8") as fault:
        got = ops.conv2d(x, pk, layer="conv_q")
    assert fault.calls == 1
    # the f32 tier convolves the *dequantized* operands: same
    # quantization error, only epilogue rounding differs from the oracle
    assert float(jnp.max(jnp.abs(got - oracle))) < 1e-3 * \
        float(jnp.max(jnp.abs(oracle)))
    (ev,) = guard.events()
    assert (ev["tier"], ev["to"], ev["layer"]) == ("q8", "pallas",
                                                   "conv_q")


@pytest.mark.guard_events
def test_q8_double_failure_demotes_to_ref_oracle():
    x, pk, oracle = _quantized_layer()
    with faults.lowering_failure("q8"), faults.lowering_failure("pallas"):
        got = ops.conv2d(x, pk)
    # the final tier IS the oracle: bit-identical
    assert bool(jnp.all(got == oracle))
    tiers = [(e["tier"], e["to"]) for e in guard.events()]
    assert tiers == [("q8", "pallas"), ("pallas", "ref")]


# ---------------------------------------------------------------------------
# Dtype plumbing: dtype_width and dtype-derived plan traffic
# ---------------------------------------------------------------------------

def test_dtype_width_single_source_of_truth():
    assert dtype_width("float32") == dtype_width("f32") == 4
    assert dtype_width("bfloat16") == dtype_width("bf16") == 2
    assert dtype_width("int8") == dtype_width("s8") == 1
    assert dtype_width(jnp.int8) == 1
    assert dtype_width(jnp.dtype("float16")) == 2
    assert dtype_width(np.float64) == 8
    with pytest.raises(ValueError, match="unknown dtype"):
        dtype_width("float40")
    assert resolve_dtype_bytes(2) == 2                # ints pass through
    assert resolve_dtype_bytes("bfloat16") == 2


def test_bf16_plan_prices_two_byte_traffic():
    """The satellite-1 regression: a plan built from a dtype (not a
    hard-coded ``=4``) must bill 2-byte traffic for bf16 and 1-byte for
    int8 — exactly half / a quarter of the f32 plan, with the element
    counts (and therefore Ops/MAcc) unchanged."""
    kw = dict(stride=1, pad=1, tile_h=8, tile_cout=8)
    p32 = ConvPlan.build((1, 16, 16, 8), (3, 3, 8, 8), dtype_bytes=4,
                         **kw)
    p16 = ConvPlan.build((1, 16, 16, 8), (3, 3, 8, 8),
                         dtype_bytes="bfloat16", **kw)
    p8 = ConvPlan.build((1, 16, 16, 8), (3, 3, 8, 8),
                        dtype_bytes=jnp.int8, **kw)
    assert (p16.dtype_bytes, p8.dtype_bytes) == (2, 1)
    for mode in ("3dtrim", "trim"):
        b32 = p32.hbm_bytes(mode)
        b16 = p16.hbm_bytes(mode)
        b8 = p8.hbm_bytes(mode)
        for key in ("input", "weights", "total"):
            assert b16[key] * 2 == b32[key], (mode, key)
            assert b8[key] * 4 == b32[key], (mode, key)


def test_netplan_derives_dtype_bytes_from_dtype():
    from repro.core.netplan import NetworkPlan
    np32 = NetworkPlan.build("alexnet", n=1)
    np16 = NetworkPlan.build("alexnet", n=1, dtype="bfloat16")
    assert all(s.plan.dtype_bytes == 4 for s in np32.steps)
    assert all(s.plan.dtype_bytes == 2 for s in np16.steps)
    # element-count accounting (the Ops/MAcc goldens) is dtype-invariant
    a32 = np32.arch_compare()["ops_per_macc"]
    a16 = np16.arch_compare()["ops_per_macc"]
    assert a32 == a16
    # byte accounting is not: on the same strips, bf16 moves exactly
    # half the bytes (the default strips may differ: a 2-byte strip row
    # takes less VMEM, so bf16 can afford taller strips)
    for s32, s16 in zip(np32.steps, np16.steps):
        same = dataclasses.replace(s32.plan, tile_h=s16.plan.tile_h)
        assert s16.plan.hbm_bytes()["total"] * 2 \
            == same.hbm_bytes()["total"], s16.name


def test_kernel_plans_key_on_input_dtype():
    """trim_conv2d builds its plan from x.dtype: the bf16 kernel call
    must price 2-byte VMEM residency, not a hard-coded 4."""
    from repro.kernels.trim_conv2d import make_plan
    p16 = make_plan((1, 16, 16, 8), (3, 3, 8, 8),
                    dtype_bytes=jnp.bfloat16)
    p32 = make_plan((1, 16, 16, 8), (3, 3, 8, 8), dtype_bytes=4)
    assert p16.dtype_bytes == 2
    assert p16.vmem_resident_bytes < p32.vmem_resident_bytes


# ---------------------------------------------------------------------------
# Autotune: the conv2d_q8 namespace
# ---------------------------------------------------------------------------

def test_q8_knobs_come_only_from_q8_namespace():
    """An f32 record for the identical geometry must never leak tuning
    knobs into the int8 route, and vice versa."""
    x_shape, w_shape = (1, 16, 16, 8), (3, 3, 8, 12)
    f32_key = autotune.make_key(x_shape, w_shape, stride=1, pad=0)
    q8_key = autotune.make_key(x_shape, w_shape, stride=1, pad=0,
                               dtype="int8", op="conv2d_q8")
    assert q8_key.startswith("conv2d_q8:")
    assert f32_key != q8_key
    autotune.store(f32_key, dict(tile_h=8, tile_cout=4, dataflow="carry"))
    assert autotune.knobs_for(x_shape, w_shape, dtype="int8",
                              op="conv2d_q8") is None
    autotune.store(q8_key, dict(tile_h=4, tile_cout=8, dataflow="halo"))
    got = autotune.knobs_for(x_shape, w_shape, dtype="int8",
                             op="conv2d_q8")
    assert (got["tile_h"], got["dataflow"]) == (4, "halo")
    # the plain conv2d consult still sees only its own record
    assert autotune.knobs_for(x_shape, w_shape)["tile_h"] == 8


def test_tune_q8_round_trip_and_forward_consult():
    """``tune(op="conv2d_q8", dtype="int8")`` persists under the q8
    namespace with 1-byte candidate pricing, and the quantized forward
    actually honors the record (observable via the packed tile_cout
    guard: a mismatched record is ignored)."""
    x = _f32((1, 16, 16, 8))
    w = _f32((3, 3, 8, 12), 0.1)
    rec = autotune.tune(x.shape, w.shape, stride=1, pad=0, dtype="int8",
                        op="conv2d_q8")
    key = autotune.make_key(x.shape, w.shape, stride=1, pad=0,
                            dtype="int8", op="conv2d_q8")
    assert autotune.lookup(key) == rec
    pk = ops.quantize_conv2d_weights(
        w, None, x_scale=float(jnp.max(jnp.abs(x))) / 127.0,
        x_zero_point=0, tile_cout=rec["tile_cout"])
    got = ops.conv2d(x, pk, padding="valid")
    q = _quantize_problem(x, w, zero_point=0)
    want = ref.conv2d_quantized(q["x_q"], q["w_q"], x_scale=q["x_scale"],
                                x_zero_point=0, w_scale=q["w_scale"],
                                padding="valid")
    assert bool(jnp.all(got == want))


def test_measured_q8_tune_runs_int8_kernel():
    """measure=True on an int8 problem wall-clocks the *int8* kernel
    (integer operands + unit scale row) without tripping the
    int8-route argument validation."""
    rec = autotune.tune((1, 12, 12, 8), (3, 3, 8, 8), stride=1, pad=0,
                        dtype="int8", op="conv2d_q8", measure=True,
                        measure_top_k=1)
    assert rec["source"] == "measured"


# ---------------------------------------------------------------------------
# Energy model (satellite 6's gate, unit-level)
# ---------------------------------------------------------------------------

def test_energy_model_int8_vs_f32():
    from repro.core import energy
    int8 = energy.energy_per_inference("vgg16", dtype_bytes=1,
                                       mac="mac_int8")
    f32 = energy.energy_per_inference("vgg16", dtype_bytes=4,
                                      mac="mac_fp32")
    # the acceptance gate: quantization buys > 2x modeled energy
    assert f32["total_uJ"] / int8["total_uJ"] > 2.0
    assert f32["tops_per_watt"] < int8["tops_per_watt"]
    # the OPs/pJ == TOPS/W identity holds against a by-hand recompute
    from repro.core import model as acc_model
    ops_total = 2 * sum(l.macs for l in acc_model.vgg16_layers())
    assert int8["tops_per_watt"] == pytest.approx(
        ops_total / (int8["total_uJ"] * 1e6))
    with pytest.raises(ValueError, match="unknown network"):
        energy.energy_per_inference("resnet50")
