"""The depthwise VPU kernel (DESIGN.md §14) and the symmetric padding of
the layer executor, against the oracles, in interpret mode.

* ``trim_depthwise_conv2d`` and ``ops.conv2d``'s dispatch to it against
  ``ref.conv2d(feature_group_count=C)``: C in {32, 128, 200}, stride 1
  and 2, even and odd sizes, with and without the bias + ReLU epilogue;
  its gradients, its counter, its tuner records;
* padding: a layer's stated ``(p, p)`` is what runs (a 3x3/s2 conv on
  an even input pads ``(1, 1)``, not XLA 'same''s ``(0, 1)``), and
  ``layer_kernel_problem`` refuses pads it cannot run;
* MobileNetV1 (``mobilenet_v1_layers``) through the serving engine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.conv_plan import (KERNEL_VMEM_BUDGET, LANES,
                                  DepthwisePlan)
from repro.core.model import ConvLayer, mobilenet_v1_layers, resnet18_graph
from repro.core.netplan import layer_kernel_problem, scale_layers
from repro.kernels import ops, ref
from repro.kernels.trim_depthwise import trim_depthwise_conv2d

RNG = np.random.default_rng(7)


def _arr(*shape, scale=1.0):
    return jnp.asarray(RNG.standard_normal(shape) * scale, jnp.float32)


def _close(got, want, tol=1e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    scale = float(np.abs(want).max()) + 1e-9
    assert float(np.abs(got - want).max()) / scale < tol


# ---------------------------------------------------------------------------
# The kernel against the oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("epilogue", [False, True])
@pytest.mark.parametrize("size", [10, 11])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("c", [32, 128, 200])
def test_depthwise_kernel_matches_oracle(c, stride, size, epilogue):
    x = _arr(2, size, size, c)
    w = _arr(3, 3, 1, c, scale=0.3)
    b = _arr(c) if epilogue else None
    act = "relu" if epilogue else None
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    want = ref.conv2d(xp, w, stride=stride, padding="valid",
                      feature_group_count=c, bias=b, activation=act)
    got = trim_depthwise_conv2d(xp, w, b, stride=stride, activation=act)
    _close(got, want)
    # several strips (the carry), several images a step
    got = trim_depthwise_conv2d(xp, w, b, stride=stride, tile_h=2,
                                tile_n=2, activation=act)
    _close(got, want)


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_runs_depthwise_on_the_vpu_kernel(pallas_calls, stride):
    """``ops.conv2d`` sends a multiplier-1 depthwise conv to the VPU
    kernel (one kernel named after its layer, over the ``(K*K, C)``
    taps) and the result is the oracle's."""
    x, w, b = _arr(2, 12, 12, 16), _arr(3, 3, 1, 16, scale=0.3), _arr(16)

    def fwd(x, w, b):
        return ops.conv2d(x, w, stride=stride, padding=1,
                          feature_group_count=16, bias=b, activation="relu",
                          layer=f"dw_s{stride}")

    got = fwd(x, w, b)
    want = ref.conv2d(x, w, stride=stride, padding=1,
                      feature_group_count=16, bias=b, activation="relu")
    _close(got, want)
    [(name, shapes)] = pallas_calls(fwd, x, w, b)
    assert name == f"dw_s{stride}" and (9, 16) in shapes


def test_runs_depthwise_rule():
    def kind(x_shape, w_shape, groups, dtype):
        return ops.conv_route(x_shape, w_shape, stride=1, groups=groups,
                              dtype=dtype).kind

    assert kind((1, 8, 8, 16), (3, 3, 1, 16), 16, "float32") == "depthwise"
    # a depthwise multiplier of 2, grouped convs, int8, one group
    assert kind((1, 8, 8, 16), (3, 3, 1, 32), 16, "float32") == "mxu"
    assert kind((1, 8, 8, 16), (3, 3, 2, 16), 8, "float32") == "mxu"
    assert kind((1, 8, 8, 16), (3, 3, 1, 16), 16, "int8") == "mxu"
    assert kind((1, 8, 8, 1), (3, 3, 1, 1), 1, "float32") == "folded"


@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_gradients_match_oracle(stride):
    x, w, b = _arr(1, 9, 9, 8), _arr(3, 3, 1, 8, scale=0.3), _arr(8)

    def loss(impl):
        return lambda x, w, b: (ops.conv2d(
            x, w, stride=stride, padding=1, feature_group_count=8, bias=b,
            activation="relu", impl=impl) ** 2).sum()

    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(x, w, b)
    want = jax.grad(loss("ref"), argnums=(0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        _close(g, r)


# ---------------------------------------------------------------------------
# The plan and the tuner
# ---------------------------------------------------------------------------

def test_depthwise_plan_geometry():
    # dw2 of MobileNetV1 at batch 8: 114 padded rows, stride 2
    p = DepthwisePlan.build((8, 114, 114, 64), 3, stride=2, tile_h=8)
    assert (p.h_out, p.w_out, p.carry_rows, p.strip_rows) == (56, 56, 1, 16)
    assert p.grid == (8, 1, 7)
    assert p.main_rows + p.carry_rows == 113     # the last row is unread
    assert p.hbm_bytes()["output"] == 8 * 56 * 56 * 64 * 4
    # stride 1 carries K-1 rows; a strip covering the map is clamped
    p = DepthwisePlan.build((1, 16, 16, 256), 3, tile_h=99)
    assert (p.carry_rows, p.tile_h, p.g_tiles, p.tile_c) == (2, 14, 1, 128)
    # strided loads take one lane tile: wider C rounds up to whole tiles
    p = DepthwisePlan.build((1, 12, 12, 200), 3, stride=2)
    assert (p.c, p.tile_c) == (256, LANES)
    with pytest.raises(ValueError, match="strided loads"):
        DepthwisePlan.build((1, 12, 12, 256), 3, stride=2, tile_c=256)
    with pytest.raises(ValueError, match="carried"):
        DepthwisePlan.build((1, 12, 12, 8), 3, tile_h=1)
    with pytest.raises(ValueError, match="tile_c"):
        DepthwisePlan.build((1, 12, 12, 256), 3, tile_c=96)
    # the default strip fits the budget and divides the output rows
    p = DepthwisePlan.build((128, 114, 114, 32), 3)
    assert p.vmem_resident_bytes <= KERNEL_VMEM_BUDGET
    assert p.h_out % p.tile_h == 0


def test_tuner_records_depthwise_layers_under_their_own_key():
    """``tune_network`` tunes a depthwise layer as the VPU kernel's
    problem (``conv2d_dw:``), ``ops.conv2d`` reads that record, and a
    record whose knobs do not fit is a miss."""
    topo = [ConvLayer("dw", 14, 64, 64, kernel=3, stride=2, padding=1,
                      groups=64),
            ConvLayer("pw", 7, 64, 128, kernel=1)]
    recs = autotune.tune_network(topo, n=4)
    assert recs["dw"]["key"].startswith("conv2d_dw:n4h16w16cin64")
    assert recs["pw"]["key"].startswith("conv2d:")
    got = autotune.depthwise_knobs_for((4, 16, 16, 64), 3, stride=2)
    assert got == {k: v for k, v in recs["dw"].items() if k != "key"}
    plan = DepthwisePlan.build((4, 16, 16, 64), 3, stride=2,
                               tile_h=got["tile_h"], tile_c=got["tile_c"],
                               tile_n=got["tile_n"])
    best = min(autotune.candidate_depthwise_knobs((4, 16, 16, 64), 3,
                                                  stride=2),
               key=lambda p: p.model_step_time_s)
    assert plan.model_step_time_s == best.model_step_time_s
    autotune.store(recs["dw"]["key"], dict(got, tile_c=96))
    with pytest.warns(RuntimeWarning, match="infeasible"):
        assert autotune.depthwise_knobs_for((4, 16, 16, 64), 3,
                                            stride=2) is None


def test_tuned_depthwise_knobs_reach_the_kernel(monkeypatch):
    x, w = _arr(4, 14, 14, 64), _arr(3, 3, 1, 64, scale=0.3)
    xp_shape = (4, 16, 16, 64)
    autotune.store(autotune.depthwise_key(xp_shape, 3, stride=1),
                   dict(tile_h=7, tile_c=64, tile_n=2, source="model",
                        model_step_time_s=0.0))
    seen = {}
    real = ops.trim_depthwise_conv2d

    def spy(*a, **kw):
        seen.update(kw)
        return real(*a, **kw)

    monkeypatch.setattr(ops, "trim_depthwise_conv2d", spy)
    got = ops.conv2d(x, w, padding=1, feature_group_count=64)
    assert (seen["tile_h"], seen["tile_c"], seen["tile_n"]) == (7, 64, 2)
    _close(got, ref.conv2d(x, w, padding=1, feature_group_count=64))


# ---------------------------------------------------------------------------
# Symmetric padding of the executor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_stride2_pad1_on_even_input_pads_symmetrically(impl):
    """A 3x3/s2, pad-1 conv on an even input runs pads (1, 1): it equals
    ``lax.conv_general_dilated`` with those explicit pads, where XLA's
    'same' (pads (0, 1)) gives other numbers."""
    x, w = _arr(1, 12, 12, 16), _arr(3, 3, 16, 32, scale=0.3)
    want = jax.lax.conv_general_dilated(
        x, w, (2, 2), [(1, 1), (1, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    got = ops.conv2d(x, w, stride=2, padding=1, impl=impl)
    _close(got, want)
    same = ops.conv2d(x, w, stride=2, padding="same", impl=impl)
    assert same.shape == got.shape
    assert float(jnp.abs(same - want).max()) > 1e-2
    assert ops.conv_pads(12, 12, 3, 2, 1) == ((1, 1), (1, 1))
    assert ops.conv_pads(12, 12, 3, 2, "same") == ((0, 1), (0, 1))


def test_executor_runs_the_layers_padding():
    """The layer executor pads as the layer states: MobileNetV1's
    stride-2 stem and ResNet-18's 7x7/s2 stem are keyed and run on
    their symmetric pads, and stride-1 odd-K 'same'-equivalent layers
    keep their problems."""
    stem = mobilenet_v1_layers()[0]
    x_shape, pad, w_shape, padding = layer_kernel_problem(stem, n=2)
    assert (x_shape, pad, w_shape, padding) == \
        ((2, 226, 226, 3), 0, (3, 3, 3, 32), 1)
    r_stem = resnet18_graph()[0].layer
    assert layer_kernel_problem(r_stem)[0] == (1, 230, 230, 3)
    vgg = ConvLayer("c", 224, 64, 64, kernel=3, padding=1)
    assert layer_kernel_problem(vgg)[0] == ops.kernel_input_shape(
        (1, 224, 224, 64), 3, 1, "same")[0]


@pytest.mark.parametrize("padding", [-1, 3, 4])
def test_layer_kernel_problem_refuses_pads_it_cannot_run(padding):
    bad = ConvLayer("bad", 16, 3, 8, kernel=3, padding=padding)
    with pytest.raises(ValueError, match="symmetric pads 0 <= p < K"):
        layer_kernel_problem(bad)
    with pytest.raises(ValueError, match="symmetric pads"):
        autotune.tune_network([bad])


def test_conv_pads_refuses_unknown_padding():
    with pytest.raises(ValueError, match="padding="):
        ops.conv_pads(8, 8, 3, 1, "full")
    with pytest.raises(ValueError, match="padding="):
        ops.conv_pads(8, 8, 3, 1, -1)


# ---------------------------------------------------------------------------
# MobileNetV1 through the serving engine
# ---------------------------------------------------------------------------

def test_mobilenet_v1_topology():
    layers = mobilenet_v1_layers()
    assert len(layers) == 27
    assert sum(l.macs for l in layers) / 1e6 == pytest.approx(567.7,
                                                              abs=0.05)
    dw = [l for l in layers if l.groups > 1]
    assert [l.stride for l in dw].count(2) == 4 and len(dw) == 13
    assert all(l.groups == l.in_channels == l.out_channels for l in dw)
    assert layers[-1].out_size == 7 and layers[-1].out_channels == 1024


def test_engine_serves_mobilenet_v1_on_the_depthwise_kernel():
    """A small MobileNetV1 (32x32, widths / 8) served through
    ``ServingEngine.for_topology`` equals the plain-``lax`` forward, and
    ``stats()["depthwise"]`` names every depthwise layer."""
    from repro.core.serving import ServingEngine
    from repro.models import layers as mlayers
    from repro.models.base import init_params
    topo = scale_layers(mobilenet_v1_layers(32), 8)
    p = init_params(mlayers.cnn_params_from_layers(topo, n_classes=10),
                    jax.random.PRNGKey(3))
    engine = ServingEngine.for_topology(topo, p, buckets=(2,))
    engine.prewarm()
    x = np.asarray(_arr(2, 32, 32, 3))
    got = engine.replicas[0].fn(x)
    with jax.default_matmul_precision("highest"):
        want = mlayers.cnn_apply_from_layers(p, topo, jnp.asarray(x),
                                             impl="ref")
    _close(got, want, tol=1e-4)
    stats = engine.stats()
    assert stats["depthwise"] == {l.name: l.in_channels for l in topo
                                  if l.groups > 1}
    assert len(stats["depthwise"]) == 13
    assert stats["cold_tunes"] == 0
