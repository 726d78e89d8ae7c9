"""Tap folding (DESIGN.md §13): a conv whose K*K*Cin fits one 128-lane
tile runs as a 1x1 TrIM conv over its tap-folded input.

Interpret mode on the CPU: results against the oracle, gradients, the
rule's boundary, the stride-2 fold's selection views against strided
slices, the kernel's name, the tuner's key and the serving engine's
``tap_folded`` and ``fold_strided`` reports."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.conv_plan import ConvPlan
from repro.core.model import ConvLayer
from repro.kernels import ops, ref

RNG = np.random.default_rng(14)


def _arrays(cin, k, cout=8, shape=(2, 9, 8)):
    x = jnp.asarray(RNG.standard_normal(shape + (cin,)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((k, k, cin, cout)) * 0.3,
                    jnp.float32)
    b = jnp.asarray(RNG.standard_normal((cout,)), jnp.float32)
    return x, w, b


@pytest.mark.parametrize("epilogue", [False, True])
@pytest.mark.parametrize("padding", ["same", "valid"])
@pytest.mark.parametrize("stride", [1, 2])
@pytest.mark.parametrize("k", [3, 5])
@pytest.mark.parametrize("cin", [1, 3, 14])
def test_folded_conv_matches_ref(pallas_calls, cin, k, stride, padding,
                                 epilogue):
    """Every (Cin, K) of the grid against ``ref.conv2d``; the fold
    engages exactly where K*K*Cin fits the lane tile (K=5 with Cin=14,
    350 lanes, is the unfolded control): the traced program's one
    kernel runs a 1x1 over the K*K*Cin folded lanes."""
    x, w, b = _arrays(cin, k)
    kw = dict(bias=b, activation="relu") if epilogue else {}
    layer = f"fold_c{cin}k{k}s{stride}{padding}{int(epilogue)}"

    def fwd(x, w):
        return ops.conv2d(x, w, stride=stride, padding=padding,
                          layer=layer, **kw)

    got = fwd(x, w)
    want = ref.conv2d(x, w, stride=stride, padding=padding, **kw)
    assert got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    [(name, (xs, ws, *_))] = pallas_calls(fwd, x, w)
    assert name == layer
    folds = k * k * cin <= 128
    assert xs[-1] == (k * k * cin if folds else cin)
    assert ws[:3] == ((1, 1, k * k * cin) if folds else (k, k, cin))


@pytest.mark.parametrize("cin,k,stride,padding", [
    (3, 3, 1, "same"),          # VGG-16 conv1
    (1, 3, 1, "same"),          # U-Net enc0a
    (3, 3, 2, "same"),          # MobileNet's stride-2 stem
    (5, 5, 2, "valid"),
])
def test_folded_conv_grads_match_ref(cin, k, stride, padding):
    """``jax.grad`` runs through the fold's views (slices and concat at
    stride 1, selection contractions at stride 2), then the 1x1 kernel's
    backward kernels: dx, dw and db match the oracle's."""
    assert ops.conv_route((2, 11, 10, cin), (k, k, cin, 8), stride=stride,
                          groups=1, dtype="float32").kind == "folded"
    x, w, b = _arrays(cin, k, shape=(2, 11, 10))

    def loss(conv):
        def f(x, w, b):
            y = conv(x, w, stride=stride, padding=padding, bias=b,
                     activation="relu")
            return jnp.sum(y * y)
        return f

    got = jax.grad(loss(ops.conv2d), (0, 1, 2))(x, w, b)
    want = jax.grad(loss(ref.conv2d), (0, 1, 2))(x, w, b)
    for g, r in zip(got, want):
        assert g.shape == r.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("w_shape,groups,folds", [
    ((3, 3, 14, 8), 1, True),          # 126 lanes: the last that fits
    ((3, 3, 15, 8), 1, False),         # 135 lanes
    ((5, 5, 5, 8), 1, True),           # 125 lanes
    ((1, 1, 3, 8), 1, False),          # 1x1: nothing to fold
    ((3, 3, 1, 8), 8, False),          # depthwise
    ((3, 3, 2, 8), 4, False),          # grouped
    ((9, 9, 1, 8), 1, False),          # K > MAX_NATIVE_K: adder tree
])
def test_fold_rule_boundary(w_shape, groups, folds):
    x_shape = (2, 12, 12, w_shape[2] * groups)
    got = ops.conv_route(x_shape, w_shape, stride=1, groups=groups,
                         dtype="float32")
    assert (got.kind == "folded") == folds
    if folds:
        k, _, cin, cout = w_shape
        assert (got.x_shape, got.w_shape, got.stride, got.groups) == \
            ((2, 13 - k, 13 - k, k * k * cin), (1, 1, k * k * cin, cout),
             1, 1)
    else:        # the conv runs as it is
        assert (got.x_shape, got.w_shape, got.stride, got.groups) == \
            (x_shape, w_shape, 1, groups)


def test_folded_shape_with_stride():
    # 'same' pre-padded 15x15 at stride 2: 7x7 outputs
    got = ops.conv_route((1, 15, 15, 3), (3, 3, 3, 64), stride=2, groups=1,
                         dtype="float32")
    assert (got.kind, got.x_shape, got.w_shape, got.stride) == \
        ("folded", (1, 7, 7, 27), (1, 1, 27, 64), 1)


def _slice_fold(x, kh, kw, stride, rows):
    """The fold as K*K strided ``lax.slice`` views of the zero-padded,
    channel-major input, stacked tap-major and moved channel-last: the
    construction the selection path must reproduce bit for bit."""
    h_out = (x.shape[1] - kh) // stride + 1
    w_out = (x.shape[2] - kw) // stride + 1
    x = jnp.pad(jnp.moveaxis(x, 3, 1),
                ((0, 0), (0, 0), (0, (rows - h_out) * stride), (0, 0)))
    n, c = x.shape[:2]
    taps = jnp.concatenate(
        [jax.lax.slice(x, (0, 0, ki, kj),
                       (n, c, ki + (rows - 1) * stride + 1,
                        kj + (w_out - 1) * stride + 1),
                       (1, 1, stride, stride))
         for ki in range(kh) for kj in range(kw)], axis=1)
    return jnp.moveaxis(taps, 1, 3)


@pytest.mark.parametrize("shape,rows", [
    ((2, 226, 226, 3), 112),    # MobileNetV1's pre-padded stem
    ((1, 15, 15, 3), 7),        # odd size
    ((1, 15, 15, 3), 10),       # rows past H_out: the plan's strip rows
    ((2, 17, 16, 1), 9),        # Cin 1: 9 lanes
    ((2, 17, 16, 14), 8),       # Cin 14: 126 lanes
])
def test_strided_fold_equals_the_slice_fold(shape, rows):
    """At stride 2 the fold's selection contractions give every value of
    every view, and the rows past ``H_out``, exactly as the strided
    slices do: same tap-major order, bit for bit."""
    x = jnp.asarray(RNG.standard_normal(shape)
                    * 10.0 ** RNG.integers(-6, 6, shape), jnp.float32)
    got = jax.jit(lambda x: ops._fold_taps(x, 3, 3, 2, rows))(x)
    want = jax.jit(lambda x: _slice_fold(x, 3, 3, 2, rows))(x)
    assert got.shape == want.shape == (shape[0], rows, (shape[2] - 1) // 2,
                                       9 * shape[3])
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("stride", [1, 2])
def test_fold_takes_its_views_without_a_gather(stride):
    """The im2col's K*K views are never a gather: the chip's compiler
    lays a gather out with the batch in the lanes, which a chunk of 8
    images fills to a sixteenth.  At stride 1 they are nine slices; at
    stride 2 no slice has a stride on the last (lane) axis: the views
    are selection contractions."""
    x = jnp.zeros((8, 17, 17, 3), jnp.float32)
    rows = (17 - 3) // stride + 1
    jaxpr = jax.make_jaxpr(
        lambda x: ops._fold_taps(x, 3, 3, stride, rows))(x)
    assert "gather" not in str(jaxpr)
    slices = [e for e in jaxpr.eqns if e.primitive.name == "slice"]
    if stride == 1:
        assert len(slices) == 9
    else:
        assert all((e.params["strides"] or (1,))[-1] == 1 for e in slices)
        assert sum(e.primitive.name == "dot_general"
                   for e in jaxpr.eqns) == 2


def test_folded_kernel_keeps_the_layer_name(pallas_calls):
    """The folded conv is one Pallas kernel named after its layer, the
    name a profiler trace shows (``%<layer>.<n>`` on the chip), over
    the 27 folded lanes."""
    x, w, b = _arrays(3, 3)

    def fwd(x, w, b):
        return ops.conv2d(x, w, bias=b, activation="relu", layer="conv1")

    calls = pallas_calls(fwd, x, w, b)
    assert [name for name, _ in calls] == ["conv1"]
    assert calls[0][1][0][-1] == 27


def test_tune_network_writes_the_folded_key():
    """One sweep over a two-layer topology: the first layer (27 lanes)
    is tuned under its folded 1x1 key, the second (144 lanes) under
    its own 3x3 key; the forward looks up, and finds, the folded one."""
    topo = [ConvLayer("c1", 12, 3, 16, kernel=3, padding=1),
            ConvLayer("c2", 12, 16, 16, kernel=3, padding=1)]
    recs = autotune.tune_network(topo, n=2)
    folded = autotune.knobs_for((2, 12, 12, 27), (1, 1, 27, 16))
    assert folded is not None
    assert recs["c1"]["key"] == autotune.make_key((2, 12, 12, 27),
                                                  (1, 1, 27, 16))
    assert autotune.knobs_for((2, 14, 14, 3), (3, 3, 3, 16)) is None
    assert autotune.knobs_for((2, 14, 14, 16), (3, 3, 16, 16)) is not None

    seen = []
    real = autotune.knobs_for

    def spy(*a, **kw):
        rec = real(*a, **kw)
        seen.append((tuple(a[0]), tuple(a[1]), rec is not None))
        return rec

    x, w, _ = _arrays(3, 3, cout=16, shape=(2, 12, 12))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(autotune, "knobs_for", spy)
        ops.conv2d(x, w, layer="c1")
    assert ((2, 12, 12, 27), (1, 1, 27, 16), True) in seen


def test_tune_network_folded_backward_keys():
    """With ``include_backward`` the folded layer's cotangent records are
    keyed by the shape its 1x1 kernel sees (whole strips of rows), so
    the backward finds them."""
    topo = [ConvLayer("c1", 12, 3, 16, kernel=3, padding=1)]
    rec = autotune.tune_network(topo, n=2, include_backward=True)["c1"]
    plan = ConvPlan.build((2, 12, 12, 27), (1, 1, 27, 16),
                          tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
                          dataflow=rec["dataflow"])
    x_seen = (2, plan.rows_padded, 12, 27)
    assert autotune.weight_grad_knobs_for(x_seen, (1, 1, 27, 16)) \
        is not None


def test_engine_reports_the_folded_layers():
    """``ServingEngine.stats()["tap_folded"]`` names exactly the
    topology's folded layers, with their K*K*Cin."""
    from repro.core.serving import ServingEngine
    from repro.models import layers as mlayers
    from repro.models.base import init_params
    topo = [ConvLayer("tf0", ifmap=8, in_channels=3, out_channels=16,
                      kernel=3, stride=1, padding=1),
            ConvLayer("tf1", ifmap=8, in_channels=16, out_channels=16,
                      kernel=3, stride=1, padding=1)]
    params = init_params(mlayers.cnn_params_from_layers(topo,
                                                        n_classes=4),
                         jax.random.PRNGKey(0))
    eng = ServingEngine.for_topology(topo, params, buckets=(1, 2))
    eng.prewarm()
    assert eng.stats()["tap_folded"] == {"tf0": 27}


@pytest.mark.parametrize("net,want", [("mobilenetv1", {"conv1": 2}),
                                      ("vgg16", {})])
def test_engine_reports_the_strided_folds(net, want):
    """``ServingEngine.stats()["fold_strided"]`` names the folded convs
    whose views are taken by selection, with their stride: MobileNetV1's
    3x3/s2 stem, none of VGG-16's (its conv1 folds at stride 1)."""
    from repro.core.model import mobilenet_v1_layers
    from repro.core.netplan import network_layers
    from repro.core.serving import ServingEngine
    topo = (mobilenet_v1_layers() if net == "mobilenetv1"
            else network_layers(net))
    stats = ServingEngine.for_topology(topo, {}, buckets=(8,)).stats()
    assert stats["fold_strided"] == want
    assert stats["tap_folded"] == {"conv1": 27}


def test_folded_conv_never_fuses_and_fused_stays_bitwise():
    """A conv that folds is not fusion-eligible: the residency plan
    leaves it in a depth-1 group, fuses the wider convs after it, and
    the fused network still equals the per-layer one bit for bit."""
    from repro.core.fuse_plan import FusedGroupPlan
    from repro.models import layers as mlayers
    from repro.models.base import init_params
    topo = [ConvLayer("f0", 12, 3, 16, 3, 1, 1),      # 27 lanes: folds
            ConvLayer("f1", 12, 16, 16, 3, 1, 1),
            ConvLayer("f2", 12, 16, 16, 3, 1, 1)]
    plan = FusedGroupPlan.build(topo, n=2, residency="always")
    assert [(g.start, g.depth, g.fused) for g in plan.groups] == \
        [(0, 1, False), (1, 2, True)]
    params = init_params(mlayers.cnn_params_from_layers(topo),
                         jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.standard_normal((2, 12, 12, 3)), jnp.float32)
    per_layer = mlayers.cnn_apply_from_layers(params, topo, x)
    fused = mlayers.cnn_apply_from_layers(params, topo, x, fuse_plan=plan)
    assert jnp.array_equal(per_layer, fused)
