"""Ahead-of-time compiles of the TrIM kernels for a described TPU v5e.

Interpret mode (every other test) never reaches Mosaic, so only a
compile for the chip catches a block the compiler cannot tile, a plan
that overflows VMEM, or a Pallas API the installed JAX no longer has.
These tests compile, for one chip of a ``v5e:2x2`` topology described
without the chip, the kernels of the VGG-16 serving path at published
widths (with the default and the tuner-chosen plans), the tap-folded
first convs of VGG-16 and U-Net, MobileNetV1's depthwise convs on the
VPU kernel, ResNet-50's stride-2 convs (strided taps), the weight-grad
kernel and the int8 kernel.  Nothing runs: they prove compilation, not
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers import every
test file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune
from repro.core.fuse_plan import FusedGroupPlan
from repro.core.netplan import network_layers
from repro.kernels.trim_conv2d import trim_conv2d, trim_conv2d_weight_grad
from repro.kernels.trim_conv2d_fused import fused_group_apply


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def native(monkeypatch):
    """Kernels lowered natively (``runtime.on_tpu`` is True) on a clean
    trace cache.  The jitted kernels take ``interpret=None`` and resolve
    it while they are traced, so a problem an earlier test of the same
    process traced on the CPU would come back from JAX's cache as an
    interpreted kernel (no ``tpu_custom_call`` in the program); the
    caches are cleared before the test and after it."""
    from repro.kernels import runtime
    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; the Pallas kernel must be
    in the program (a compile that dropped it would prove nothing)."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# VGG-16 layers at 224x224 as the kernel sees them ('same' pre-padded)
VGG16 = {
    "conv1_1": (1, 226, 3, 64),
    "conv1_2": (8, 226, 64, 64),
    "conv3_2": (8, 58, 256, 256),
    "conv5_1": (1, 16, 512, 512),
}


@pytest.mark.parametrize("layer", sorted(VGG16))
@pytest.mark.parametrize("plan", ["default", "tuned"])
def test_vgg16_conv_compiles(one_chip, layer, plan):
    n, hw, cin, cout = VGG16[layer]
    x_shape, w_shape = (n, hw, hw, cin), (3, 3, cin, cout)
    knobs = {}
    if plan == "tuned":
        rec = autotune.tune(x_shape, w_shape, write=False)
        knobs = dict(tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
                     dataflow=rec["dataflow"])
    x = jax.ShapeDtypeStruct(x_shape, jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct(w_shape, jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((cout,), jnp.float32, sharding=one_chip)
    _compile(lambda x, w, b: trim_conv2d(x, w, b, activation="relu",
                                         interpret=False, **knobs), x, w, b)


def test_vgg16_halo_conv_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1, 114, 114, 128), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, 3, 128, 128), jnp.float32,
                             sharding=one_chip)
    _compile(lambda x, w: trim_conv2d(x, w, dataflow="halo",
                                      interpret=False), x, w)


def test_vgg16_weight_grad_compiles(one_chip):
    x = jax.ShapeDtypeStruct((2, 58, 58, 256), jnp.float32,
                             sharding=one_chip)
    g = jax.ShapeDtypeStruct((2, 56, 56, 256), jnp.float32,
                             sharding=one_chip)
    _compile(lambda x, g: trim_conv2d_weight_grad(
        x, g, kernel_size=(3, 3), interpret=False), x, g)


def test_q8_conv_compiles(one_chip):
    s = one_chip
    x = jax.ShapeDtypeStruct((2, 58, 58, 128), jnp.int8, sharding=s)
    w = jax.ShapeDtypeStruct((3, 3, 128, 256), jnp.int8, sharding=s)
    b = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=s)
    scale = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=s)
    _compile(lambda x, w, b, scale: trim_conv2d(
        x, w, b, scale, activation="relu", interpret=False),
        x, w, b, scale)


@pytest.mark.parametrize("dataflow", ["carry", "halo"])
def test_conv_kernel_carries_its_layer_name(one_chip, native, dataflow):
    """``ops.conv2d(layer=...)`` lowered for the chip names its Pallas
    kernel after the layer: the compiled custom call is ``%<layer>.<n>``,
    the text a profiler trace shows for the kernel."""
    from repro.kernels import ops
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)
    layer = f"enc0{dataflow}"

    def fwd(x, w, b):
        return ops.conv2d(x, w, bias=b, activation="relu",
                          dataflow=dataflow, layer=layer)

    text = jax.jit(fwd).lower(sds((2, 32, 32, 16)), sds((3, 3, 16, 128)),
                              sds((128,))).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip() for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels and all(k.startswith(f"%{layer}.") for k in kernels), \
        kernels


# first convs whose taps fold into the lanes (ops.conv_route), as their
# layers see them: (layer, batch, size, C_in, C_out, stride)
FOLDED = {
    "vgg16_conv1_b32": ("conv1", 32, 224, 3, 64, 1),
    "vgg16_conv1_b1": ("conv1", 1, 224, 3, 64, 1),
    "unet_enc0a_b8": ("enc0a", 8, 512, 1, 64, 1),
    "mobilenetv1_conv1_b8": ("conv1", 8, 224, 3, 32, 2),
}


@pytest.mark.parametrize("case", sorted(FOLDED))
@pytest.mark.parametrize("plan", ["default", "tuned"])
def test_folded_first_conv_compiles(one_chip, native, pallas_calls, case,
                                    plan):
    """The tap-folded first conv (XLA im2col, then the 1x1 TrIM kernel)
    compiles at published size with the default and the tuner-chosen
    plan, and its one kernel keeps the layer's name.  At stride 2 no
    slice of the compiled program has a stride on the last (lane) axis:
    the views are selection contractions."""
    from repro.core.model import ConvLayer
    from repro.kernels import ops
    layer, n, hw, cin, cout, stride = FOLDED[case]
    if plan == "tuned":
        autotune.tune_network([ConvLayer(layer, hw, cin, cout, kernel=3,
                                         stride=stride, padding=1)], n=n)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)

    def fwd(x, w, b):
        return ops.conv2d(x, w, stride=stride, padding=1, bias=b,
                          activation="relu", layer=layer)

    args = (sds((n, hw, hw, cin)), sds((3, 3, cin, cout)), sds((cout,)))
    text = jax.jit(fwd).lower(*args).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip() for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1 and kernels[0].startswith(f"%{layer}."), \
        kernels
    [(_, (xs, *_))] = pallas_calls(fwd, *args)
    assert xs[-1] == 9 * cin            # the 1x1 over the folded lanes
    assert "gather(" not in text
    assert not re.search(r"slice=\{[^}]*:2\]\}", text)


# MobileNetV1's depthwise convs as their layers see them, at the
# benchmark's batch: (layer, batch, size, C, stride)
DEPTHWISE = {
    "dw1_b128": ("dw1", 128, 112, 32, 1),
    "dw2_b128": ("dw2", 128, 112, 64, 2),
    "dw12_b128": ("dw12", 128, 14, 512, 2),
}


@pytest.mark.parametrize("case", sorted(DEPTHWISE))
@pytest.mark.parametrize("plan", ["default", "tuned"])
def test_depthwise_conv_compiles(one_chip, native, pallas_calls, case,
                                 plan):
    """A MobileNetV1 depthwise conv through ``ops.conv2d`` compiles for
    the chip on the VPU kernel, strided loads and all, with the default
    and the tuner-chosen plan: one kernel, named after its layer, and
    no XLA convolution."""
    from repro.core.model import ConvLayer
    from repro.kernels import ops
    layer, n, hw, c, stride = DEPTHWISE[case]
    if plan == "tuned":
        autotune.tune_network([ConvLayer(layer, hw, c, c, kernel=3,
                                         stride=stride, padding=1,
                                         groups=c)], n=n)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)

    def fwd(x, w, b):
        return ops.conv2d(x, w, stride=stride, padding=1,
                          feature_group_count=c, bias=b, activation="relu",
                          layer=layer)

    args = (sds((n, hw, hw, c)), sds((3, 3, 1, c)), sds((c,)))
    text = jax.jit(fwd).lower(*args).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip() for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1 and kernels[0].startswith(f"%{layer}."), \
        kernels
    assert " convolution(" not in text
    [(_, shapes)] = pallas_calls(fwd, *args)
    assert (9, c) in shapes             # the VPU kernel's (K*K, C) taps


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Only 2D gather is supported' (the "
                          "megakernel's strided max-pool slices)")
def test_fused_vgg16_group_compiles(one_chip):
    group = FusedGroupPlan.build(network_layers("vgg16"), n=1).groups[1]
    assert group.fused
    s0 = group.stages[0]
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)
    x = sds((1, s0.h_in, s0.w_in, s0.cin))
    ws = [sds(st.weight_shape) for st in group.stages]
    bs = [sds((st.cout,)) for st in group.stages]
    _compile(lambda x, ws, bs: fused_group_apply(
        x, ws, bs, group=group, interpret=False), x, ws, bs)


def test_stride2_7x7_stem_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1, 229, 229, 3), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((7, 7, 3, 64), jnp.float32, sharding=one_chip)
    _compile(lambda x, w: trim_conv2d(x, w, stride=2, interpret=False),
             x, w)


# ResNet-50 v1.5's seven stride-2 convs at the benchmark's batch, as
# their layers state them: (layer, batch, size, C_in, C_out, K, pad)
STRIDED = {
    "conv1": ("conv1", 128, 224, 3, 64, 7, 3),
    "s3b1b": ("s3b1b", 128, 56, 128, 128, 3, 1),
    "s4b1b": ("s4b1b", 128, 28, 256, 256, 3, 1),
    "s5b1b": ("s5b1b", 128, 14, 512, 512, 3, 1),
    "s3b1p": ("s3b1p", 128, 56, 256, 512, 1, 0),
    "s4b1p": ("s4b1p", 128, 28, 512, 1024, 1, 0),
    "s5b1p": ("s5b1p", 128, 14, 1024, 2048, 1, 0),
}


@pytest.mark.parametrize("case", sorted(STRIDED))
@pytest.mark.parametrize("plan", ["default", "tuned"])
def test_resnet50_strided_conv_compiles(one_chip, native, case, plan):
    """Each stride-2 conv of ResNet-50 through ``ops.conv2d`` compiles
    for the chip on the route ``ops.conv_route`` gives it, with the
    default and the tuner-chosen plan: one kernel, named after its
    layer, and no gather.  The six on the ``mxu`` route (strided taps)
    have no XLA convolution; the stem folds (147 lanes, two lane tiles
    at stride 2), and its only two are the selection contractions of
    its views."""
    from repro.core.model import ConvLayer
    from repro.core.netplan import layer_route
    from repro.kernels import ops
    layer, n, hw, cin, cout, k, pad = STRIDED[case]
    kind = layer_route(ConvLayer(layer, hw, cin, cout, kernel=k, stride=2,
                                 padding=pad), n=n).kind
    assert kind == ("folded" if layer == "conv1" else "mxu")
    if plan == "tuned":
        autotune.tune_network([ConvLayer(layer, hw, cin, cout, kernel=k,
                                         stride=2, padding=pad)], n=n)
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)

    def fwd(x, w, b):
        return ops.conv2d(x, w, stride=2, padding=pad, bias=b,
                          activation="relu", layer=layer)

    text = jax.jit(fwd).lower(sds((n, hw, hw, cin)), sds((k, k, cin, cout)),
                              sds((cout,))).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip() for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(kernels) == 1 and kernels[0].startswith(f"%{layer}."), \
        kernels
    assert text.count(" convolution(") == (2 if kind == "folded" else 0)
    assert "gather(" not in text


@pytest.mark.parametrize("k,hw,cin,cout", [(1, 56, 256, 512),
                                           (3, 58, 128, 128),
                                           (7, 230, 3, 64)])
@pytest.mark.parametrize("dataflow", ["carry", "halo"])
def test_stride2_taps_compile(one_chip, k, hw, cin, cout, dataflow):
    """The MXU kernel's strided taps on both dataflows at K = 1, 3, 7:
    the window is read by strided ref loads, which the chip's compiler
    takes where it refuses a strided slice of a loaded value."""
    sds = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa
                                             sharding=one_chip)
    _compile(lambda x, w, b: trim_conv2d(x, w, b, stride=2,
                                         activation="relu",
                                         dataflow=dataflow,
                                         interpret=False),
             sds((8, hw, hw, cin)), sds((k, k, cin, cout)), sds((cout,)))


def test_resnet50_kernels_carry_their_layer_names(one_chip, native):
    """ResNet-50 v1.5 at published widths, tuned and compiled for the
    chip as one program: 53 Pallas kernels, one per conv, each named
    after its layer (what a profiler trace reads); its only XLA
    convolutions are the folded stem's two selection contractions."""
    from repro.core.model import resnet50_graph
    from repro.models import layers as mlayers
    from repro.models.base import init_params
    nodes = resnet50_graph()
    autotune.tune_graph(nodes, n=8)
    decls = mlayers.cnn_params_from_graph(nodes, n_classes=1000)
    p = jax.eval_shape(lambda: init_params(decls, jax.random.PRNGKey(0)))
    p = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=one_chip), p)
    x = jax.ShapeDtypeStruct((8, 224, 224, 3), jnp.float32,
                             sharding=one_chip)
    text = jax.jit(lambda p, x: mlayers.cnn_apply_from_graph(
        p, nodes, x)).lower(p, x).compile().as_text()
    kernels = [ln.split("=", 1)[0].strip().lstrip("%").rsplit(".", 1)[0]
               for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert sorted(kernels) == sorted(nd.name for nd in nodes
                                     if nd.op == "conv")
    assert text.count(" convolution(") == 2
