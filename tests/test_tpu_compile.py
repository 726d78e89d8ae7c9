"""Ahead-of-time compiles of the TrIM kernels for a described TPU v5e.

Interpret mode (every other test) never reaches Mosaic, so only a
compile for the chip catches a block the compiler cannot tile, a plan
that overflows VMEM, or a Pallas API the installed JAX no longer has.
These tests compile, for one chip of a ``v5e:2x2`` topology described
without the chip, the kernels of the VGG-16 serving path at published
widths (with the default and the tuner-chosen plans), the weight-grad
kernel and the int8 kernel.  Nothing runs: they prove compilation, not
results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and pytest-xdist workers import every
test file.
"""

import os

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


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="Mosaic: 'Only 2D gather is supported' (strided "
                          "tap slices, trim_conv2d._tap_matmuls)")
def test_stride2_7x7_stem_compiles(one_chip):
    x = jax.ShapeDtypeStruct((1, 229, 229, 3), jnp.float32,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((7, 7, 3, 64), jnp.float32, sharding=one_chip)
    _compile(lambda x, w: trim_conv2d(x, w, stride=2, interpret=False),
             x, w)
