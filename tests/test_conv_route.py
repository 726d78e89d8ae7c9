"""The conv route (DESIGN.md §16): ``ops.conv_route`` is the one place
that decides which kernel runs a conv, and the tuner writes exactly the
autotune keys the forward reads.

* the kind of every conv of the benchmark's four networks as the
  program builds them (VGG-16, U-Net 512²/64/depth 4, MobileNetV1 224²,
  ResNet-50 v1.5 224²) and of AlexNet's K = 11 conv1;
* per network, at batch 8: the keys ``tune_network`` / ``tune_graph``
  write equal the keys the forward looks up while it is traced.
"""

import json

import jax
import jax.numpy as jnp
import pytest

from repro.core import autotune
from repro.core.model import mobilenet_v1_layers, resnet50_graph, \
    unet_graph
from repro.core.netplan import layer_kernel_problem, network_layers
from repro.kernels import ops

UNET = unet_graph(512, 64, 1, 2, 4)
RESNET50 = resnet50_graph()

NETWORKS = {
    "vgg16": network_layers("vgg16"),
    "unet": [nd.layer for nd in UNET if nd.op == "conv"],
    "mobilenetv1": mobilenet_v1_layers(),
    "resnet50": [nd.layer for nd in RESNET50 if nd.op == "conv"],
    "alexnet": network_layers("alexnet")[:1],
}

# the kinds stated by hand; every other conv of the table runs on the
# MXU strip kernel (ResNet-50's 3x3/s2 convs and 1x1/s2 projections with
# strided taps); ResNet-50's 7x7/s2 stem folds into two lane tiles
KINDS = {("vgg16", "conv1"): "folded", ("unet", "enc0a"): "folded",
         ("mobilenetv1", "conv1"): "folded", ("alexnet", "conv1"): "tiled",
         ("resnet50", "conv1"): "folded",
         **{("mobilenetv1", f"dw{i}"): "depthwise" for i in range(1, 14)}}

CASES = [(net, layer.name) for net, layers in NETWORKS.items()
         for layer in layers]


@pytest.mark.parametrize("net,name", CASES)
def test_benchmark_convs_take_their_route(net, name):
    layer = next(l for l in NETWORKS[net] if l.name == name)
    x_shape, _, w_shape, _ = layer_kernel_problem(layer, n=8)
    route = ops.conv_route(x_shape, w_shape, stride=layer.stride,
                           groups=layer.groups, dtype="float32")
    kind = KINDS.get((net, name), "mxu")
    assert route.kind == kind
    key = route.key("float32", "cpu")
    if kind == "folded":
        taps = layer.kernel ** 2 * layer.in_channels
        h = layer.out_size
        assert (route.x_shape, route.w_shape, route.stride) == \
            ((8, h, h, taps), (1, 1, taps, layer.out_channels), 1)
        assert key == autotune.make_key(route.x_shape, route.w_shape,
                                        backend="cpu")
    elif kind == "depthwise":
        assert (route.x_shape, route.w_shape) == (x_shape, w_shape)
        assert key == autotune.depthwise_key(
            x_shape, layer.kernel, stride=layer.stride, backend="cpu")
    elif kind == "mxu":
        assert (route.x_shape, route.w_shape, route.stride) == \
            (x_shape, w_shape, layer.stride)
        assert key == autotune.make_key(x_shape, w_shape,
                                        stride=layer.stride,
                                        groups=layer.groups, backend="cpu")
    else:
        assert key is None and route.knobs("float32") is None


def _forward(net):
    """``(tune(n), fwd(p, x), params)`` of one network as the program
    builds and tunes it."""
    from repro.models import layers as mlayers
    graphs = {"unet": UNET, "resnet50": RESNET50}
    if net in graphs:
        g = graphs[net]
        return (lambda n: autotune.tune_graph(g, n=n),
                lambda p, x: mlayers.cnn_apply_from_graph(p, g, x),
                mlayers.cnn_params_from_graph(g))
    topo = NETWORKS[net]
    return (lambda n: autotune.tune_network(topo, n=n),
            lambda p, x: mlayers.cnn_apply_from_layers(p, topo, x),
            mlayers.cnn_params_from_layers(topo))


@pytest.mark.parametrize("net", ["vgg16", "unet", "mobilenetv1",
                                 "resnet50"])
def test_tuned_keys_are_the_keys_the_forward_reads(monkeypatch, net):
    """At batch 8, the set of keys the tuner writes equals the set the
    traced forward looks up: no record is written where no conv reads
    it, and no conv reads where nothing was written."""
    from repro.models.base import init_params
    tune, fwd, decls = _forward(net)
    tune(8)
    with open(autotune.cache_path()) as f:
        written = set(json.load(f)["entries"])
    read = []
    real = autotune.lookup

    def spy(key, path=None):
        read.append(key)
        return real(key, path)

    monkeypatch.setattr(autotune, "lookup", spy)
    p = jax.eval_shape(lambda: init_params(decls, jax.random.PRNGKey(0)))
    size, cin = NETWORKS[net][0].ifmap, NETWORKS[net][0].in_channels
    x = jax.ShapeDtypeStruct((8, size, size, cin), jnp.float32)
    jax.eval_shape(fwd, p, x)
    assert written and set(read) == written
