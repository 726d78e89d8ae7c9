"""ResNet-50 v1.5 (``core.model.resnet50_graph``) on the system's DAG
path — ``autotune.tune_graph``, then the jitted
``models/layers.cnn_apply_from_graph`` on the Pallas kernels (interpret
mode here) — against the pure-XLA graph oracle of
``tests/test_netgraph.py`` on seeded random weights at small sizes; its
full-size shapes; the MXU kernel's stride-2 taps against
``kernels/ref.py`` on both dataflows; and the ``mxu_strided`` counter."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import NetworkGraph, autotune
from repro.core.model import resnet50_graph
from repro.core.netplan import layer_route, mxu_strided
from repro.kernels import ref
from repro.kernels.trim_conv2d import make_plan, trim_conv2d
from repro.models import layers as mlayers
from repro.models.base import init_params

from test_netgraph import _close, _inputs, ref_graph_apply


def test_full_size_is_table_1():
    """53 convs (36 1x1, 16 3x3, the 7x7 stem), 56x56 after the padded
    pool, 16 adds with a ReLU, 20 convs without one (each block's last
    1x1 and the four projections), 7x7x2048 out."""
    nodes = resnet50_graph()
    convs = [nd.layer for nd in nodes if nd.op == "conv"]
    assert len(convs) == 53
    assert [sum(l.kernel == k for l in convs) for k in (1, 3, 7)] == \
        [36, 16, 1]
    assert sum(l.macs for l in convs) == 4_087_136_256
    adds = [nd for nd in nodes if nd.op == "add"]
    assert len(adds) == 16 and all(nd.relu is True for nd in adds)
    assert sum(nd.relu is False for nd in nodes if nd.op == "conv") == 20
    assert sorted(l.name for l in convs if l.stride == 2) == \
        ["conv1", "s3b1b", "s3b1p", "s4b1b", "s4b1p", "s5b1b", "s5b1p"]
    gp = NetworkGraph.build(nodes)
    sizes = {s.name: s.out_size for s in gp.steps}
    assert sizes["conv1"] == 112 and sizes["pool1"] == 56
    assert (sizes["s2b3"], sizes["s3b4"], sizes["s4b6"], sizes["s5b3"]) \
        == (56, 28, 14, 7)
    assert gp.steps[-1].channels == 2048


@pytest.mark.parametrize("image,base", [(32, 8), (48, 8)])
def test_small_resnet50_matches_oracle(image, base):
    """Tuned, jitted, on the Pallas kernels: the logits equal the
    oracle's (its features through the same mean-pool linear head)."""
    nodes = resnet50_graph(image=image, base=base)
    x = _inputs(nodes, n=2, seed=image)
    autotune.tune_graph(nodes, n=x.shape[0])
    p = init_params(mlayers.cnn_params_from_graph(nodes, n_classes=10),
                    jax.random.PRNGKey(image))
    got = jax.jit(lambda p_, x_: mlayers.cnn_apply_from_graph(
        p_, nodes, x_))(p, x)
    feats = ref_graph_apply(p, nodes, x)
    want = feats.mean(axis=(1, 2)) @ p["head"]["w"] + p["head"]["b"]
    assert got.shape == (2, 10)
    _close(got, want)


# (K, size, C_in, C_out, tile_h): the stem's 3 channels, two lane
# chunks with zero padding (160), two whole chunks (256); several strips
@pytest.mark.parametrize("k,hw,cin,cout,tile_h", [(7, 23, 3, 16, 4),
                                                  (3, 17, 160, 32, 4),
                                                  (1, 15, 256, 64, 2)])
@pytest.mark.parametrize("dataflow", ["carry", "halo"])
def test_stride2_taps_match_ref(k, hw, cin, cout, tile_h, dataflow):
    """The MXU kernel at stride 2 reads its taps with strided loads,
    the carry dataflow from a staged window whose top K-1 rows cross
    the strips: equal to ``ref.conv2d`` at K = 1, 3, 7."""
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.standard_normal((2, hw, hw, cin)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, k, cin, cout)) / k,
                    jnp.float32)
    b = jnp.asarray(rng.standard_normal(cout), jnp.float32)
    plan = make_plan(x.shape, w.shape, stride=2, pad=k // 2,
                     tile_h=tile_h, dataflow=dataflow)
    assert plan.g_tiles > 1
    got = trim_conv2d(x, w, b, stride=2, pad=k // 2, tile_h=tile_h,
                      dataflow=dataflow, activation="relu", interpret=True)
    want = ref.conv2d(x, w, stride=2, padding=k // 2, bias=b,
                      activation="relu")
    _close(got, want)


def test_mxu_strided_counter():
    """``netplan.mxu_strided``: the stride-2 convs whose route is the
    MXU kernel, with their strides, from the graph's nodes; the same
    rule ``ServingEngine.stats()`` reads for a chain."""
    from repro.core.model import ConvLayer
    from repro.core.serving import Replica, ServingEngine
    nodes = resnet50_graph()
    want = {l.name: 2 for l in (nd.layer for nd in nodes if nd.op == "conv")
            if l.stride == 2 and layer_route(l).kind == "mxu"}
    assert mxu_strided(nodes) == want
    assert set(want) >= {"s3b1b", "s3b1p", "s4b1b", "s4b1p", "s5b1b",
                         "s5b1p"}
    chain = [ConvLayer("a", 16, 128, 128, kernel=3, stride=2, padding=1),
             ConvLayer("b", 8, 128, 128, kernel=3, padding=1)]
    eng = ServingEngine([Replica("r0", lambda x: x)], (1,), topo=chain)
    assert eng.stats()["mxu_strided"] == {"a": 2}
