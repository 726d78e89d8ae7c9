"""The work counts (``cellspec.NetSpec``) against figures worked out by
hand from the published networks."""

import pytest

import cellspec

VGG = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/vgg16-f32.json")
UNET = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/unet-f32.json")


def macs(size, cin, cout, k=3):
    return size * size * cin * cout * k * k


def test_vgg16_work():
    spec = cellspec.NetSpec(VGG)
    # (output size, C_in, C_out) of the 13 convs of configuration D
    convs = [(224, 3, 64), (224, 64, 64), (112, 64, 128), (112, 128, 128),
             (56, 128, 256), (56, 256, 256), (56, 256, 256), (28, 256, 512),
             (28, 512, 512), (28, 512, 512), (14, 512, 512), (14, 512, 512),
             (14, 512, 512)]
    want = sum(macs(*c) for c in convs)
    assert spec.conv_flops_per_image == 2 * want
    assert want / 1e9 == pytest.approx(15.35, abs=0.005)
    conv_params = sum(9 * ci * co + co for _, ci, co in convs)
    assert conv_params / 1e6 == pytest.approx(14.71, abs=0.005)
    assert spec.n_params == conv_params + 512 * 1000 + 1000
    assert spec.out_shape == (1000,)
    # least bytes of a step: input, conv weights and biases, logits (f32)
    assert spec.step_bytes(32) == 4 * (32 * 224 * 224 * 3 + conv_params
                                       + 32 * 1000)


def test_unet_work():
    spec = cellspec.NetSpec(UNET)
    enc = [(512, 1, 64), (512, 64, 64), (256, 64, 128), (256, 128, 128),
           (128, 128, 256), (128, 256, 256), (64, 256, 512), (64, 512, 512),
           (32, 512, 1024), (32, 1024, 1024)]
    # per level: channel-halving conv after the upsample, then two convs
    # over the concat with the skip
    dec = []
    for size, c in [(64, 512), (128, 256), (256, 128), (512, 64)]:
        dec += [(size, 2 * c, c), (size, 2 * c, c), (size, c, c)]
    want = sum(macs(*c) for c in enc + dec) + macs(512, 64, 2, k=1)
    assert spec.conv_flops_per_image == 2 * want
    assert want / 1e9 == pytest.approx(261.1, abs=0.05)
    assert len(spec.convs) == 23
    assert spec.n_params / 1e6 == pytest.approx(34.5, abs=0.05)
    assert spec.out_shape == (512, 512, 2)
    assert spec.step_bytes(8) == 4 * (8 * 512 * 512 + spec.n_params
                                      + 8 * 512 * 512 * 2)


def test_roofline_bound_takes_the_larger_term():
    spec = cellspec.NetSpec(VGG)
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert spec.conv_step_bound_s(32, peak) == pytest.approx(
        32 * spec.conv_flops_per_image / 197e12)
    slow_flops = {"flops": 1e30, "hbm_bytes_per_s": 819e9}
    assert spec.conv_step_bound_s(1, slow_flops) == pytest.approx(
        spec.step_bytes(1) / 819e9)


def test_peaks_table_is_keyed_by_device_kind():
    peak = cellspec.peak_for("TPU v5 lite", "float32")
    assert peak == {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        cellspec.peak_for("TPU v9 imaginary", "float32")


def test_shapes_are_checked():
    bad = dict(VGG, nodes=VGG["nodes"] + [{"name": "conv1", "op": "conv",
                                           "k": 3, "pad": 1, "cout": 8}])
    with pytest.raises(ValueError, match="duplicate"):
        cellspec.NetSpec(bad)
    cat = dict(UNET, nodes=UNET["nodes"][:3] + [
        {"name": "c", "op": "concat", "in": ["enc0b", "pool0"]}])
    with pytest.raises(ValueError, match="concat of sizes"):
        cellspec.NetSpec(cat)


def resnet50_v15():
    """ResNet-50 v1.5 in the configuration format: Table 1's 50-layer
    column of arXiv:1512.03385 with each stage's stride on the
    bottleneck's 3x3 conv (torchvision's ``resnet50``), batch norm
    folded into one bias per conv."""
    nodes = [{"name": "conv1", "op": "conv", "k": 7, "stride": 2, "pad": 3,
              "cout": 64},
             {"name": "pool1", "op": "pool", "window": 3, "stride": 2,
              "pad": 1}]
    prev = "pool1"
    for stage, (blocks, width) in enumerate([(3, 64), (4, 128), (6, 256),
                                             (3, 512)]):
        for i in range(blocks):
            s = 2 if i == 0 and stage > 0 else 1
            b = f"s{stage + 2}b{i + 1}"
            nodes += [
                {"name": f"{b}a", "op": "conv", "in": [prev], "k": 1,
                 "cout": width},
                {"name": f"{b}b", "op": "conv", "k": 3, "stride": s,
                 "pad": 1, "cout": width},
                {"name": f"{b}c", "op": "conv", "k": 1, "cout": 4 * width,
                 "relu": False}]
            short = prev
            if i == 0:
                short = f"{b}p"
                nodes.append({"name": short, "op": "conv", "in": [prev],
                              "k": 1, "stride": s, "cout": 4 * width,
                              "relu": False})
            nodes.append({"name": b, "op": "add", "in": [f"{b}c", short],
                          "relu": True})
            prev = b
    return {"name": "resnet50-v1.5", "dtype": "float32", "reference": "cnn",
            "input": {"size": 224, "channels": 3}, "nodes": nodes,
            "head": {"classes": 1000}}


def test_resnet50_work():
    spec = cellspec.NetSpec(resnet50_v15())
    assert spec.by_name["pool1"].size == 56
    last = spec.nodes[-1]
    assert (last.op, last.size, last.channels, last.relu) == \
        ("add", 7, 2048, True)
    convs = spec.convs
    assert len(convs) == 53
    assert sorted({n.k for n in convs}) == [1, 3, 7]
    assert [sum(n.k == k for n in convs) for k in (1, 3, 7)] == [36, 16, 1]
    assert sum(not n.relu for n in convs) == 16 + 4
    assert spec.conv_flops_per_image == 2 * 4_087_136_256
    assert sum(n.n_params for n in convs) == 23_481_472
    assert spec.n_params == 25_530_472
    assert spec.out_shape == (1000,)


def _net(*nodes):
    return {"name": "t", "dtype": "float32", "reference": "cnn",
            "input": {"size": 112, "channels": 8}, "nodes": list(nodes)}


STEM = {"name": "c0", "op": "conv", "k": 3, "pad": 1, "cout": 8}


@pytest.mark.parametrize("pad,size", [(0, 55), (1, 56)])
def test_padded_pool_size(pad, size):
    spec = cellspec.NetSpec(_net(STEM, {"name": "p", "op": "pool",
                                        "window": 3, "stride": 2,
                                        "pad": pad}))
    node = spec.by_name["p"]
    assert (node.size, node.channels, node.pad) == (size, 8, pad)


def test_add_and_relu_defaults():
    spec = cellspec.NetSpec(_net(
        STEM, {"name": "c1", "op": "conv", "k": 1, "cout": 8, "relu": False},
        {"name": "a", "op": "add", "in": ["c0", "c1"]},
        {"name": "r", "op": "add", "in": ["a", "c0"], "relu": True}))
    got = {n.name: (n.relu, n.size, n.channels, n.flops, n.n_params)
           for n in spec.nodes}
    assert got["c0"][0] is True and got["c1"][0] is False
    assert got["a"] == (False, 112, 8, 0, 0)
    assert got["r"] == (True, 112, 8, 0, 0)


@pytest.mark.parametrize("bad,match", [
    ({"name": "bad", "op": "add", "in": ["c0", "wide"]}, "add of shapes"),
    ({"name": "bad", "op": "add", "in": ["c0", "half"]}, "add of shapes"),
    ({"name": "bad", "op": "add", "in": ["half"]}, "two or more"),
    ({"name": "bad", "op": "pool", "window": 2, "stride": 2, "relu": True},
     "takes no"),
    ({"name": "bad", "op": "concat", "in": ["c0", "wide"], "relu": False},
     "takes no"),
    ({"name": "bad", "op": "conv", "k": 1, "cout": 8, "relu": 0},
     "not true or false"),
    ({"name": "bad", "op": "pool", "window": 3, "stride": 2, "pad": 2},
     "pool pad"),
    ({"name": "bad", "op": "pool", "window": 2, "stride": 2, "pad": -1},
     "pool pad"),
])
def test_malformed_nodes_are_refused_by_name(bad, match):
    net = _net(STEM,
               {"name": "wide", "op": "conv", "in": ["c0"], "k": 1,
                "cout": 16},
               {"name": "half", "op": "conv", "in": ["c0"], "k": 1,
                "stride": 2, "cout": 8},
               bad)
    with pytest.raises(ValueError, match=f"^bad: .*{match}"):
        cellspec.NetSpec(net)


@pytest.mark.parametrize("node", [
    {"name": "bad", "op": "conv", "k": 1, "cout": 8, "relu": False},
    {"name": "bad", "op": "pool", "window": 3, "stride": 2, "pad": 1},
    {"name": "bad", "op": "add", "in": ["c0", "c1"]},
])
def test_chain_refuses_what_it_cannot_serve(node):
    net = _net(STEM, {"name": "c1", "op": "conv", "k": 1, "cout": 8}, node)
    sys_engine = cellspec.load_module("systems", "engine")
    with pytest.raises(ValueError, match="node bad "):
        sys_engine.chain_layers(cellspec.NetSpec(net))


def test_configurations_are_the_programs_networks():
    """The configurations describe the networks the program builds by
    name: VGG-16's conv chain and the U-Net graph of ``unet_graph``."""
    from repro.core.model import unet_graph
    from repro.core.netplan import network_layers

    sys_engine = cellspec.load_module("systems", "engine")
    mine = sys_engine.chain_layers(cellspec.NetSpec(VGG))
    theirs = network_layers("vgg16")
    assert [(l.ifmap, l.in_channels, l.out_channels, l.kernel, l.stride,
             l.padding) for l in mine] == \
        [(l.ifmap, l.in_channels, l.out_channels, l.kernel, l.stride,
          l.padding) for l in theirs]

    sys_graph = cellspec.load_module("systems", "graph")
    mine = sys_graph.graph_nodes(cellspec.NetSpec(UNET))
    theirs = unet_graph(image=512, base=64, in_channels=1, out_channels=2,
                        depth=4)

    def key(n):
        shape = None if n.layer is None else (
            n.layer.ifmap, n.layer.in_channels, n.layer.out_channels,
            n.layer.kernel, n.layer.stride, n.layer.padding)
        # relu / pool_pad: the graph adapter's fields for a departure
        # from the executor's defaults (None: the op's default)
        return (n.name, n.op, tuple(n.inputs), shape, n.pool,
                n.pool_window, n.scale, getattr(n, "relu", None),
                getattr(n, "pool_pad", 0))
    assert [key(n) for n in mine] == [key(n) for n in theirs]
