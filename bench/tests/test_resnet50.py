"""The ``resnet50-f32`` configuration against the program and the plain
reference, on the CPU: its topology (the program's ``resnet50_graph``),
its work counts, the cell's rehearsal (correct as configured, not
correct with a ReLU on the convs that have none), and the convs
``conv_roofline.strided`` reads."""

import time

import pytest

import cellspec
import harness

NAME = "resnet50-f32"
CFG = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/{NAME}.json")


def _key(n):
    shape = None if n.layer is None else (
        n.layer.ifmap, n.layer.in_channels, n.layer.out_channels,
        n.layer.kernel, n.layer.stride, n.layer.padding)
    return (n.name, n.op, tuple(n.inputs), shape, n.pool, n.pool_window,
            n.scale, n.relu, n.pool_pad)


@pytest.mark.parametrize("image,divisor", [(224, 1), (32, 16)])
def test_configuration_is_the_programs_network(image, divisor):
    """Node for node, departures included, the configuration is the
    program's ``resnet50_graph``, at full size and at the rehearsal's."""
    from repro.core.model import resnet50_graph
    from repro.core.netplan import scale_graph
    graph = cellspec.load_module("systems", "graph")
    spec = cellspec.NetSpec(CFG, image=image, channel_divisor=divisor)
    mine = graph.graph_nodes(spec)
    theirs = scale_graph(resnet50_graph(image=image), divisor)
    assert [_key(n) for n in mine] == [_key(n) for n in theirs]


def test_work_is_table_1():
    spec = cellspec.NetSpec(CFG)
    assert len(spec.convs) == 53
    assert spec.conv_flops_per_image == 8_174_272_512
    assert spec.n_params == 25_530_472
    assert spec.by_name["pool1"].size == 56
    assert spec.out_shape == (1000,)
    assert sum(not n.relu for n in spec.convs) == 20
    assert all(n.relu for n in spec.nodes if n.op == "add")


def test_strided_reader_picks_the_seven_stride_2_convs():
    reader = cellspec.load_module("metrics", "conv_roofline.strided")
    assert sorted(n.name for n in reader.strided_convs(
        cellspec.NetSpec(CFG))) == \
        ["conv1", "s3b1b", "s3b1p", "s4b1b", "s4b1p", "s5b1b", "s5b1p"]
    cell = cellspec.find_cell(f"{NAME}.batch128")
    names = {m["name"] for m in cell.per_layer}
    assert {"conv_roofline.strided", "conv_roofline.pointwise",
            "conv_roofline", "step_mfu", "executor.nonconv_ms_per_image",
            "device_idle.batch"} <= names
    assert "conv_roofline.full_res" not in names


def _graph_with_relu_on_every_conv(load):
    """The graph system with the convs' ``relu=False`` dropped: every
    conv then takes the executor's ReLU."""
    import dataclasses
    mod = load("systems", "graph")
    nodes_of = mod.graph_nodes
    mod.graph_nodes = lambda spec: [
        dataclasses.replace(nd, relu=None) if nd.op == "conv" else nd
        for nd in nodes_of(spec)]
    return mod


@pytest.mark.parametrize("relu_on_every_conv", [False, True])
def test_rehearsal_of_the_cell(monkeypatch, relu_on_every_conv):
    """The cell end to end at its rehearsal size: correct as configured;
    with a ReLU on the convs that have none, not correct."""
    load = cellspec.load_module

    def load_module(kind, name, *args, **kw):
        if relu_on_every_conv and (kind, name) == ("systems", "graph"):
            return _graph_with_relu_on_every_conv(load)
        return load(kind, name, *args, **kw)
    monkeypatch.setattr(cellspec, "load_module", load_module)
    cell = cellspec.find_cell(f"{NAME}.batch128")
    res = harness.run(cell, seed=2**33 + 7, seconds=0.2, trace=False,
                      rehearse=True, t_start=time.monotonic(),
                      log=lambda m: None)
    err = res["checks"]["max_rel_err"]
    if relu_on_every_conv:
        assert res["correct"] is False
        assert err["value"] > 100 * err["limit"]
    else:
        assert res["correct"], res["checks"]
        assert res["failed"] == 0 and res["attempted"] >= 128
