"""The ``mobilenetv1-f32`` configuration against the program and the
plain reference, on the CPU: its work counts, its topology (the
program's ``mobilenet_v1_layers``), the engine serving it at 32x32 and
full widths against ``bench/reference/cnn.py``, and the control (the
reference one precision lower) over the configuration's limit."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import cellspec
import harness
import inputs

NAME = "mobilenetv1-f32"
CFG = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/{NAME}.json")


def _key(layer):
    return (layer.name, layer.ifmap, layer.in_channels, layer.out_channels,
            layer.kernel, layer.stride, layer.padding, layer.groups)


def test_work_is_table_1():
    spec = cellspec.NetSpec(CFG)
    assert len(spec.convs) == 27
    dw = [n for n in spec.convs if n.groups > 1]
    pw = [n for n in spec.convs if n.k == 1]
    assert len(dw) == len(pw) == 13
    assert [n.stride for n in dw].count(2) == 4
    mmac = lambda nodes: sum(n.flops for n in nodes) / 2e6  # noqa: E731
    assert mmac(spec.convs) == pytest.approx(567.7, abs=0.05)
    assert mmac(pw) == pytest.approx(539.5, abs=0.05)
    assert mmac(dw) == pytest.approx(17.4, abs=0.05)
    assert spec.n_params / 1e6 == pytest.approx(4.22, abs=0.005)
    assert spec.out_shape == (1000,)
    assert spec.convs[-1].size == 7


@pytest.mark.parametrize("image", [224, 32])
def test_configuration_is_the_programs_network(image):
    """At both sizes the configuration's chain is the program's
    ``mobilenet_v1_layers``, which it serves by the name
    ``mobilenet_v1``."""
    from repro.core.model import mobilenet_v1_layers
    from repro.core.netplan import network_layers

    engine = cellspec.load_module("systems", "engine")
    mine = engine.chain_layers(cellspec.NetSpec(CFG, image=image))
    assert [_key(l) for l in mine] == \
        [_key(l) for l in mobilenet_v1_layers(image)]
    if image == 224:
        assert [_key(l) for l in mine] == \
            [_key(l) for l in network_layers("mobilenet_v1")]


def test_engine_serves_the_reference_network():
    """The rehearsal-size network (32x32, full widths) served through
    ``ServingEngine.for_topology`` equals the plain reference within the
    configuration's limit, with every depthwise conv on the depthwise
    kernel, the stem tap-folded and no guard demotion."""
    from repro.core import guard

    spec = cellspec.NetSpec(CFG, **CFG["rehearsal"])
    seed = 2**31 + 17
    params = inputs.make_params(spec, seed)
    pool = inputs.make_pool(spec, 4, seed)
    engine = cellspec.load_module("systems", "engine").build(
        spec, params, buckets=(4,))
    served = np.asarray(engine.replicas[0].fn(pool))
    refs = harness.reference_outputs(spec, seed, pool, range(4))
    err = harness.max_rel_err(list(enumerate(served)), refs)
    assert err <= CFG["check"]["limit"], err
    assert guard.events() == []
    stats = engine.stats()
    dw = {n.name: n.channels for n in spec.convs if n.groups > 1}
    assert stats["depthwise"] == dw and len(dw) == 13
    assert stats["tap_folded"] == {"conv1": 27}
    assert stats["cold_tunes"] == 0


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_is_not_correct(seed):
    """The control (the reference in three bfloat16 passes) at the
    rehearsal size reads over the configuration's limit."""
    spec = cellspec.NetSpec(CFG, **CFG["rehearsal"])
    pool = inputs.make_pool(spec, 4, seed)
    hi = harness.reference_outputs(spec, seed, pool, range(4), "highest")
    lo = harness.reference_outputs(spec, seed, pool, range(4), "bf16x3")
    assert harness.max_rel_err(list(lo.items()), hi) > \
        CFG["check"]["limit"]


def test_rehearsal_of_the_cell_is_correct():
    """The cell end to end at its rehearsal size: traffic, window,
    check; the per-layer readers find no trace and give nothing."""
    cell = cellspec.find_cell(f"{NAME}.batch128")
    res = harness.run(cell, seed=2**31 + 5, seconds=0.2, trace=False,
                      rehearse=True, t_start=time.monotonic(),
                      log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 128
    assert "cpu.images_per_s" in res["rehearsal_metrics"]


def test_depthwise_readers_find_nothing_without_a_trace():
    cell = cellspec.find_cell(f"{NAME}.batch128")
    names = [m["name"] for m in cell.per_layer]
    assert "conv_roofline.depthwise" in names
    assert "conv_roofline.pointwise" in names
    assert "conv_roofline.full_res" not in names
    run = harness.RunData(spec=cellspec.NetSpec(CFG), setup_s=1.0,
                          window_s=1.0, completed=128,
                          latencies_s=jnp.zeros(1), queue_waits_s=None,
                          steps=[128], peak=None, trace=None)
    for name in ("conv_roofline.depthwise", "conv_roofline.pointwise"):
        assert cellspec.load_module("metrics", name).read(run) is None
