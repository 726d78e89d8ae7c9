"""The plain references (``bench/reference/``) against the program's own
plain-JAX path (``impl="ref"``), at a small size on the CPU, and against
a straight-line forward of a tiny residual net."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import cellspec
import inputs

SMALL = {"image": 32, "channel_divisor": 16}


def small(name):
    cfg = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/{name}.json")
    return cellspec.NetSpec(cfg, **SMALL)


def program_ref_forward(spec, params):
    """The program's forward with every conv on its ``ref`` tier."""
    from repro.models import layers as mlayers
    if spec.classes:
        topo = cellspec.load_module("systems", "engine").chain_layers(spec)
        p = {f"conv{i}": params[nd.name] for i, nd in enumerate(spec.convs)}
        p["head"] = params["head"]
        return lambda x: mlayers.cnn_apply_from_layers(p, topo, x,
                                                       impl="ref")
    nodes = cellspec.load_module("systems", "graph").graph_nodes(spec)
    return lambda x: mlayers.cnn_apply_from_graph(params, nodes, x,
                                                  impl="ref")


@pytest.mark.parametrize("name", ["vgg16-f32", "unet-f32"])
def test_reference_matches_program_ref_path(name):
    spec = small(name)
    ref = cellspec.load_module("reference", "cnn")
    params = inputs.make_params(spec, 11)
    x = jnp.asarray(inputs.make_pool(spec, 3, 11))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(program_ref_forward(spec, params)(x))
    got = np.asarray(ref.make_forward(spec)(params, x))
    assert got.shape == (3,) + spec.out_shape
    scale = np.abs(want).max()
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6 * scale)


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("name", ["vgg16-f32", "unet-f32"])
def test_control_is_not_correct(name, seed):
    """The control, the reference one precision lower (three bfloat16
    passes), put in the program's place at the rehearsal size: its
    reading is over the configuration's limit."""
    import harness
    cfg = cellspec.read_json(f"{cellspec.BENCH_DIR}/configs/{name}.json")
    spec = cellspec.NetSpec(cfg, **cfg["rehearsal"])
    pool = inputs.make_pool(spec, 4, seed)
    hi = harness.reference_outputs(spec, seed, pool, range(4), "highest")
    lo = harness.reference_outputs(spec, seed, pool, range(4), "bf16x3")
    assert harness.max_rel_err(list(lo.items()), hi) > \
        cfg["check"]["limit"]


def test_unknown_precision_is_refused():
    ref = cellspec.load_module("reference", "cnn")
    with pytest.raises(ValueError, match="precision"):
        ref.forward(small("vgg16-f32"), {}, jnp.zeros((1, 32, 32, 3)),
                    precision="bf16")


RESIDUAL = cellspec.read_json(f"{cellspec.BENCH_DIR}/tests/data/"
                              "resnet-tiny-f32.json")


def straight_line(params, x):
    """The tiny residual net written out op by op: a ReLU after the stem
    and the block's first two convs, none after its third conv or its
    projection, one after the sum; the pool padded with -inf."""
    def conv(name, y, stride, pad, relu=True):
        y = lax.conv_general_dilated(
            y, params[name]["w"], (stride, stride), [(pad, pad)] * 2,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=lax.Precision.HIGHEST) + params[name]["b"]
        return jnp.maximum(y, 0.0) if relu else y

    stem = conv("stem", x, 2, 1)
    padded = jnp.pad(stem, [(0, 0), (1, 1), (1, 1), (0, 0)],
                     constant_values=-jnp.inf)
    pool = lax.reduce_window(padded, -jnp.inf, lax.max, (1, 3, 3, 1),
                             (1, 2, 2, 1), "VALID")
    main = conv("b1c", conv("b1b", conv("b1a", pool, 1, 0), 2, 1), 1, 0,
                relu=False)
    y = jnp.maximum(main + conv("b1p", pool, 2, 0, relu=False), 0.0)
    return jnp.dot(y.mean(axis=(1, 2)), params["head"]["w"],
                   precision=lax.Precision.HIGHEST) + params["head"]["b"]


def test_reference_matches_straight_line_residual_net():
    spec = cellspec.NetSpec(RESIDUAL)
    ref = cellspec.load_module("reference", "cnn")
    params = inputs.make_params(spec, 2**33 + 7)
    x = jnp.asarray(inputs.make_pool(spec, 4, 2**33 + 7))
    want = np.asarray(jax.jit(straight_line)(params, x))
    got = np.asarray(ref.make_forward(spec)(params, x))
    assert got.shape == (4, 10)
    scale = np.abs(want).max()
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * scale)
    # the control goes through the same code, one precision lower
    lo = np.asarray(ref.make_forward(spec, "bf16x3")(params, x))
    assert 0 < np.abs(lo - want).max() < 1e-3 * scale


@dataclasses.dataclass(frozen=True)
class NodeWithFields:
    """A stand-in for the program's ``GraphNode`` with the two fields the
    adapter passes departures on by (None: the op's default)."""

    name: str
    op: str
    inputs: tuple = ()
    layer: object = None
    pool: int = 1
    pool_window: int = 1
    scale: int = 1
    relu: bool | None = None
    pool_pad: int = 0


def test_graph_adapter_passes_departures_field_for_field(monkeypatch):
    from repro.core import model
    monkeypatch.setattr(model, "GraphNode", NodeWithFields)
    nodes = cellspec.load_module("systems", "graph").graph_nodes(
        cellspec.NetSpec(RESIDUAL))
    assert {n.name: (n.op, n.relu, n.pool_pad) for n in nodes} == {
        "stem": ("conv", None, 0), "pool1": ("pool", None, 1),
        "b1a": ("conv", None, 0), "b1b": ("conv", None, 0),
        "b1c": ("conv", False, 0), "b1p": ("conv", False, 0),
        "b1": ("add", True, 0)}
    assert nodes[-1].inputs == ("b1c", "b1p")
    assert (nodes[1].pool, nodes[1].pool_window) == (2, 3)


@pytest.mark.parametrize("drop,node", [("pool_pad", "pool1"),
                                       ("relu", "b1c")])
def test_graph_adapter_refuses_what_the_program_cannot_carry(
        monkeypatch, drop, node):
    """A program whose GraphNode lacks a field would serve the node with
    its default: the adapter stops at the first such node, by name."""
    from repro.core import model
    fields = [(f.name, f.type, f) for f in
              dataclasses.fields(NodeWithFields) if f.name != drop]
    monkeypatch.setattr(model, "GraphNode", dataclasses.make_dataclass(
        "NodeWithout", fields, frozen=True))
    with pytest.raises(ValueError, match=f"node {node} .*no field {drop}"):
        cellspec.load_module("systems", "graph").graph_nodes(
            cellspec.NetSpec(RESIDUAL))
