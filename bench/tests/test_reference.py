"""The plain references (``bench/reference/``) against the program's own
plain-JAX path (``impl="ref"``), at a small size on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
