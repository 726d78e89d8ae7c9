"""Differential DAG test layer (DESIGN.md §12): NetworkGraph planning
and the graph executor against an independent pure-XLA oracle.

The oracle executor below re-implements the DAG walk from scratch on
``kernels.ref`` convs + jnp joins — it shares nothing with
``models/layers.cnn_apply_from_graph`` except the GraphNode topology —
so forward and both gradients of the resnet18/unet zoo are genuinely
differential.  Planning tests pin the residency pass's per-edge
semantics: the dataflow x residency grid, forced spills under a zero
budget, the skip-edge re-fetch byte formula, and the full-scale
resnet18 goldens the CI ratio gate relies on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (GraphFusePlan, NetworkGraph, NetworkPlan,
                        PoolInferenceError, autotune, graph_nodes,
                        scale_graph)
from repro.core.fuse_plan import graph_segments
from repro.core.model import ConvLayer, GraphNode, resnet18_graph, \
    unet_graph
from repro.core.netplan import pool_between
from repro.kernels import ref
from repro.models import layers as mlayers
from repro.models.base import init_params


def tiny_graph(net: str):
    """Execution-sized variants of the DAG zoo (CPU interpret mode)."""
    if net == "resnet18":
        return scale_graph(resnet18_graph(image=32, base=8), 2)
    return unet_graph(image=16, base=4, depth=2)


def _source(nodes):
    return next(nd for nd in nodes if not nd.inputs)


def _inputs(nodes, n=2, seed=0):
    src = _source(nodes)
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(
        (n, src.layer.ifmap, src.layer.ifmap, src.layer.in_channels)),
        jnp.float32)


def _oracle_pool(v, nd):
    pad = ((0, 0), (nd.pool_pad, nd.pool_pad), (nd.pool_pad, nd.pool_pad),
           (0, 0))
    return jax.lax.reduce_window(
        v, -jnp.inf, jax.lax.max, (1, nd.pool_window, nd.pool_window, 1),
        (1, nd.pool, nd.pool, 1), pad)


def ref_graph_apply(p, nodes, x):
    """Independent DAG oracle: ``ref.conv2d`` (+ bias/relu epilogue and
    reduce_window pooling) per conv node, jnp joins — written against
    the GraphNode spec, not against the production executor.  Each conv
    pads as its layer states, symmetrically: ResNet-18's 7x7/s2 stem
    pads (3, 3) and its 3x3/s2 convs (1, 1), where XLA's 'same' would
    pad (2, 3) and (0, 1).  A conv has a ReLU unless ``relu=False``, an
    add only with ``relu=True``; a pool pads ``pool_pad`` with -inf."""
    outs = {}
    for nd in nodes:
        if nd.op == "conv":
            v = x if not nd.inputs else outs[nd.inputs[0]]
            l = nd.layer
            v = ref.conv2d(v, p[nd.name]["w"], stride=l.stride,
                           padding=l.padding, bias=p[nd.name].get("b"),
                           activation=None if nd.relu is False else "relu")
            if nd.pool > 1 or nd.pool_window > 1:
                v = _oracle_pool(v, nd)
            outs[nd.name] = v
        elif nd.op == "pool":
            outs[nd.name] = _oracle_pool(outs[nd.inputs[0]], nd)
        elif nd.op == "add":
            v = outs[nd.inputs[0]] + outs[nd.inputs[1]]
            outs[nd.name] = jnp.maximum(v, 0.0) if nd.relu else v
        elif nd.op == "concat":
            outs[nd.name] = jnp.concatenate(
                [outs[s] for s in nd.inputs], axis=-1)
        elif nd.op == "upsample":
            v = outs[nd.inputs[0]]
            v = jnp.repeat(v, nd.scale, axis=1)
            outs[nd.name] = jnp.repeat(v, nd.scale, axis=2)
        else:                                    # pragma: no cover
            raise AssertionError(nd.op)
    return outs[nodes[-1].name]


def _close(a, b, tol=1e-5):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = float(np.abs(b).max()) + 1e-9
    assert float(np.abs(a - b).max()) / scale < tol


# ---------------------------------------------------------------------------
# Differential: production graph executor vs the in-test oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["resnet18", "unet"])
def test_graph_forward_matches_oracle(net):
    nodes = graph_nodes(tiny_graph(net))
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(0))
    x = _inputs(nodes)
    want = ref_graph_apply(p, nodes, x)
    got = mlayers.cnn_apply_from_graph(p, nodes, x, impl="pallas")
    assert got.shape == want.shape
    _close(got, want)


@pytest.mark.parametrize("net", ["resnet18", "unet"])
def test_graph_gradients_match_oracle(net):
    """Both gradients — d/dx and d/dparams — of a scalar loss through
    the whole DAG, kernel path vs the oracle."""
    nodes = graph_nodes(tiny_graph(net))
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(1))
    x = _inputs(nodes, seed=1)

    def loss_prod(p_, x_):
        return (mlayers.cnn_apply_from_graph(p_, nodes, x_,
                                             impl="pallas") ** 2).sum()

    def loss_ref(p_, x_):
        return (ref_graph_apply(p_, nodes, x_) ** 2).sum()

    gp, gx = jax.grad(loss_prod, argnums=(0, 1))(p, x)
    rp, rx = jax.grad(loss_ref, argnums=(0, 1))(p, x)
    _close(gx, rx)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(rp)):
        _close(a, b)


@pytest.mark.parametrize("net", ["resnet18", "unet"])
def test_graph_fused_bitmatches_per_layer(net):
    """Fused segment execution is a pure perf transform: the graph
    executor with GraphFusePlan megakernels returns the bit-identical
    tensor of the per-layer walk."""
    nodes = graph_nodes(tiny_graph(net))
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(2))
    x = _inputs(nodes, seed=2)
    per_layer = mlayers.cnn_apply_from_graph(p, nodes, x, impl="pallas")
    fused = mlayers.cnn_apply_from_graph(p, nodes, x, impl="pallas",
                                         fused=True)
    assert jnp.array_equal(per_layer, fused)
    # a prebuilt plan routes identically
    plan = GraphFusePlan.build(nodes, n=x.shape[0])
    fused2 = mlayers.cnn_apply_from_graph(p, nodes, x, impl="pallas",
                                          fused=True, fuse_plan=plan)
    assert jnp.array_equal(per_layer, fused2)


def test_graph_head_logits_and_packed_params():
    """n_classes adds the linear head over the terminal node; packed
    params run through the same walk."""
    nodes = graph_nodes(tiny_graph("resnet18"))
    p = init_params(mlayers.cnn_params_from_graph(nodes, n_classes=5),
                    jax.random.PRNGKey(3))
    x = _inputs(nodes, seed=3)
    y = mlayers.cnn_apply_from_graph(p, nodes, x, impl="pallas")
    assert y.shape == (x.shape[0], 5)
    want = ref_graph_apply(p, nodes, x)
    want = want.mean(axis=(1, 2)) @ p["head"]["w"] + p["head"]["b"]
    _close(y, want)
    pk = mlayers.cnn_pack_params_from_graph(p, nodes, n=x.shape[0])
    y_pk = mlayers.cnn_apply_from_graph(pk, nodes, x)
    _close(y_pk, want)


def _departure_graph(case):
    """A small graph with departures from the defaults: a conv without
    ReLU (and a last one, in every case), an add with a ReLU over such
    a conv's output, or a padded 3x3/s2 pool."""
    c0 = ConvLayer("c0", 12, 3, 8, kernel=3, padding=1)
    c1 = ConvLayer("c1", 12, 8, 8, kernel=1)
    nodes = [GraphNode("c0", "conv", (), c0),
             GraphNode("c1", "conv", ("c0",), c1,
                       relu=None if case == "pool_pad_1" else False),
             GraphNode("s", "add", ("c1", "c0"),
                       relu=True if case == "add_relu_true" else None),
             GraphNode("p", "pool", ("s",), pool=2, pool_window=3,
                       pool_pad=1 if case == "pool_pad_1" else 0)]
    size = 6 if case == "pool_pad_1" else 5
    nodes.append(GraphNode("c2", "conv", ("p",),
                           ConvLayer("c2", size, 8, 8, kernel=3,
                                     padding=1), relu=False))
    return nodes


@pytest.mark.parametrize("impl", ["pallas", "ref"])
@pytest.mark.parametrize("case", ["conv_relu_false", "add_relu_true",
                                  "pool_pad_1"])
def test_departures_match_oracle(case, impl):
    """A conv with ``relu=False``, an add with ``relu=True`` and a pool
    with ``pool_pad=1``, on the interpret path and on ``impl="ref"``,
    against the oracle; and the graph plans the padded pool's size."""
    nodes = _departure_graph(case)
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(5))
    x = _inputs(nodes, seed=5)
    want = ref_graph_apply(p, nodes, x)
    got = mlayers.cnn_apply_from_graph(p, nodes, x, impl=impl)
    _close(got, want)
    sizes = {s.name: s.out_size for s in NetworkGraph.build(nodes).steps}
    assert sizes["p"] == (6 if case == "pool_pad_1" else 5)
    if case == "add_relu_true":
        plain = [nd if nd.name != "s" else GraphNode("s", "add",
                                                     ("c1", "c0"))
                 for nd in nodes]
        assert not jnp.array_equal(
            got, mlayers.cnn_apply_from_graph(p, plain, x, impl=impl))


def _stated(nodes):
    """The nodes with today's semantics stated explicitly: a ReLU on
    every conv, none after an add, no pool padding."""
    import dataclasses
    return [dataclasses.replace(nd, relu=True) if nd.op == "conv" else
            dataclasses.replace(nd, relu=False) if nd.op == "add" else
            dataclasses.replace(nd, pool_pad=0) for nd in nodes]


@pytest.mark.parametrize("net", ["resnet18", "unet", "vgg16"])
def test_default_fields_trace_to_stated_semantics(net):
    """With every field at its default a graph traces to the very jaxpr
    of the same graph with the defaults stated: the fields change
    nothing of the networks that do not use them."""
    nodes = graph_nodes(tiny_graph(net) if net != "vgg16" else
                        [ConvLayer("a", 16, 3, 8, kernel=3, padding=1),
                         ConvLayer("b", 8, 8, 8, kernel=3, padding=1)])
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(6))
    x = _inputs(nodes, seed=6)

    def jaxpr(ns):
        return str(jax.make_jaxpr(
            lambda p_, x_: mlayers.cnn_apply_from_graph(p_, ns, x_))(p, x))
    assert jaxpr(nodes) == jaxpr(_stated(nodes))


@pytest.mark.parametrize("case", ["conv_relu_false", "add_relu_true",
                                  "pool_pad_1"])
def test_fused_execution_refuses_departures(case):
    """Fused segments run every conv with the executor's activation and
    unpadded pools, so a graph whose segments hold a departure is
    refused by name, never run with a ReLU it does not have."""
    nodes = _departure_graph(case)
    p = init_params(mlayers.cnn_params_from_graph(nodes),
                    jax.random.PRNGKey(7))
    x = _inputs(nodes, seed=7)
    with pytest.raises(ValueError, match="relu or pool_pad"):
        mlayers.cnn_apply_from_graph(p, nodes, x, fused=True)


@pytest.mark.parametrize("kw,match", [
    (dict(op="pool", pool=2, pool_window=2, relu=True), "takes no relu"),
    (dict(op="concat", relu=False), "takes no relu"),
    (dict(op="pool", pool=2, pool_window=3, pool_pad=2), "pool_pad"),
    (dict(op="pool", pool=2, pool_window=3, pool_pad=-1), "pool_pad"),
])
def test_graph_node_refuses_malformed_departures(kw, match):
    with pytest.raises(ValueError, match=match):
        GraphNode("bad", inputs=("a", "b"), **kw)


# ---------------------------------------------------------------------------
# Residency pass: the dataflow x residency grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("net", ["resnet18", "unet"])
@pytest.mark.parametrize("dataflow", ["carry", "halo"])
@pytest.mark.parametrize("residency", ["auto", "always", "never"])
def test_residency_grid(net, dataflow, residency):
    gp = NetworkGraph.build(net, dataflow=dataflow, residency=residency)
    pos = {nd.name: i for i, nd in enumerate(gp.nodes)}
    for e in gp.edges:
        assert e.boundaries == (pos[e.producer], pos[e.consumer])
        assert e.span >= 1
    if residency == "always":
        assert all(e.resident for e in gp.edges)
        assert gp.spilled_edge_bytes == 0
    if residency == "never":
        assert not any(e.resident for e in gp.edges)
        assert gp.boundary_occupancy() == [0] * (gp.n_nodes - 1)
    if residency == "auto":
        assert all(o <= gp.residency_budget
                   for o in gp.boundary_occupancy())
    # OPs are a property of the topology, not the residency policy
    never = NetworkGraph.build(net, dataflow=dataflow,
                               residency="never")
    assert gp.ops == never.ops
    for mode in ("3dtrim", "trim"):
        assert gp.hbm_bytes(mode)["total"] <= \
            never.hbm_bytes(mode)["total"]


@pytest.mark.parametrize("net", ["resnet18", "unet"])
def test_zero_budget_forces_every_spill(net):
    """residency_budget=0 under "auto" must refuse every edge — the
    skip edges re-fetch, and the totals equal the "never" policy."""
    gp = NetworkGraph.build(net, residency_budget=0)
    assert not any(e.resident for e in gp.edges)
    assert all(e.state == "refetch" for e in gp.edges)
    assert all(e.refetch_bytes == e.bytes for e in gp.edges)
    never = NetworkGraph.build(net, residency="never")
    for mode in ("3dtrim", "trim"):
        assert gp.hbm_bytes(mode) == never.hbm_bytes(mode)
        assert gp.accesses(mode) == never.accesses(mode)
    # skip edges exist and span > 1 boundary on both zoo nets
    assert any(e.span > 1 for e in gp.edges)


def test_skip_edge_refetch_byte_formula():
    """The re-fetch cost of a spilled skip edge is exactly the pooled
    activation it carries: n * out^2 * channels * dtype_bytes."""
    gp = NetworkGraph.build("resnet18", residency="never")
    edges = {(e.producer, e.consumer): e for e in gp.edges}
    skip = edges[("pool1", "l1b0_add")]
    assert skip.span > 1                       # a true skip connection
    assert skip.bytes == 56 * 56 * 64 * 4 == 802816
    assert skip.refetch_bytes == skip.bytes
    # the join consumer bills exactly its non-resident in-edges
    join = next(s for s in gp.steps if s.name == "l1b0_add")
    assert join.hbm_bytes()["input"] == \
        edges[("l1b0_conv2", "l1b0_add")].bytes + skip.bytes
    # and a join read shows up in the paper-metric denominator
    assert join.accesses() == join.hbm_bytes()["input"] // 4
    assert join.macs == 0 and join.ops == 0


# ---------------------------------------------------------------------------
# Full-scale resnet18 goldens (the CI ratio gate's numbers)
# ---------------------------------------------------------------------------

def test_resnet18_arch_golden_values():
    gp = NetworkGraph.build("resnet18")
    assert gp.n_nodes == 29
    assert len(gp.conv_steps) == 20
    assert len(gp.edges) == 36
    arch = gp.arch_compare()
    assert arch["improvement"] == \
        pytest.approx(3.245935585013433, rel=1e-6)
    assert arch["improvement"] > 2.0           # the CI gate
    # the 7x7/s2 stem's strips are 10 rows: at stride 2 the plan holds
    # every tap's strided view in VMEM (ConvPlan.vmem_resident_bytes)
    cmp = gp.compare()
    assert cmp["ops_per_macc_3dtrim"] == \
        pytest.approx(310.5401561843431, rel=1e-6)
    assert cmp["ops_per_macc_trim"] == \
        pytest.approx(308.13732724249616, rel=1e-6)
    # at batch 1 every edge fits the 8 MB budget
    assert all(e.resident for e in gp.edges)
    assert max(gp.boundary_occupancy()) == 3211264


def test_unet_arch_golden_values():
    gp = NetworkGraph.build("unet")
    assert len(gp.conv_steps) == 13
    assert gp.arch_compare()["improvement"] == \
        pytest.approx(3.788476083401472, rel=1e-6)


# ---------------------------------------------------------------------------
# Graph construction + segmentation semantics
# ---------------------------------------------------------------------------

def test_graph_validation_rejects_broken_topologies():
    l = ConvLayer("x", 8, 3, 4, kernel=3, padding=1)
    with pytest.raises(ValueError, match="duplicate node name"):
        NetworkGraph.build([GraphNode("a", "conv", (), l),
                           GraphNode("a", "conv", ("a",),
                                     ConvLayer("x", 8, 4, 4, kernel=3,
                                               padding=1))])
    with pytest.raises(ValueError, match="topological"):
        NetworkGraph.build([GraphNode("a", "conv", ("missing",), l)])
    with pytest.raises(ValueError, match="exactly one input"):
        NetworkGraph.build([
            GraphNode("a", "conv", (), l),
            GraphNode("b", "conv", ("a", "a"),
                      ConvLayer("y", 8, 4, 4, kernel=3, padding=1))])
    with pytest.raises(ValueError, match="needs inputs"):
        GraphNode("j", "add", ())
    with pytest.raises(ValueError, match="op"):
        GraphNode("a", "matmul", (), l)


def test_graph_params_reject_reserved_head_name():
    l = ConvLayer("x", 8, 3, 4, kernel=3, padding=1)
    with pytest.raises(ValueError, match="head"):
        mlayers.cnn_params_from_graph([GraphNode("head", "conv", (), l)])


def test_pool_inference_structured_errors():
    """Dims only a strided or upsampling join can explain must raise a
    PoolInferenceError carrying the structured fields (satellite 4)."""
    a = ConvLayer("a", 16, 3, 4, kernel=3, padding=1)      # out 16
    up = ConvLayer("b", 32, 4, 4, kernel=3, padding=1)     # needs 32
    with pytest.raises(PoolInferenceError) as ei:
        pool_between(a, up)
    err = ei.value
    assert isinstance(err, ValueError)          # stays catchable as-was
    assert (err.producer, err.consumer) == ("a", "b")
    assert (err.out_size, err.in_size) == (16, 32)
    assert err.reason == "upsample"
    assert "upsample" in str(err)

    deep = ConvLayer("c", 3, 4, 4, kernel=3, padding=1)    # 16 -> 3
    with pytest.raises(PoolInferenceError) as ei:
        pool_between(a, deep)                   # stride 5 > MAX_STRIDE
    err = ei.value
    assert err.reason == "strided-join"
    assert err.stride > PoolInferenceError.MAX_STRIDE
    # every zoo boundary (VGG 2/2, AlexNet 3/2, ResNet/U-Net 2/2,
    # sub-2x 3/1) stays inferable under the caps
    for nets in ("vgg16", "alexnet", "mobilenet"):
        NetworkPlan.build(nets)
    for nets in ("resnet18", "unet"):
        NetworkGraph.build(nets)


def test_graph_segments_break_on_unrecoverable_pool():
    """A pool whose params the dims between two convs would re-infer
    differently (o=10 pooled 2x2/s3 re-infers as 4x4/s3) must bound the
    segment instead of being silently absorbed."""
    a = ConvLayer("a", 10, 3, 4, kernel=3, padding=1)      # out 10
    b = ConvLayer("b", 3, 4, 4, kernel=3, padding=1)       # in 3
    nodes = [GraphNode("a", "conv", (), a),
             GraphNode("p", "pool", ("a",), pool=3, pool_window=2),
             GraphNode("b", "conv", ("p",), b)]
    NetworkGraph.build(nodes)                  # plans fine as a DAG
    segs = graph_segments(nodes)
    assert [names for names, _ in segs] == [("a",), ("b",)]
    # a recoverable pool (2x2/s2) is absorbed into one segment (its
    # name rides along so the executor can mark the node covered)
    c = ConvLayer("c", 5, 4, 4, kernel=3, padding=1)
    nodes2 = [GraphNode("a", "conv", (), a),
              GraphNode("p", "pool", ("a",), pool=2, pool_window=2),
              GraphNode("c", "conv", ("p",), c)]
    segs2 = graph_segments(nodes2)
    assert [names for names, _ in segs2] == [("a", "p", "c")]
    assert [l.name for l in segs2[0][1]] == ["a", "c"]
    # and the fused walk over it still bit-matches the per-node walk
    p = init_params(mlayers.cnn_params_from_graph(nodes2),
                    jax.random.PRNGKey(4))
    x = _inputs(nodes2, seed=4)
    per_node = mlayers.cnn_apply_from_graph(p, nodes2, x, impl="pallas")
    fused = mlayers.cnn_apply_from_graph(p, nodes2, x, impl="pallas",
                                         fused=True)
    assert jnp.array_equal(per_node, fused)


def test_graph_segments_cover_every_conv_once():
    for net in ("resnet18", "unet"):
        nodes = graph_nodes(net)
        segs = graph_segments(nodes)
        covered = [nm for names, _ in segs for nm in names]
        convs = [nd.name for nd in nodes if nd.op == "conv"]
        assert sorted(covered) == sorted(convs)
        assert len(covered) == len(set(covered))


def test_tune_graph_sweep_and_consumption(tmp_path):
    """One tune_graph sweep caches every conv node's knobs (and the
    fused segment records); the executor then runs on cached plans."""
    path = str(tmp_path / "tune.json")
    # 16 channels at the least: narrower convs fold their taps and
    # never fuse (DESIGN.md §13); nothing is executed here
    nodes = graph_nodes(unet_graph(image=16, base=16, depth=2))
    out = autotune.tune_graph(nodes, n=1, fused=True, path=path)
    convs = [nd for nd in nodes if nd.op == "conv"]
    assert len(out["layers"]) == len(convs)
    assert out["fused"]                        # multi-conv segments exist
    gp = NetworkGraph.build(nodes, use_autotune_cache=True)
    assert len(gp.conv_steps) == len(convs)
