"""A DAG network executed by the program's graph executor.

The configuration's nodes become the program's ``list[GraphNode]``; a
jitted ``models.layers.cnn_apply_from_graph`` (per-node Pallas conv
kernels, joins as XLA ops) runs each batch the way an engine replica
does: numpy batch in, device, numpy out.  The engine cannot build DAG
replicas itself, so the replica is handed to ``ServingEngine`` as is;
plans are tuned with ``autotune.tune_graph`` at every bucket and every
bucket is compiled before the window."""

from __future__ import annotations

import dataclasses


def graph_nodes(spec):
    """The program's ``list[GraphNode]`` for the configuration.

    Where a node departs from the program's defaults (every conv takes
    the executor's activation, a join none, a pool no padding), the
    departure goes to the ``GraphNode`` field of its own: ``relu`` for a
    conv without ReLU or an add with one, ``pool_pad`` for a padded
    pool.  A program whose ``GraphNode`` has no such field could only
    serve the node wrongly: that is an error naming the node."""
    from repro.core.model import ConvLayer, GraphNode

    fields = {f.name for f in dataclasses.fields(GraphNode)}
    out = []
    for nd in spec.nodes:
        extra = {}
        if nd.op == "conv" and not nd.relu or nd.op == "add" and nd.relu:
            extra["relu"] = nd.relu
        if nd.op == "pool" and nd.pad:
            extra["pool_pad"] = nd.pad
        missing = sorted(set(extra) - fields)
        if missing:
            raise ValueError(f"{spec.name}: node {nd.name} ({nd.op}) states "
                             f"{extra}; the program's GraphNode has no "
                             f"field {', '.join(missing)} to carry it")
        if nd.op == "conv":
            layer = ConvLayer(nd.name, ifmap=nd.size_in, in_channels=nd.cin,
                              out_channels=nd.channels, kernel=nd.k,
                              stride=nd.stride, padding=nd.pad,
                              groups=nd.groups)
            out.append(GraphNode(nd.name, "conv", nd.inputs, layer,
                                 **extra))
        elif nd.op == "pool":
            out.append(GraphNode(nd.name, "pool", nd.inputs, pool=nd.stride,
                                 pool_window=nd.window, **extra))
        elif nd.op == "upsample":
            out.append(GraphNode(nd.name, "upsample", nd.inputs,
                                 scale=nd.scale))
        else:
            out.append(GraphNode(nd.name, nd.op, nd.inputs, **extra))
    return out


def build(spec, params, buckets):
    """A prewarmed ``ServingEngine`` whose one replica runs the graph."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import autotune
    from repro.core.serving import Replica, ServingEngine
    from repro.models import layers as mlayers

    nodes = graph_nodes(spec)
    for b in sorted(set(buckets)):
        autotune.tune_graph(nodes, n=b)
    call = jax.jit(lambda p, x: mlayers.cnn_apply_from_graph(p, nodes, x))

    def fn(batch):
        return np.asarray(call(params, jnp.asarray(np.asarray(batch))))

    engine = ServingEngine([Replica("replica0", fn)], tuple(buckets),
                           input_shape=spec.input_shape)
    engine.prewarm(tune=False)
    return engine
