"""A DAG network executed by the program's graph executor.

The configuration's nodes become the program's ``list[GraphNode]``; a
jitted ``models.layers.cnn_apply_from_graph`` (per-node Pallas conv
kernels, joins as XLA ops) runs each batch the way an engine replica
does: numpy batch in, device, numpy out.  The engine cannot build DAG
replicas itself, so the replica is handed to ``ServingEngine`` as is;
plans are tuned with ``autotune.tune_graph`` at every bucket and every
bucket is compiled before the window."""

from __future__ import annotations


def graph_nodes(spec):
    """The program's ``list[GraphNode]`` for the configuration."""
    from repro.core.model import ConvLayer, GraphNode

    out = []
    for nd in spec.nodes:
        if nd.op == "conv":
            layer = ConvLayer(nd.name, ifmap=nd.size_in, in_channels=nd.cin,
                              out_channels=nd.channels, kernel=nd.k,
                              stride=nd.stride, padding=nd.pad,
                              groups=nd.groups)
            out.append(GraphNode(nd.name, "conv", nd.inputs, layer))
        elif nd.op == "pool":
            out.append(GraphNode(nd.name, "pool", nd.inputs, pool=nd.stride,
                                 pool_window=nd.window))
        elif nd.op == "upsample":
            out.append(GraphNode(nd.name, "upsample", nd.inputs,
                                 scale=nd.scale))
        else:
            out.append(GraphNode(nd.name, nd.op, nd.inputs))
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
