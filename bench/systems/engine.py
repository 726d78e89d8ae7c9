"""A chain network served by the program's ``ServingEngine``.

The configuration's conv/pool chain becomes the program's
``list[ConvLayer]`` and is served through
``ServingEngine.for_topology`` (one jitted
``models.layers.cnn_apply_from_layers`` per bucket, per-layer Pallas
kernels), prewarmed over the traffic's buckets: every plan tuned and
every bucket compiled before the window."""

from __future__ import annotations


def chain_layers(spec):
    """The program's topology for a conv/pool chain, checked against the
    pools the program infers between consecutive layers.  The chain
    puts a ReLU after every conv and pads no pool: a node that states
    otherwise is an error naming it."""
    from repro.core.model import ConvLayer
    from repro.core.netplan import infer_pools

    layers, pools = [], []
    for nd in spec.nodes:
        if nd.op == "conv" and not nd.relu or nd.op == "pool" and nd.pad:
            raise ValueError(f"{spec.name}: node {nd.name} ({nd.op}) has no "
                             "ReLU or a padded pool, which the chain "
                             "program cannot serve; use entry 'graph'")
        if nd.op == "conv":
            layers.append(ConvLayer(nd.name, ifmap=nd.size_in,
                                    in_channels=nd.cin,
                                    out_channels=nd.channels, kernel=nd.k,
                                    stride=nd.stride, padding=nd.pad,
                                    groups=nd.groups))
            pools.append((1, 1))
        elif nd.op == "pool" and layers and pools[-1] == (1, 1):
            pools[-1] = (nd.stride, nd.window)
        else:
            raise ValueError(f"{spec.name}: node {nd.name} ({nd.op}) does "
                             "not fit a conv/pool chain; use entry 'graph'")
    inferred = [tuple(p) for p in infer_pools(layers)]
    if inferred != pools:
        raise ValueError(f"{spec.name}: the program infers pools "
                         f"{inferred}, the configuration states {pools}")
    return layers


def build(spec, params, buckets):
    """A prewarmed ``ServingEngine`` over ``buckets``."""
    from repro.core.serving import ServingEngine

    layers = chain_layers(spec)
    p = {f"conv{i}": params[nd.name] for i, nd in enumerate(spec.convs)}
    if "head" in params:
        p["head"] = params["head"]
    engine = ServingEngine.for_topology(layers, p, buckets=tuple(buckets))
    engine.prewarm()
    return engine
