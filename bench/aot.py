"""Ahead-of-time compile of a cell's timed programs for a described TPU
v5e, without the chip.

    JAX_PLATFORMS=cpu python3 bench/aot.py --workload vgg16-f32.saturate

Builds the program's jitted forward exactly as the cell's system does
(tuned plans, per-layer Pallas kernels lowered natively, not in
interpret mode) at every bucket of the cell's traffic, compiles it for
one chip of a ``v5e:2x2`` topology, and prints the compiler's memory
analysis and whether the Pallas kernels are in the program.  Nothing
runs: this proves compilation, not results or times.
"""

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import cellspec  # noqa: E402


def forward_for(cell, spec):
    """``fn(params, x)`` as the cell's system jits it."""
    from repro.models import layers as mlayers
    entry = cell.config["entry"]
    mod = cellspec.load_module("systems", entry)
    if entry == "engine":
        topo = mod.chain_layers(spec)

        def fwd(p, x):
            q = {f"conv{i}": p[nd.name] for i, nd in enumerate(spec.convs)}
            if "head" in p:
                q["head"] = p["head"]
            return mlayers.cnn_apply_from_layers(q, topo, x)
        return fwd, topo
    nodes = mod.graph_nodes(spec)
    return (lambda p, x: mlayers.cnn_apply_from_graph(p, nodes, x)), nodes


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["REPRO_CONVTUNE_CACHE"] = os.path.join(
        cellspec.ROOT, "artifacts", "bench", "aot_convtune.json")
    cell = cellspec.find_cell(args.workload)
    sys.path.insert(0, os.path.join(cellspec.ROOT, "src"))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core import autotune
    from repro.kernels import runtime

    import inputs
    jax.config.update("jax_enable_compilation_cache", False)
    runtime.on_tpu = lambda: True          # lower the kernels natively
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = cellspec.NetSpec(cell.config)
    fwd, net = forward_for(cell, spec)
    params = jax.eval_shape(lambda: inputs.make_params(spec, 0))
    p_sds = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        params)
    ok = True
    for b in cell.traffic["buckets"]:
        if cell.config["entry"] == "engine":
            autotune.tune_network(net, n=b)
        else:
            autotune.tune_graph(net, n=b)
        x = jax.ShapeDtypeStruct((b,) + spec.input_shape, jnp.float32,
                                 sharding=chip)
        compiled = jax.jit(fwd).lower(p_sds, x).compile()
        text = compiled.as_text()
        kernels = text.count("tpu_custom_call")
        mem = compiled.memory_analysis()
        print(f"{cell.name} bucket {b}: compiled, {kernels} "
              f"tpu_custom_call mentions; {mem}", flush=True)
        ok &= kernels > 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
