"""Batched CNN serving on sharded TrIM convolutions, rebased onto the
continuous-batching engine (DESIGN.md §6/§10).

Requests enter the :class:`~repro.core.serving.ServingEngine` queue and
are served in *bucket* batches — a fixed grid of batch sizes, one
compiled program each; partial batches pad up to the bucket and the
padding rows are masked out of the results.  The engine prewarms the
autotune cache and every bucket's compiled program before the first
request, so serving never hits a cold tune.

The default small CNN keeps the ``shard_map`` halo-exchange path:
images shard over the mesh's 'data' axis, output H-strips over 'model',
with the K-1 boundary rows exchanged between neighbor devices before
each per-shard Pallas kernel.  The modeled ``ShardedConvPlan`` traffic
of the first layer is printed next to the measured throughput so the
analytical and observed costs sit side by side.

``--net vgg16|alexnet`` swaps the small CNN for a full paper topology
(every conv layer, real spatial dims and pooling; channels divided by
``--scale``) served through the engine's tuned guarded plans —
``--fused`` runs the residency-group megakernels of DESIGN.md §8.

  PYTHONPATH=src python examples/serve_cnn.py --devices 4 --data 2 \
      --spatial 2 --requests 64 --batch 16
  PYTHONPATH=src python examples/serve_cnn.py --net vgg16 --scale 16 \
      --requests 8 --batch 4
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# --devices N must take effect before the first jax import (XLA reads
# the host-device flag at initialization; hostdevices is jax-free)
from repro.launch.hostdevices import force_host_device_count_from_argv
force_host_device_count_from_argv()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (FusedGroupPlan, NetworkPlan, guard,
                        scale_layers, network_layers)
from repro.core.conv_shard import ShardedConvPlan
from repro.core.roofline import sharded_conv_roofline
from repro.core.serving import Replica, ServingEngine, pow2_buckets, replay
from repro.kernels import ops
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_conv_mesh
from repro.models import layers
from repro.models.base import init_params

IMAGE, CIN, N_CLASSES = 32, 3, 10
CHANNELS = (8, 16)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=1,
                    help="force N host CPU devices (handled pre-import) "
                         "for the sharded path on a CPU-only machine; "
                         "never on a TPU host, where it hides the chips")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel shards (images over 'data')")
    ap.add_argument("--spatial", type=int, default=1,
                    help="spatial shards (output H-strips over 'model')")
    ap.add_argument("--requests", type=int, default=32,
                    help="total images queued")
    ap.add_argument("--batch", type=int, default=8,
                    help="largest serving bucket (the grid is powers of "
                         "two up to it; requests pad up to a bucket)")
    ap.add_argument("--net", default=None,
                    choices=["vgg16", "alexnet", "mobilenet"],
                    help="serve a full paper topology on tuned guarded "
                         "plans (single-device; default: the small "
                         "sharded CNN)")
    ap.add_argument("--scale", type=int, default=16,
                    help="channel divisor for the executed --net "
                         "configuration")
    ap.add_argument("--fused", action="store_true",
                    help="serve --net on fused residency-group "
                         "megakernels (DESIGN.md §8) instead of "
                         "per-layer plans")
    args = ap.parse_args()
    use_compile_cache()
    if args.fused and not args.net:
        raise SystemExit("--fused needs --net (the small CNN serves the "
                         "sharded per-layer path)")

    mesh = None
    buckets = pow2_buckets(args.batch)
    if args.data * args.spatial > 1:
        if args.net:
            raise SystemExit("--net serves single-device plans; "
                             "drop --data/--spatial")
        mesh = make_conv_mesh(args.data, args.spatial)
        if args.batch % args.data:
            raise SystemExit(f"--batch {args.batch} must divide over "
                             f"--data {args.data}")
        # every bucket's batch must shard evenly over 'data'
        buckets = tuple(b for b in buckets if b % args.data == 0)

    if args.net:
        topo = scale_layers(network_layers(args.net), args.scale)
        image, cin = topo[0].ifmap, topo[0].in_channels
        params = init_params(
            layers.cnn_params_from_layers(topo, n_classes=N_CLASSES),
            jax.random.PRNGKey(0))
        if args.fused:
            fplan = FusedGroupPlan.build(topo, n=args.batch)
            fs = fplan.summary()
            print(f"{args.net} fused plan @ batch {args.batch}: "
                  f"{fs['groups']} groups (max depth {fs['max_depth']}), "
                  f"executed {fs['executed_bytes']/1e6:.1f}MB vs "
                  f"per-layer {fs['per_layer_bytes']/1e6:.1f}MB "
                  f"({fs['executed_ratio']:.2f}x)")
        netplan = NetworkPlan.build(args.net, n=args.batch)
        t = netplan.hbm_bytes()
        print(f"{args.net} NetworkPlan @ batch {args.batch} (full scale): "
              f"hbm={t['total']/1e6:.1f}MB, Ops/MAcc 3dtrim "
              f"{netplan.ops_per_macc('3dtrim'):.1f} vs trim "
              f"{netplan.ops_per_macc('trim'):.1f}")
        engine = ServingEngine.for_topology(topo, params, buckets=buckets,
                                            fused=args.fused)
    else:
        image, cin = IMAGE, CIN
        params = init_params(
            layers.simple_cnn_params(cin=CIN, channels=CHANNELS,
                                     n_classes=N_CLASSES),
            jax.random.PRNGKey(0))

        # the modeled sharded traffic of the first conv layer at the
        # largest bucket
        kshape, _ = ops.kernel_input_shape(
            (args.batch, IMAGE, IMAGE, CIN), 3, 1, "same")
        plan = ShardedConvPlan.build(kshape, (3, 3, CIN, CHANNELS[0]),
                                     batch_shards=args.data,
                                     spatial_shards=args.spatial)
        traffic = plan.sharded_traffic()
        terms = sharded_conv_roofline("conv0", plan)
        print(f"conv0 plan @ batch {args.batch}: "
              f"hbm={traffic['hbm_total']}B "
              f"halo={traffic['halo']}B "
              f"({plan.halo_bytes_per_device:.0f}B/dev, "
              f"t_coll={terms.t_collective * 1e6:.2f}us, "
              f"dominant={terms.dominant})")

        call = jax.jit(lambda p, x: layers.simple_cnn_apply(p, x,
                                                            mesh=mesh))
        rep = Replica(name="replica0",
                      fn=lambda b: np.asarray(call(params,
                                                   jnp.asarray(b))))
        engine = ServingEngine([rep], buckets,
                               input_shape=(image, image, cin))

    engine.prewarm()

    rng = np.random.default_rng(0)
    xs = rng.standard_normal(
        (args.requests, image, image, cin)).astype(np.float32)
    # the original one-shot driver drained a full queue: arrive
    # everything at t=0 and let continuous batching carve it into
    # max-bucket batches FIFO (service times measured from the real
    # forwards)
    trace = [(0.0, i, xs[i]) for i in range(args.requests)]
    results, rejected = replay(engine, trace)

    preds = np.asarray([results[i].argmax(-1)
                        for i in sorted(results)])
    s = engine.recorder.summary()
    st = engine.stats()
    mesh_desc = (f"{args.data}x{args.spatial} (data x spatial)"
                 if mesh is not None else
                 f"single device ({args.net} x{args.scale})" if args.net
                 else "single device")
    print(f"served {st['served']} images in {s['span_s']:.2f}s "
          f"({s['throughput_rps']:.1f} img/s) on {mesh_desc}; "
          f"bucket batches {st['bucket_batches']}, "
          f"cold tunes {st['cold_tunes']}, rejected {len(rejected)}; "
          f"class histogram {np.bincount(preds, minlength=N_CLASSES)}")

    # degraded-mode report (DESIGN.md §9): silence means every conv ran
    # on its intended tier; a served batch that survived on a fallback
    # tier is labeled, never silent
    for name, rep_stats in st["replicas"].items():
        for e in rep_stats["guard_events"]:
            where = f" [{e['layer']}]" if e.get("layer") else ""
            print(f"DEGRADED {name}: {e['tier']} -> {e['to']}{where} "
                  f"({e['kind']}): {e['error'][:100]}")


if __name__ == "__main__":
    main()
