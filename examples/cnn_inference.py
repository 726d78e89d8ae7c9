"""The paper's own workload: CNN inference running through the
trim_conv2d Pallas kernel — bias + ReLU fused into the kernel epilogue,
a MobileNet-style depthwise-separable block on the grouped-conv path, and
the paper's Ops/Access accounting printed alongside.

This is the closed loop of the conv execution engine (DESIGN.md §4/§7):
each layer is autotuned once (model-guided (tile_h, tile_cout, dataflow)
search persisted in a JSON cache), weights are pre-packed into the
kernel's padded layout at load time, and the forward pass then runs
entirely on packed params and cached plans — ``ops.conv2d`` finds every
knob in the cache.

Two modes:

  PYTHONPATH=src python examples/cnn_inference.py
      the original demo: a reduced VGG-16 head + depthwise block, plus
      the full-scale per-layer Fig. 6 accounting.

  PYTHONPATH=src python examples/cnn_inference.py --net vgg16 [--scale 8]
      the whole-network engine: run the FULL topology (every conv layer,
      real spatial dims / strides / pooling, channels divided by
      ``--scale`` so CPU interpret mode stays fast) on tuned, packed
      plans, then print the ``NetworkPlan`` whole-network accounting —
      HBM traffic, residency decisions and the paper's trim-vs-3dtrim
      Ops/MAcc comparison — for the full-scale configuration.

Every traffic/arithmetic-intensity number comes from the same ``ConvPlan``
objects the kernels execute.
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# keep the example's tuning records repo-local (and the run reproducible)
os.environ.setdefault("REPRO_CONVTUNE_CACHE", os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "convtune.json"))

import jax
import numpy as np
import jax.numpy as jnp

from repro.core import (FusedGroupPlan, NetworkPlan, autotune,
                        compare_layer, guard, mobilenet_layers,
                        network_layers, scale_layers, vgg16_layers)
from repro.core.roofline import conv_plan_roofline, network_roofline
from repro.launch.compile_cache import use_compile_cache
from repro.models import layers
from repro.models.base import init_params


def run_network(net: str, scale: int, batch: int,
                fused: bool = False) -> None:
    """The whole-network path: tune every layer, pack every weight, run
    the full topology, print the NetworkPlan evaluation.  ``fused``
    swaps the per-layer engine for the residency-group megakernels
    (DESIGN.md §8): raw params (the megakernel streams weight taps
    itself), one ``pallas_call`` per fused conv→[pool]→conv group."""
    full = network_layers(net)
    topo = scale_layers(full, scale)
    image = topo[0].ifmap

    t0 = time.perf_counter()
    recs = autotune.tune_network(topo, n=batch)
    tuned = sum(1 for r in recs.values() if "skipped" not in r)
    print(f"tuned {tuned}/{len(topo)} layers in "
          f"{time.perf_counter() - t0:.2f}s "
          f"(skipped: {[k for k, r in recs.items() if 'skipped' in r]})")

    params = init_params(layers.cnn_params_from_layers(topo),
                         jax.random.PRNGKey(0))
    fplan = None
    if fused:
        fplan = FusedGroupPlan.build(topo, n=batch)
        groups = [f"conv{g.start}..conv{g.start + g.depth - 1}"
                  f"(T={g.strip_rows})" if g.fused else f"conv{g.start}"
                  for g in fplan.groups]
        print(f"fused groups: {' | '.join(groups)}")
    else:
        params = layers.cnn_pack_params(params, topo, n=batch)

    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (batch, image, image, topo[0].in_channels)), jnp.float32)
    t0 = time.perf_counter()
    y = layers.cnn_apply_from_layers(params, topo, x, fused=fused,
                                     fuse_plan=fplan)
    y.block_until_ready()
    mode = "fused megakernels" if fused else "packed+tuned"
    print(f"{net} x{scale} forward (batch {batch}, {len(topo)} convs, "
          f"{mode}): {y.shape}, mean {float(y.mean()):.4f}, "
          f"{time.perf_counter() - t0:.2f}s")

    if fused:
        # executed-traffic accounting of the same fusion at full scale
        fs = FusedGroupPlan.build(net, n=batch).summary()
        print(f"  executed HBM (full scale): fused "
              f"{fs['executed_bytes']/1e6:.1f} MB vs per-layer "
              f"{fs['per_layer_bytes']/1e6:.1f} MB -> "
              f"{fs['executed_ratio']:.2f}x less traffic "
              f"({fs['fused_layers']}/{len(full)} layers in depth>=2 "
              f"groups)")

    # the full-scale analytical evaluation of the same topology
    plan = NetworkPlan.build(net, n=batch)
    cmp, arch = plan.compare(), plan.arch_compare()
    t = plan.hbm_bytes()
    resident = [s.name for s in plan.steps if s.resident_out]
    print(f"\nNetworkPlan ({net}, full scale, batch {batch}, "
          f"residency=auto):")
    print(f"  HBM {t['total']/1e6:.1f} MB "
          f"(input {t['input']/1e6:.1f} / weights {t['weights']/1e6:.1f} "
          f"/ output {t['output']/1e6:.1f}); "
          f"resident boundaries: {resident or 'none'}")
    print(f"  Ops/MAcc (engine strips): 3dtrim "
          f"{cmp['ops_per_macc_3dtrim']:.1f} vs trim "
          f"{cmp['ops_per_macc_trim']:.1f} ({cmp['improvement']:.3f}x)")
    print(f"  Ops/MAcc (paper arch model): 3D-TrIM "
          f"{arch['ops_per_macc']['3d-trim']:.1f} vs TrIM "
          f"{arch['ops_per_macc']['trim']:.1f} -> "
          f"{arch['improvement']:.2f}x per slice")
    terms = network_roofline(net, plan)
    print(f"  roofline: T_comp {terms.t_compute*1e3:.2f} ms, "
          f"T_mem {terms.t_memory*1e3:.2f} ms -> {terms.dominant}-bound")


def run_demo() -> None:
    """The original reduced-head demo (kept as the default)."""
    rng = jax.random.PRNGKey(0)

    # a reduced VGG-16 head (channel counts /8, 32x32 input) that runs in
    # seconds on CPU interpret mode; the access accounting uses full
    # configs
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((1, 32, 32, 3)),
        jnp.float32)
    channels = [8, 8, 16, 16, 32]

    # load time: tune each layer's plan once (persisted), pack each
    # layer's weights into the kernel layout once
    packed, cur = [], x.shape
    for i, c in enumerate(channels):
        p = init_params(layers.conv2d_params(3, cur[-1], c),
                        jax.random.fold_in(rng, i))
        kshape = (cur[0], cur[1] + 2, cur[2] + 2, cur[3])  # 'same', K=3
        autotune.tune(kshape, p["w"].shape, stride=1, pad=0)
        packed.append(layers.conv2d_pack_params(p, x_shape=cur))
        hw = (cur[1] // 2, cur[2] // 2) if i % 2 == 1 else (cur[1], cur[2])
        cur = (cur[0], *hw, c)

    # inference: packed params + cached plans only
    for i, p in enumerate(packed):
        x = layers.conv2d_apply(p, x, activation="relu")  # fused bias+ReLU
        if i % 2 == 1:
            x = x[:, ::2, ::2, :]      # poor man's maxpool (stride slice)
    print("reduced VGG head output:", x.shape, "mean", float(x.mean()))
    rec = autotune.knobs_for((1, 34, 34, 3), (3, 3, 3, 8), stride=1, pad=0)
    print("layer-0 cached plan:", rec)

    # depthwise-separable block (MobileNet scenario, grouped kernel
    # path), same treatment: pack both convs at load time
    p = init_params(layers.depthwise_separable_params(3, x.shape[-1], 64),
                    jax.random.fold_in(rng, 99))
    p = layers.depthwise_separable_pack_params(p, x_shape=x.shape,
                                               stride=2)
    y = layers.depthwise_separable_apply(p, x, stride=2)
    print("depthwise-separable block output:", y.shape,
          "mean", float(y.mean()))

    print("\nFull VGG-16 per-layer OPs/Access/Slice (Fig. 6a):")
    for layer in vgg16_layers():
        row = compare_layer(layer)
        print(f"  {row['layer']:>18s}: 3D-TrIM {row['3d-trim']:.2f} "
              f"vs TrIM {row['trim']:.2f}  ({row['improvement']:.2f}x)")

    print("\nTPU-side ConvPlan traffic + roofline "
          "(same plan the kernel runs):")
    for layer in [vgg16_layers()[1]] + mobilenet_layers()[:2]:
        for dataflow in ("carry", "halo"):
            plan = layer.plan(dataflow=dataflow)
            t = plan.hbm_bytes()
            print(f"  {layer.name:>6s} [{dataflow:5s}]: input "
                  f"{t['input']/1e6:7.1f} MB "
                  f"(halo overhead {t['overhead_pct']:4.1f}%)  "
                  f"AI {plan.arithmetic_intensity():7.1f} flop/B")
        plan = layer.plan()
        terms = conv_plan_roofline(layer.name, plan)
        print(f"  {layer.name:>6s} roofline: "
              f"T_comp {terms.t_compute*1e6:.0f} us "
              f"T_mem {terms.t_memory*1e6:.0f} us -> "
              f"{terms.dominant}-bound, "
              f"grid {plan.grid}, tile_h {plan.tile_h}, "
              f"VMEM {plan.vmem_resident_bytes/2**20:.1f} MiB")


def report_degraded() -> None:
    """Print the guarded-dispatch demotion report (DESIGN.md §9): which
    tiers fell, to where, and why.  Silence means every conv ran on its
    intended tier — a degraded run is never mistaken for a healthy one."""
    evts = guard.events()
    if not evts:
        return
    print(f"\nDEGRADED MODE: {len(evts)} conv tier demotion(s) "
          f"(results remain correct via fallback):")
    for e in evts:
        where = f" [{e['layer']}]" if e.get("layer") else ""
        print(f"  {e['tier']} -> {e['to']}{where} ({e['kind']}): "
              f"{e['error'][:100]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--net", default=None,
                    choices=["vgg16", "alexnet", "mobilenet"],
                    help="run a full topology on tuned, packed plans "
                         "(default: the reduced-head demo)")
    ap.add_argument("--scale", type=int, default=8,
                    help="divide channel counts by this for the "
                         "executed configuration (accounting stays "
                         "full-scale)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--fused", action="store_true",
                    help="execute residency groups as fused megakernels "
                         "(conv->pool->conv chains VMEM-resident, "
                         "DESIGN.md §8) instead of one pallas_call per "
                         "layer; requires --net")
    args = ap.parse_args()
    use_compile_cache()
    if args.fused and not args.net:
        raise SystemExit("--fused needs --net (the reduced-head demo "
                         "has no fusion plan)")
    if args.net:
        run_network(args.net, args.scale, args.batch, fused=args.fused)
    else:
        run_demo()
    report_degraded()


if __name__ == "__main__":
    main()
