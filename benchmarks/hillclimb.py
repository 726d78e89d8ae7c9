"""Perf hillclimb runner: hypothesis -> change -> re-lower -> validate.

Each experiment is (cell, variant-overrides, hypothesis).  Variants change
sharding rules / plan knobs ONLY — model math is identical — and re-run
the dry-run analysis, producing a before/after roofline comparison that is
appended to artifacts/hillclimb.json and rendered for EXPERIMENTS.md §Perf.

``--conv <layer>`` hillclimbs the trim_conv2d ``ConvPlan`` knobs
(tile_h x tile_cout x dataflow) for one conv layer against the analytical
roofline — the same plan object the kernel executes, so the winning knobs
transfer directly to ``trim_conv2d(tile_h=..., tile_cout=...,
dataflow=...)``.  ``--measure`` additionally wall-clocks the top
candidates through the real kernel (slow in interpret mode; the true
refinement loop runs on TPU), and ``--write-cache`` persists the winner
into the autotune cache ``ops.conv2d`` consults by default — the sweep
seeds the cache.

  PYTHONPATH=src python -m benchmarks.hillclimb --exp <name> | --list
  PYTHONPATH=src python -m benchmarks.hillclimb --conv vgg16:conv2
  PYTHONPATH=src python -m benchmarks.hillclimb --conv mobilenet:dw1 \\
      --measure --write-cache
"""

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

ART = os.path.join(os.path.dirname(__file__), "..", "artifacts")


@dataclasses.dataclass
class Experiment:
    name: str
    arch: str
    shape: str
    hypothesis: str
    plan_overrides: dict
    cfg_overrides: dict = dataclasses.field(default_factory=dict)


EXPERIMENTS: dict[str, Experiment] = {}


def _reg(e: Experiment):
    EXPERIMENTS[e.name] = e


# --- cell A: qwen2.5-3b train (worst train-cell roofline fraction; the
# baseline 16-way Megatron TP pays ~2 psums of (B_loc, S, D) per layer) ---
_reg(Experiment(
    "qwen25-dp-zero3", "qwen2.5-3b", "train_4k",
    "TP psums dominate T_coll (the model is only 3B: TP is overkill). "
    "Re-map to pure ZeRO-3 data parallelism over all 256 chips (batch on "
    "(data, model); params FSDP over both axes): activation psums vanish; "
    "collective cost becomes per-layer weight all-gathers + gradient "
    "reduce-scatter ~ 3 * params_bytes << TP psum bytes. Predict T_coll "
    "5.3s -> <0.5s, dominant flips to compute.",
    dict(n_micro=1, fsdp=True,
         rules_overrides={"batch": ("pod", "data", "model"),
                          "embed": ("data", "model"),
                          "tokens": ("pod", "data", "model"),
                          "mlp": None, "heads": None, "kv_heads": None,
                          "vocab": None, "seq": None}),
))
_reg(Experiment(
    "qwen25-tp4-like", "qwen2.5-3b", "train_4k",
    "Half-measure control: keep TP but sequence-shard the psum boundary "
    "activations (Megatron-SP) so each TP psum becomes reduce-scatter + "
    "all-gather at 1/16 the resident size. Predict ~2x T_coll reduction "
    "(wire cost of RS+AG == AR, but bwd re-gathers shrink).",
    dict(rules_overrides={"seq": "model"}),
))

# --- cell B: qwen3-moe train (most collective-bound cell) ---
_reg(Experiment(
    "qwen3-ep-data", "qwen3-moe-30b-a3b", "train_4k",
    "The dispatch all-to-all boundary (g on data x e on model) plus TP "
    "psums dominate. Variant: experts on the DATA axis (EP=16 over data, "
    "dense/attention TP unchanged): dispatch becomes a data-axis "
    "all-to-all among the same devices that hold the tokens. Predict "
    "lower T_coll if expert traffic < TP traffic.",
    dict(n_micro=16, fsdp=True,
         rules_overrides={"experts": "data"}),
))
_reg(Experiment(
    "qwen3-zero3", "qwen3-moe-30b-a3b", "train_4k",
    "As with the dense 3B: drop TP entirely; ZeRO-3 over 256 chips with "
    "experts sharded on model only for the expert einsum. d_ff=768 per "
    "expert is tiny -> TP on mlp was pure overhead. Predict T_coll "
    "reduction >3x; compute term unchanged.",
    dict(n_micro=4, fsdp=True,
         rules_overrides={"batch": ("pod", "data", "model"),
                          "embed": ("data", "model"),
                          "tokens": ("pod", "data", "model"),
                          "mlp": None, "heads": None, "kv_heads": None,
                          "vocab": None, "seq": None}),
))

# --- cell C: falcon-mamba train (paper-technique representative:
# trim_conv1d + selective-scan dataflow) ---
_reg(Experiment(
    "mamba-zero3", "falcon-mamba-7b", "train_4k",
    "Mamba blocks are elementwise-heavy (scan) with TP only on d_inner "
    "projections; the psum of (B,S,4096) per layer dominates T_coll. "
    "ZeRO-3 re-map removes it. Predict dominant flips collective->compute.",
    dict(n_micro=2, fsdp=True,
         rules_overrides={"batch": ("pod", "data", "model"),
                          "embed": ("data", "model"),
                          "tokens": ("pod", "data", "model"),
                          "mlp": None, "heads": None, "kv_heads": None,
                          "vocab": None, "seq": None}),
))
_reg(Experiment(
    "mamba-scan-chunk-512", "falcon-mamba-7b", "train_4k",
    "Control on the compute term: doubling the selective-scan chunk from "
    "256 to 512 halves the number of chunk-boundary corrections (fewer "
    "cumprod ops) at 2x the chunk working set. Predict a small (<5%) "
    "T_compute reduction — refutation expected (associative scan flops "
    "are chunk-size-insensitive to first order).",
    dict(n_micro=2),
    cfg_overrides=dict(scan_chunk=512),
))

# --- cell: llama3-405b train (most collective-bound in the baseline) ---
_reg(Experiment(
    "llama-train-noSP", "llama3-405b", "train_4k",
    "The baseline cell's T_coll=1744s is dominated by 73TB of all-gathers "
    "that only appear in the unrolled Δ-compiles: the seq->model "
    "activation constraint forces a reshard around every unrolled "
    "attention chunk (the production scanned path reuses the gathered "
    "copy). Re-measure with the SP constraint dropped: predict T_coll "
    "collapses to the weight-gather + grad-reduce scale (~tens of "
    "seconds), exposing the true schedule. (Memory without SP grows by "
    "the saved-activation factor - kept as a measurement variant only.)",
    dict(n_micro=16, fsdp=True, moment_dtype="bfloat16",
         accum_dtype="bfloat16", rules_overrides={}),
))
_reg(Experiment(
    "llama-train-zero3", "llama3-405b", "train_4k",
    "Drop TP entirely (ZeRO-3 over 256 chips): per-layer weight "
    "all-gathers cost ~2*810GB/dev wire (~32s) vs compute ~67s -> "
    "overlappable, compute-bound, frac ~0.7. Tradeoff: saved activations "
    "lose the TP shard (memory +16x) -> needs offload/more remat; "
    "recorded as the roofline-optimal design point.",
    dict(n_micro=16, fsdp=True, moment_dtype="bfloat16",
         accum_dtype="bfloat16",
         rules_overrides={"batch": ("pod", "data", "model"),
                          "embed": ("data", "model"),
                          "tokens": ("pod", "data", "model"),
                          "mlp": None, "heads": None, "kv_heads": None,
                          "vocab": None, "seq": None}),
))

# --- decode cell (worst absolute roofline fraction): llama3-405b decode ---
_reg(Experiment(
    "llama-decode-int8kv", "llama3-405b", "decode_32k",
    "Decode is bandwidth-bound: T_mem = (params + KV cache)/BW. An int8 "
    "KV cache halves the cache term. Predict T_mem reduction by "
    "cache/(params+cache) * 1/2.",
    dict(fsdp=True, rules_overrides={"seq": "model"}),
    cfg_overrides=dict(),   # int8 cache handled via kv_cache_dtype below
))


# ---------------------------------------------------------------------------
# Conv-kernel hillclimb: sweep ConvPlan knobs against the analytical roofline
# ---------------------------------------------------------------------------

def _conv_layer(name: str):
    from repro.core import alexnet_layers, mobilenet_layers, vgg16_layers
    nets = {"vgg16": vgg16_layers, "alexnet": alexnet_layers,
            "mobilenet": mobilenet_layers}
    net, _, lname = name.partition(":")
    if net not in nets:
        raise SystemExit(f"unknown network {net!r}; have {sorted(nets)}")
    layers = nets[net]()
    if not lname:
        return layers[0]
    for l in layers:
        if l.name == lname:
            return l
    raise SystemExit(f"unknown layer {lname!r} in {net}; "
                     f"have {[l.name for l in layers]}")


def conv_hillclimb(name: str, dataflows=("carry", "halo"), *,
                   measure: bool = False, measure_top_k: int = 4,
                   write_cache: bool = False) -> dict:
    """Grid-sweep (tile_h, tile_cout, dataflow) for one layer; score by
    the modeled step time max(T_comp, T_mem) — each dataflow billed its
    own traffic mode — with a VMEM feasibility constraint.

    ``measure=True`` wall-clocks the ``measure_top_k`` model-best
    candidates through the actual Pallas kernel and re-ranks by measured
    us.  ``write_cache=True`` persists the winner into the autotune cache
    under the key ``ops.conv2d`` looks up for this layer's input.
    """
    from repro.core import autotune
    from repro.core.conv_plan import KERNEL_VMEM_BUDGET, ConvPlan
    from repro.core.roofline import conv_plan_roofline
    from repro.kernels.ops import kernel_input_shape
    layer = _conv_layer(name)
    w_shape = (layer.kernel, layer.kernel,
               layer.in_channels // layer.groups, layer.out_channels)
    # sweep (and key) the problem ops.conv2d actually runs: the 'same'
    # pre-pad folded into the input shape — asymmetric for stride > 1,
    # NOT the layer's symmetric paper padding — with residual pad 0
    x_shape, pad = kernel_input_shape(
        (1, layer.ifmap, layer.ifmap, layer.in_channels), layer.kernel,
        layer.stride, "same" if layer.padding else "valid")
    baseline = ConvPlan.build(x_shape, w_shape, stride=layer.stride,
                              pad=pad, groups=layer.groups)
    base_t = conv_plan_roofline(layer.name, baseline).step_time_s
    # same candidate generator and ranking the autotuner uses — the sweep
    # and `autotune.tune` cannot pick different winners for one layer
    plans = [p for p in autotune.candidate_knobs(
                 x_shape, w_shape, stride=layer.stride, pad=pad,
                 groups=layer.groups)
             if p.dataflow in dataflows]
    ranked = sorted(plans, key=autotune._model_score)

    def _row(p):
        return dict(tile_h=p.tile_h, tile_cout=p.tile_cout,
                    dataflow=p.dataflow,
                    step_time_s=conv_plan_roofline(layer.name,
                                                   p).step_time_s,
                    vmem_mib=p.vmem_resident_bytes / 2**20,
                    hbm_mb=p.hbm_bytes()["total"] / 1e6,
                    ai=p.arithmetic_intensity())

    rows = [_row(p) for p in ranked]
    if measure and rows:
        for plan, row in zip(ranked[:measure_top_k],
                             rows[:measure_top_k]):
            row["measured_us"] = autotune._measure_plan(
                plan, stride=layer.stride, pad=pad, groups=layer.groups)
        best = min(rows[:measure_top_k], key=lambda r: r["measured_us"])
    else:
        best = rows[0] if rows else None
    result = dict(experiment=f"conv:{name}",
                  dataflows=list(dataflows), measured=measure,
                  baseline=dict(tile_h=baseline.tile_h,
                                tile_cout=baseline.tile_cout,
                                dataflow=baseline.dataflow,
                                step_time_s=base_t,
                                budget=KERNEL_VMEM_BUDGET),
                  best=best, n_candidates=len(rows), sweep=rows)
    if write_cache and best is not None:
        key = autotune.make_key(x_shape, w_shape, stride=layer.stride,
                                pad=pad, groups=layer.groups)
        path = autotune.store(key, dict(
            tile_h=best["tile_h"], tile_cout=best["tile_cout"],
            dataflow=best["dataflow"],
            source="measured" if measure else "model",
            model_step_time_s=best["step_time_s"],
            measured_us=best.get("measured_us")))
        result["cache_key"], result["cache_path"] = key, path
    return result


def run_variant(exp: Experiment) -> dict:
    from repro.configs import registry
    from repro.launch import dryrun
    mod = registry.get(exp.arch)
    plan = mod.PLANS[exp.shape]
    for k, v in exp.plan_overrides.items():
        plan = plan.replace(**{k: v})
    cfg = mod.CONFIG.replace(**exp.cfg_overrides) if exp.cfg_overrides \
        else mod.CONFIG

    # monkeypatch the registry entry the dryrun reads
    orig_cfg, orig_plans = mod.CONFIG, mod.PLANS
    try:
        mod.CONFIG = cfg
        mod.PLANS = dict(orig_plans)
        mod.PLANS[exp.shape] = plan
        row = dryrun.run_cell(exp.arch, exp.shape, multi_pod=False)
    finally:
        mod.CONFIG, mod.PLANS = orig_cfg, orig_plans
    row["experiment"] = exp.name
    row["hypothesis"] = exp.hypothesis
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--exp", default=None)
    ap.add_argument("--baseline", action="store_true",
                    help="run the unmodified cell for comparison")
    ap.add_argument("--arch"), ap.add_argument("--shape")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--conv", default=None, metavar="NET[:LAYER]",
                    help="hillclimb ConvPlan knobs, e.g. vgg16:conv2")
    ap.add_argument("--dataflow", default="both",
                    choices=["carry", "halo", "both"],
                    help="which conv dataflow(s) to sweep")
    ap.add_argument("--mode", default=None, choices=["3dtrim", "trim"],
                    help="legacy accounting alias: 3dtrim=carry, "
                         "trim=halo")
    ap.add_argument("--measure", action="store_true",
                    help="wall-clock the top conv candidates through the "
                         "real kernel (slow in interpret mode)")
    ap.add_argument("--write-cache", action="store_true",
                    help="persist the winning conv knobs into the "
                         "autotune cache ops.conv2d consults")
    args = ap.parse_args()
    if args.list:
        for name, e in EXPERIMENTS.items():
            print(f"{name}: {e.arch}/{e.shape}")
        return
    os.makedirs(ART, exist_ok=True)
    if args.conv:
        if args.mode is not None:
            dataflows = ("carry",) if args.mode == "3dtrim" else ("halo",)
        elif args.dataflow == "both":
            dataflows = ("carry", "halo")
        else:
            dataflows = (args.dataflow,)
        res = conv_hillclimb(args.conv, dataflows, measure=args.measure,
                             write_cache=args.write_cache)
        b, base = res["best"], res["baseline"]
        print(json.dumps(dict(experiment=res["experiment"],
                              baseline=base, best=b,
                              speedup=base["step_time_s"]
                              / max(b["step_time_s"], 1e-12)), indent=1))
        if "cache_path" in res:
            print(f"cached {res['cache_key']} -> {res['cache_path']}")
        out_path = os.path.join(ART, "conv_hillclimb.json")
        results = json.load(open(out_path)) if os.path.exists(out_path) \
            else []
        results.append(res)
        json.dump(results, open(out_path, "w"), indent=1)
        print("appended to", out_path)
        return
    # dry-run path only: the 512-device mesh must be configured before
    # the first jax backend initialization (--conv/--list never need it)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    out_path = os.path.join(ART, "hillclimb.json")
    results = []
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    if args.baseline:
        from repro.launch import dryrun
        row = dryrun.run_cell(args.arch, args.shape, multi_pod=False)
        row["experiment"] = f"baseline:{args.arch}/{args.shape}"
    else:
        row = run_variant(EXPERIMENTS[args.exp])
    rf = row.get("roofline", {})
    print(json.dumps({k: rf.get(k) for k in
                      ("t_compute_s", "t_memory_s", "t_collective_s",
                       "dominant", "roofline_fraction")}, indent=1))
    results.append(row)
    json.dump(results, open(out_path, "w"), indent=1)
    print("appended to", out_path)


if __name__ == "__main__":
    main()
