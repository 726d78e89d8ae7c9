"""Bring-up smoke run of the TrIM conv engine on a TPU.

One chip (no arguments): VGG-16 at its published widths and 224x224
inputs is served through ``ServingEngine`` on the per-layer Pallas conv
kernels (buckets 1, 2, 4, 8, prewarmed).  Seeded requests are replayed
through it, and every served row is checked against the engine's
unbatched forward (bit for bit) and against the XLA oracle at HIGHEST
matmul precision (max-abs error <= 1e-3 x max|oracle| on the logits).
One VGG-16 conv3 layer runs through the f32 kernel against the same
oracle, and once calibrated to int8 through the q8 kernel, which must
equal ``ref.conv2d_quantized`` bit for bit.

``--chips 4``: only the spatially sharded VGG-16 forward
(``mesh=make_conv_mesh(1, 4)``: output rows split over four chips with
a halo exchange of the K-1 boundary rows) and the same forward on one
chip.  They must agree within the cross-device policy (DESIGN.md §6,
1e-5 relative), and the sharded output must span the four devices.

Every phase runs in this one process.  Any failed check or raised phase
gives a non-zero exit.  The seconds printed time this smoke run,
compilation included; they are not benchmark numbers.  The last line
of standard output is one JSON object naming the device.  There is no
CPU fallback: without a TPU the script exits non-zero.

    python chip_smoke.py [--chips 4] [--seed 0]
"""

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
BUCKETS = (1, 2, 4, 8)
REQUESTS = 16
TOL_REF = 1e-3         # logits vs the HIGHEST-precision XLA oracle
TOL_SHARDED = 1e-5     # DESIGN.md §6 cross-device policy (f32)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max()) / max(float(np.abs(b).max()), 1e-30)


def vgg16_params(seed: int):
    import jax
    from repro.core.netplan import network_layers
    from repro.models import layers
    from repro.models.base import init_params
    topo = network_layers("vgg16")
    params = init_params(layers.cnn_params_from_layers(topo, n_classes=1000),
                         jax.random.PRNGKey(seed))
    return topo, params


def images(n: int, seed: int):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, 224, 224, 3)).astype(np.float32)


def oracle_forward(params, topo, x):
    """The XLA oracle (``impl="ref"``) at HIGHEST matmul precision."""
    import jax
    from repro.models import layers
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(lambda p, x: layers.cnn_apply_from_layers(
            p, topo, x, impl="ref"))
        return jax.device_get(fn(params, x))


def phase_serve(args) -> list[str]:
    """VGG-16 served through ServingEngine; rows vs forward_one and the
    oracle; no guard events, no cold tunes."""
    import numpy as np
    from repro.core.serving import ServingEngine, replay
    from repro.testing.load import poisson_arrivals

    topo, params = vgg16_params(args.seed)
    engine = ServingEngine.for_topology(topo, params, buckets=BUCKETS)
    t0 = time.perf_counter()
    records = engine.prewarm()
    prewarm_s = time.perf_counter() - t0
    plans = [(name, r.get("tile_h"), r.get("tile_cout"), r.get("dataflow"))
             for name, r in records[1]["layers"].items()]
    out = [f"bucket-1 plans (layer, tile_h, tile_cout, dataflow): {plans}",
           f"prewarm (tune + compile buckets {BUCKETS}): {prewarm_s:.1f} s "
           "[smoke-run timing, not a benchmark number]"]

    xs = images(REQUESTS, args.seed + 1)
    arrivals = poisson_arrivals(400.0, REQUESTS, seed=args.seed)
    trace = [(t, i, xs[i]) for i, t in enumerate(arrivals)]
    t0 = time.perf_counter()
    # a fixed service time makes the batch mix deterministic; the
    # forwards themselves run for real
    results, rejected = replay(engine, trace, service_model=lambda b: 0.02)
    serve_s = time.perf_counter() - t0
    stats = engine.stats()
    out.append(f"served {stats['served']} requests in {serve_s:.1f} s "
               f"[smoke-run timing], batches per bucket "
               f"{stats['bucket_batches']}")
    if rejected or sorted(results) != list(range(REQUESTS)):
        raise AssertionError(f"not every request served: rejected "
                             f"{rejected}, served {sorted(results)}")

    ref = oracle_forward(params, topo, xs)
    worst = 0.0
    for rid in range(REQUESTS):
        row = results[rid]
        if not np.array_equal(row, engine.forward_one(xs[rid])):
            raise AssertionError(f"request {rid}: served row != the "
                                 "unbatched forward_one")
        worst = max(worst, rel_err(row, ref[rid]))
    out.append(f"served rows == forward_one bit for bit; max error vs "
               f"HIGHEST oracle {worst:.3e} x max|oracle| "
               f"(bound {TOL_REF:g})")
    if worst > TOL_REF:
        raise AssertionError(f"logits error {worst:.3e} > {TOL_REF:g}")
    if stats["cold_tunes"] != 0:
        raise AssertionError(f"{stats['cold_tunes']} cold tunes")
    out.append("0 cold tunes")
    return out


def conv3_problem(seed: int):
    """One VGG-16 conv3_1 layer (56x56, 128 -> 256) on two images."""
    import jax
    import jax.numpy as jnp
    kx, kw, kb = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.nn.relu(jax.random.normal(kx, (2, 56, 56, 128), jnp.float32))
    w = jax.random.normal(kw, (3, 3, 128, 256), jnp.float32) / 34.0
    b = jax.random.normal(kb, (256,), jnp.float32) * 0.1
    return x, w, b


def phase_layer(args) -> list[str]:
    """The f32 kernel on one conv3 layer vs the HIGHEST oracle."""
    import jax
    from repro.kernels import ops, ref
    x, w, b = conv3_problem(args.seed)
    y = ops.conv2d(x, w, bias=b, activation="relu")
    with jax.default_matmul_precision("highest"):
        y_ref = ref.conv2d(x, w, bias=b, activation="relu")
    err = rel_err(y, y_ref)
    if err > TOL_REF:
        raise AssertionError(f"conv3_1 f32 kernel error {err:.3e}")
    return [f"conv3_1 f32 kernel vs HIGHEST oracle: {err:.3e} "
            f"x max|oracle| (bound {TOL_REF:g})"]


def phase_int8(args) -> list[str]:
    """One calibrated conv3 layer through the q8 tier, bit-exact."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.models import layers
    x, w, b = conv3_problem(args.seed)
    pq = layers.calibrate_conv2d({"w": w, "b": b}, x)
    pk = pq["packed"]
    y_q8 = layers.conv2d_apply(pq, x, activation="relu")
    x_q = ref.quantize_int8(x, pk.input_scale, pk.zero_point)
    w_scale = ref.weight_scales_int8(w)
    w_q = ref.quantize_int8(w, w_scale[None, None, None, :])
    y_oracle = ref.conv2d_quantized(
        x_q, w_q, x_scale=pk.input_scale, x_zero_point=pk.zero_point,
        w_scale=w_scale, bias=b, activation="relu")
    if not bool(jnp.array_equal(y_q8, y_oracle)):
        raise AssertionError(
            f"int8 kernel != ref.conv2d_quantized: "
            f"{int(np.sum(np.asarray(y_q8) != np.asarray(y_oracle)))} "
            "elements differ")
    return ["conv3_1 int8 (q8 tier) == ref.conv2d_quantized bit for bit"]


def phase_sharded(args) -> list[str]:
    """Spatially sharded VGG-16 forward on four chips vs one chip."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_conv_mesh
    from repro.models import layers

    topo, params = vgg16_params(args.seed)
    x = images(2, args.seed + 1)
    mesh = make_conv_mesh(1, 4)
    replicated = NamedSharding(mesh, P())
    fwd4 = jax.jit(lambda p, x: layers.cnn_apply_from_layers(
        p, topo, x, mesh=mesh))
    t0 = time.perf_counter()
    y4 = fwd4(jax.device_put(params, replicated),
              jax.device_put(x, replicated))
    y4.block_until_ready()
    t4 = time.perf_counter() - t0
    dev0 = jax.devices()[0]
    fwd1 = jax.jit(lambda p, x: layers.cnn_apply_from_layers(p, topo, x))
    t0 = time.perf_counter()
    y1 = fwd1(jax.device_put(params, dev0), jax.device_put(x, dev0))
    y1.block_until_ready()
    t1 = time.perf_counter() - t0
    err = rel_err(jax.device_get(y4), jax.device_get(y1))
    spans = len(y4.sharding.device_set)
    out = [f"first sharded forward (compile included) {t4:.1f} s, "
           f"one-chip {t1:.1f} s [smoke-run timings]",
           f"sharded vs one chip: {err:.3e} x max|one-chip| "
           f"(bound {TOL_SHARDED:g}); output spans {spans} devices"]
    if err > TOL_SHARDED:
        raise AssertionError(f"sharded != one chip: {err:.3e}")
    if spans != 4:
        raise AssertionError(f"output spans {spans} devices, not 4")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded forward and its "
                         "one-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and images")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(SRC, "repro")):
        say(f"FAIL: the repro sources are not next to this script "
            f"({SRC})")
        return 2
    sys.path.insert(0, SRC)
    # the run tunes its own plans: no autotune record from outside
    os.environ["REPRO_CONVTUNE_CACHE"] = os.path.join(
        ROOT, "artifacts", "chip_smoke", "convtune.json")
    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        say(f"FAIL: no TPU found (JAX reports platform {dev.platform!r})")
        return 1
    from repro.kernels.runtime import on_tpu
    if not on_tpu():
        say("FAIL: runtime.on_tpu() is False on a TPU: kernels would "
            "run in interpret mode")
        return 1
    if len(devices) < args.chips:
        say(f"FAIL: --chips {args.chips} needs {args.chips} devices, "
            f"JAX reports {len(devices)}")
        return 1
    say(f"device_kind={dev.device_kind!r} count={len(devices)} "
        f"jax={jax.__version__} compile cache: {cache_dir}")

    from repro.core import guard
    phases = ([("sharded", phase_sharded)] if args.chips == 4 else
              [("serve", phase_serve), ("layer", phase_layer),
               ("int8", phase_int8)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            for line in fn(args):
                say(f"[{name}] {line}")
            # a demotion would mean a tier other than the TrIM kernels
            # produced the result
            if guard.events():
                raise AssertionError(f"guard events: {guard.events()}")
            say(f"[{name}] ok, 0 guard events "
                f"({time.perf_counter() - t0:.1f} s)")
        except Exception:
            failed.append(name)
            say(f"[{name}] FAILED")
            traceback.print_exc()
            sys.stdout.flush()
    if failed:
        say(f"FAIL: phases {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
