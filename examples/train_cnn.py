"""End-to-end CNN *training* on the trim kernels (DESIGN.md §5).

The new training scenario: a small CIFAR-shaped classifier whose every
convolution — forward, input gradient and weight gradient — executes the
3D-TrIM Pallas dataflow.  ``ops.conv2d`` carries a ``jax.custom_vjp``
whose cotangents are TrIM convolutions themselves: the input gradient a
stride-dilated, spatially-flipped conv through the ordinary forward
kernel, the weight gradient the dedicated spatially-contracting strip
kernel.  Both are planned through ``ConvPlan.build_input_grad`` /
``ConvPlan.build_weight_grad``, and ``autotune.tune_backward`` seeds the
cache so the backward shapes run on tuned plans.

The task is synthetic but learnable: each class has a fixed random
template, samples are noisy mixtures, labels the template index.  Loss
must drop over 50 steps — the training acceptance criterion.

Data + spatial parallelism (DESIGN.md §6): ``--devices N --data D
--spatial S`` forces N host CPU devices and runs every conv through the
``shard_map`` halo-exchange path — images shard over the 'data' axis,
output H-strips over 'model', with the K-1 boundary rows exchanged by
``ppermute`` before each per-shard kernel (gradients transpose the
shuffle and psum the weight cotangents).

  PYTHONPATH=src python examples/train_cnn.py [--steps 50] [--json OUT]
  PYTHONPATH=src python examples/train_cnn.py --devices 4 --data 2 \
      --spatial 2 --steps 20
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
os.environ.setdefault("REPRO_CONVTUNE_CACHE", os.path.join(
    os.path.dirname(__file__), "..", "artifacts", "convtune.json"))

# --devices N must take effect before the first jax import (XLA reads
# the host-device flag at initialization; hostdevices is jax-free)
from repro.launch.hostdevices import force_host_device_count_from_argv
force_host_device_count_from_argv()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import autotune
from repro.kernels import ops
from repro.launch.compile_cache import use_compile_cache
from repro.models import layers
from repro.models.base import init_params
from repro.optim import AdamWConfig, adamw

IMAGE, CIN, N_CLASSES = 32, 3, 10
CHANNELS = (8, 16)


def make_batch(rng: np.random.Generator, templates: np.ndarray,
               batch: int):
    """Noisy class templates; labels are the template indices."""
    labels = rng.integers(0, N_CLASSES, size=batch)
    x = templates[labels] + 0.4 * rng.standard_normal(
        (batch, IMAGE, IMAGE, CIN))
    return jnp.asarray(x, jnp.float32), jnp.asarray(labels, jnp.int32)


def loss_fn(params, x, y, mesh=None):
    logits = layers.simple_cnn_apply(params, x, mesh=mesh)
    logp = jax.nn.log_softmax(logits)
    return -jnp.take_along_axis(logp, y[:, None], axis=1).mean()


def tune_backward_shapes(batch: int) -> None:
    """Seed the autotune cache for every backward conv shape the model
    trains through ('same' K=3 pre-pads by 1 per side)."""
    shapes, cur = [], (batch, IMAGE, IMAGE, CIN)
    for c in CHANNELS:
        shapes.append((cur, (3, 3, cur[3], c), 1, 1))          # conv_i
        shapes.append(((cur[0], cur[1], cur[2], c),
                       (3, 3, c, c), 2, 1))                    # down_i
        cur = (cur[0], cur[1] // 2, cur[2] // 2, c)
    c = CHANNELS[-1]
    up = (batch, IMAGE // 2, IMAGE // 2, c)
    shapes.insert(3, (up, (3, 3, 1, c), 1, c))                 # depthwise
    for (x_shape, w_shape, stride, groups) in shapes:
        # the exact (possibly asymmetric) 'same' pre-padded shape the
        # kernel sees — the shape the backward lookups are keyed over
        kshape, pad = ops.kernel_input_shape(x_shape, 3, stride, "same")
        autotune.tune_backward(kshape, w_shape, stride=stride, pad=pad,
                               groups=groups)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--json", default=None, metavar="OUT.json")
    ap.add_argument("--devices", type=int, default=1,
                    help="force N host CPU devices (handled pre-import) "
                         "for the sharded path on a CPU-only machine; "
                         "never on a TPU host, where it hides the chips")
    ap.add_argument("--data", type=int, default=1,
                    help="data-parallel shards (images over 'data')")
    ap.add_argument("--spatial", type=int, default=1,
                    help="spatial shards (output H-strips over 'model')")
    args = ap.parse_args()
    use_compile_cache()
    mesh = None
    if args.data * args.spatial > 1:
        from repro.launch.mesh import make_conv_mesh
        mesh = make_conv_mesh(args.data, args.spatial)
        if args.batch % args.data:
            raise SystemExit(f"--batch {args.batch} must divide over "
                             f"--data {args.data}")
        print(f"mesh: {args.data} x {args.spatial} devices "
              f"(data x spatial), convs on the shard_map halo path")

    rng = np.random.default_rng(0)
    templates = rng.standard_normal((N_CLASSES, IMAGE, IMAGE, CIN))

    params = init_params(
        layers.simple_cnn_params(cin=CIN, channels=CHANNELS,
                                 n_classes=N_CLASSES),
        jax.random.PRNGKey(0))
    opt_cfg = AdamWConfig(lr=1e-2, warmup_steps=3, decay_steps=300,
                          weight_decay=0.0)
    moments = adamw.init_moments(params, opt_cfg)

    if mesh is None:
        print("tuning backward conv shapes (persisted plan cache) ...")
        tune_backward_shapes(args.batch)

    @jax.jit
    def train_step(params, moments, step, x, y):
        # mesh rides as a closure constant (it is not a jax type)
        loss, grads = jax.value_and_grad(
            lambda p, xb, yb: loss_fn(p, xb, yb, mesh))(params, x, y)
        params, moments, metrics = adamw.apply_updates(
            params, grads, moments, step, opt_cfg)
        return params, moments, loss, metrics

    losses, t0 = [], time.perf_counter()
    for i in range(args.steps):
        x, y = make_batch(rng, templates, args.batch)
        params, moments, loss, metrics = train_step(
            params, moments, jnp.int32(i), x, y)
        losses.append(float(loss))
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  loss {losses[-1]:.4f}  "
                  f"|g| {float(metrics['grad_norm']):.3f}")
    dt = time.perf_counter() - t0

    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    print(f"\nloss {first:.4f} -> {last:.4f} over {args.steps} steps "
          f"({dt / args.steps * 1e3:.0f} ms/step, all convs on trim "
          f"kernels fwd+bwd)")
    if args.steps >= 40:              # the calibrated acceptance run
        assert last < first - 0.1, (
            f"training did not learn: {first:.4f} -> {last:.4f}")
        print("OK: loss decreased")
    if args.json:
        os.makedirs(os.path.dirname(os.path.abspath(args.json)),
                    exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(dict(losses=losses, steps=args.steps,
                           ms_per_step=dt / args.steps * 1e3), f)


if __name__ == "__main__":
    main()
