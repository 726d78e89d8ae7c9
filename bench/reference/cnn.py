"""Plain-JAX forward of a configuration's convolutional network.

The reference every answer of a run is compared with: each node of the
configuration (``cellspec.NetSpec``) as one ``lax`` operation (conv,
max pool padded with -inf, residual add summed in float32, concat,
nearest upsample), a ReLU after each node that states one, and the
global-mean-pool linear head.  It imports nothing of the program under
test and takes only what the benchmark made: the seeded weights and
inputs.

``precision`` selects the arithmetic of every conv and of the head:

* ``"highest"``: float32 at ``Precision.HIGHEST``, as the configurations
  state;
* ``"bf16x3"``: the control, the nearest precision below: each operand
  split into a bfloat16 high part and a bfloat16 remainder, and the
  three largest partial products summed in float32 (what XLA's
  ``Precision.HIGH`` does on a TPU).  Every partial product of bfloat16
  values is exact in float32, so the control reads the same on a CPU
  and on a TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("highest", "bf16x3")


def _bf16(a):
    # an explicit rounding op: XLA may drop an f32 -> bf16 -> f32
    # convert pair as excess precision, but not a reduce_precision
    return lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)


def _split(a):
    hi = _bf16(a)
    return hi, _bf16(a - hi)


def _product(op, a, b, precision):
    """``op(a, b)`` for a bilinear ``op`` at the given precision."""
    if precision == "highest":
        return op(a, b)
    ah, al = _split(a)
    bh, bl = _split(b)
    return op(ah, bh) + (op(ah, bl) + op(al, bh))


def _conv(x, w, node, precision):
    def op(a, b):
        return lax.conv_general_dilated(
            a, b, (node.stride, node.stride),
            [(node.pad, node.pad), (node.pad, node.pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=node.groups,
            precision=lax.Precision.HIGHEST)
    return _product(op, x, w, precision)


def forward(spec, params, x, *, precision: str = "highest"):
    """The network of ``spec`` on images ``x`` (N, H, W, C)."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    outs = {}
    y = x
    for nd in spec.nodes:
        src = [outs[i] for i in nd.inputs] or [x]
        if nd.op == "conv":
            p = params[nd.name]
            y = _conv(src[0], p["w"], nd, precision) + p["b"]
        elif nd.op == "pool":
            y = lax.reduce_window(src[0], -jnp.inf, lax.max,
                                  (1, nd.window, nd.window, 1),
                                  (1, nd.stride, nd.stride, 1),
                                  [(0, 0), (nd.pad, nd.pad),
                                   (nd.pad, nd.pad), (0, 0)])
        elif nd.op == "add":
            y = src[0].astype(jnp.float32)
            for s in src[1:]:
                y = y + s.astype(jnp.float32)
        elif nd.op == "concat":
            y = jnp.concatenate(src, axis=-1)
        elif nd.op == "upsample":
            y = jnp.repeat(jnp.repeat(src[0], nd.scale, axis=1),
                           nd.scale, axis=2)
        if nd.relu:
            y = jnp.maximum(y, 0.0)
        outs[nd.name] = y
    if spec.classes is None:
        return y
    head = params["head"]

    def dot(a, b):
        return jnp.dot(a, b, precision=lax.Precision.HIGHEST)
    return _product(dot, y.mean(axis=(1, 2)), head["w"], precision) \
        + head["b"]


def make_forward(spec, precision: str = "highest"):
    """``fn(params, x)``, jitted, for ``spec`` at ``precision``."""
    return jax.jit(lambda p, x: forward(spec, p, x, precision=precision))
