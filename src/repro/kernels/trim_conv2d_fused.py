"""Fused residency-group megakernel: conv→[pool]→conv chains in one
``pallas_call`` (DESIGN.md §8).

One grid step computes one *strip* of the group's final pooled output
and the whole stage chain feeding it, with every interior activation
resident in VMEM — the paper's shadow-register reuse lifted from
within-layer to between-layer.  The geometry comes from
:class:`~repro.core.fuse_plan.FusedGroup`: stage *i*'s input rows are an
affine window (``in_start + g*in_step``, ``in_rows`` wide) of stage
*i-1*'s pooled output, chained back to an overlapping element-offset
window of the HBM input (the only activation fetch the group pays).

Three design points keep this exactly equal to the per-layer path:

* **Identical tap math** — each stage runs the same ``(ki, kj)``-ordered
  tap loop as ``trim_conv2d._tap_matmuls``: fp32 accumulator, one MXU
  matmul per tap, bias added on the fp32 accumulator, activation, cast.
  A column split of the weight (per-layer ``tile_cout``) or a row split
  of the strip never changes an output element's reduction order, so
  the fused forward bit-matches the per-layer forward.

* **Masked rows ARE the next stage's padding** — rows of a strip buffer
  outside a stage's valid extent are forced to zero after pooling
  (a ``broadcasted_iota`` over global row indices), which makes them
  *exactly* the 'same'-padding zeros the next conv expects.  Valid
  pooled rows provably never read garbage conv rows: a valid pooled row
  ``r`` reads conv rows ``[r*ps, r*ps+pw) ⊆ [0, H_conv)``, and a valid
  conv row's window stays inside the 'same'-padded input.  W padding is
  applied in-kernel with ``jnp.pad`` (exact zeros).

* **Streamed weights** — weight tensors stay in HBM (``pl.ANY``) and
  one ``(Cin, Cout)`` tap slice at a time is DMA'd into a VMEM scratch
  buffer, so the VMEM working set is windows + accumulators + one tap
  per stage.  That is what makes 512-channel groups feasible at all.

Gradients: the fused op is a ``jax.custom_vjp`` whose backward pass
*recomputes* through the equivalent per-layer chain (``ops.conv2d`` +
max-pool) with ``jax.vjp`` — so cotangents run on the existing TrIM
backward kernels and training sees fused-forward speed at unchanged
gradient math (standard rematerialization).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret
from repro.kernels.trim_conv2d import ACTIVATIONS, F32_DOT_PRECISION


def _maxpool(x, stride, window):
    """VALID max-pool on NHWC, identical to ``models/layers._maxpool``."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


# ---------------------------------------------------------------------------
# Kernel body
# ---------------------------------------------------------------------------

def _stage_conv(buf, tap_load, b_ref, st, *, activation, dtype):
    """One conv stage on a resident row buffer: 'same' W-pad, the
    ``(ki, kj)``-ordered tap matmuls of ``trim_conv2d._tap_matmuls``
    (weights arriving via ``tap_load``), then the exact per-layer
    epilogue (fp32 bias add, activation, cast)."""
    k, s = st.kernel, st.stride
    xp = jnp.pad(buf, ((0, 0), (st.pad_lo, st.pad_hi), (0, 0)))
    acc = jnp.zeros((st.conv_rows * st.w_conv, st.cout), jnp.float32)
    for ki in range(k):
        for kj in range(k):
            tap = tap_load(ki, kj)                      # (Cin, Cout)
            rows = xp[ki: ki + (st.conv_rows - 1) * s + 1: s,
                      kj: kj + (st.w_conv - 1) * s + 1: s, :]
            acc += jnp.dot(rows.reshape(st.conv_rows * st.w_conv, st.cin),
                           tap, precision=F32_DOT_PRECISION
                           if buf.dtype == jnp.float32 else None,
                           preferred_element_type=jnp.float32)
    acc += b_ref[0].astype(jnp.float32)
    acc = ACTIVATIONS[activation](acc)
    return acc.reshape(st.conv_rows, st.w_conv, st.cout).astype(dtype)


def _stage_pool(y, st):
    """VALID max-pool of one stage's conv strip — a static max tree over
    the (pw x pw) shifted strided views, exactly ``reduce_window`` max."""
    if not st.pooled:
        return y
    ps, pw = st.pool_stride, st.pool_window
    out = None
    for wi in range(pw):
        for wj in range(pw):
            v = y[wi: wi + (st.pool_rows - 1) * ps + 1: ps,
                  wj: wj + (st.w_pool - 1) * ps + 1: ps, :]
            out = v if out is None else jnp.maximum(out, v)
    return out


def _fused_kernel(group, activation, dtype, *refs):
    """refs = x_ref, (w_ref, b_ref) per stage, o_ref, tap scratch per
    stage, DMA semaphore."""
    depth = group.depth
    x_ref = refs[0]
    wb = refs[1:1 + 2 * depth]
    o_ref = refs[1 + 2 * depth]
    taps = refs[2 + 2 * depth: 2 + 3 * depth]
    sem = refs[2 + 3 * depth]
    g = pl.program_id(1)

    buf = x_ref[...]                               # (in_rows0, W0, Cin0)
    for i, st in enumerate(group.stages):
        w_ref, b_ref, tap_ref = wb[2 * i], wb[2 * i + 1], taps[i]

        def tap_load(ki, kj, w_ref=w_ref, tap_ref=tap_ref):
            cp = pltpu.make_async_copy(w_ref.at[ki, kj], tap_ref, sem)
            cp.start()
            cp.wait()
            return tap_ref[...]

        y = _stage_conv(buf, tap_load, b_ref, st,
                        activation=activation, dtype=dtype)
        y = _stage_pool(y, st)
        # zero every row outside the stage's valid pooled extent: those
        # rows are garbage (bias-activated padding) and, once zeroed,
        # they are exactly the next stage's 'same' H-padding.
        start = st.pool_start + g * st.pool_step
        idx = jax.lax.broadcasted_iota(
            jnp.int32, (st.pool_rows, 1, 1), 0) + start
        buf = jnp.where((idx >= 0) & (idx < st.h_pool), y,
                        jnp.zeros_like(y))
    o_ref[0] = buf


# ---------------------------------------------------------------------------
# pallas_call wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("group", "activation", "interpret"))
def _fused_forward(x, weights, biases, *, group, activation, interpret):
    interpret = resolve_interpret(interpret)
    s0, lt = group.stages[0], group.last
    dtype = x.dtype
    xp = jnp.pad(x, ((0, 0), (group.extra_top, group.pad_bottom),
                     (0, 0), (0, 0)))

    # overlapping row windows: Mosaic takes all-Element blocks (batch
    # squeezed), see trim_conv2d._element_window
    in_specs = [pl.BlockSpec(
        (None, pl.Element(s0.in_rows), pl.Element(s0.w_in),
         pl.Element(s0.cin)),
        lambda n, g: (n, group.in_row_offset(g), 0, 0))]
    for st in group.stages:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        in_specs.append(pl.BlockSpec((1, st.cout), lambda n, g: (0, 0)))
    scratch = [pltpu.VMEM((st.cin, st.cout), dtype) for st in group.stages]
    scratch.append(pltpu.SemaphoreType.DMA)

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))

    operands = [xp]
    for w, b in zip(weights, biases):
        operands.append(w)
        operands.append(b.reshape(1, -1).astype(dtype))

    out = pl.pallas_call(
        functools.partial(_fused_kernel, group, activation, dtype),
        grid=(group.n, group.n_strips),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, group.strip_rows, lt.w_pool, lt.cout),
            lambda n, g: (n, g, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(group.padded_output_shape, dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=interpret,
    )(*operands)
    return out[:, :lt.h_pool]


def reference_chain(x, weights, biases, *, group, activation="relu",
                    impl="pallas", use_autotune_cache=False):
    """The per-layer execution of the same group: ``ops.conv2d`` (with
    its 'same' pre-pad and TrIM kernels) + a separate max-pool per
    stage.  This is both the differential-test oracle for the megakernel
    and the recompute path of its backward pass."""
    from repro.kernels import ops
    for st, w, b in zip(group.stages, weights, biases):
        padding = "same" if (st.pad_lo or st.pad_hi) else "valid"
        x = ops.conv2d(x, w, stride=st.stride, padding=padding,
                       impl=impl, bias=b, activation=activation,
                       use_autotune_cache=use_autotune_cache)
        if st.pooled:
            x = _maxpool(x, st.pool_stride, st.pool_window)
    return x


# ---------------------------------------------------------------------------
# custom_vjp: fused forward, per-layer recompute backward
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _fused_vjp(group, activation, interpret, x, weights, biases):
    return _fused_forward(x, weights, biases, group=group,
                          activation=activation, interpret=interpret)


def _fused_vjp_fwd(group, activation, interpret, x, weights, biases):
    out = _fused_forward(x, weights, biases, group=group,
                         activation=activation, interpret=interpret)
    return out, (x, weights, biases)


def _fused_vjp_bwd(group, activation, interpret, res, gy):
    x, weights, biases = res

    def chain(x_, ws_, bs_):
        return reference_chain(x_, ws_, bs_, group=group,
                               activation=activation)

    _, vjp = jax.vjp(chain, x, weights, biases)
    return vjp(gy)


_fused_vjp.defvjp(_fused_vjp_fwd, _fused_vjp_bwd)


def fused_group_apply(x, weights, biases, *, group, activation="relu",
                      interpret=None):
    """Run one fused residency group: ``x (N, H, W, Cin)`` through the
    group's conv→[pool] stage chain in a single megakernel.

    ``weights``/``biases`` are per-stage lists (``(K, K, Cin, Cout)``
    and ``(Cout,)``; pass ``None`` biases for zero).  Forward executes
    the fused Pallas kernel; gradients recompute through the per-layer
    chain so the backward kernels are the ordinary TrIM cotangent convs.
    """
    if len(weights) != group.depth or len(biases) != group.depth:
        raise ValueError(
            f"group depth {group.depth} needs {group.depth} weights/"
            f"biases, got {len(weights)}/{len(biases)}")
    s0 = group.stages[0]
    if x.shape != (group.n, s0.h_in, s0.w_in, s0.cin):
        raise ValueError(
            f"input {x.shape} does not match the group's stage-0 "
            f"problem {(group.n, s0.h_in, s0.w_in, s0.cin)}")
    for st, w in zip(group.stages, weights):
        if tuple(w.shape) != st.weight_shape:
            raise ValueError(
                f"stage {st.name}: weight {tuple(w.shape)} != planned "
                f"{st.weight_shape}")
    biases = tuple(
        jnp.zeros((st.cout,), x.dtype) if b is None else b
        for st, b in zip(group.stages, biases))
    return _fused_vjp(group, activation, interpret, x, tuple(weights),
                      biases)
