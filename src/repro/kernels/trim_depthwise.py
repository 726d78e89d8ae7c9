"""Depthwise 2D convolution (``groups == C``) as a TPU Pallas kernel on
the VPU (DESIGN.md §14).

A depthwise conv has nothing for the MXU to contract: each output is
K*K products of one channel.  So the channels ride the 128 lanes and
the taps are f32 multiply-adds on the vector unit, which round like the
plain product and sum (no bf16 passes, no precision knob).

* **Strips with carried rows.**  The grid is ``(image tiles, channel
  tiles, strips)``.  A strip is ``tile_h`` output rows, ``stride *
  tile_h`` fresh input rows; the ``K - stride`` input rows above them
  come from a VMEM scratch written by the previous strip (the paper's
  shadow registers; ``core.conv_plan.DepthwisePlan.carry_rows``).  The
  first strip of a sweep takes them from the input's ``head`` rows, a
  second small input, so no strip re-reads a row from HBM and no output
  row is thrown away.

* **Rows and taps.**  The taps run one output row at a time: a row is
  ``(W_out, tile_c)`` (a few vregs); tap ``(ki, kj)`` loads input row
  ``stride * r + ki`` from column ``kj`` on, every ``stride``-th column
  (a strided load: the chip's compiler cannot slice a loaded value with
  a stride on the sublane axis, but loads one with a stride), and the
  row's bias and activation are applied before its one store.

All geometry comes from :class:`~repro.core.conv_plan.DepthwisePlan`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_plan import KERNEL_VMEM_LIMIT, DepthwisePlan
from repro.kernels.runtime import resolve_interpret
from repro.kernels.trim_conv2d import ACTIVATIONS


def _kernel(x_ref, h_ref, w_ref, *rest, k: int, s: int, tile_h: int,
            tile_n: int, w_out: int, carry_rows: int, strips: int,
            activation: str | None, has_bias: bool):
    """One grid step: ``tile_h`` output rows of ``tile_n`` images for
    one channel tile."""
    b_ref = rest[0] if has_bias else None
    o_ref = rest[has_bias]
    # with one strip the head rows are the whole carry
    c_ref = rest[has_bias + 1] if strips > 1 else h_ref
    if strips > 1:
        @pl.when(pl.program_id(2) == 0)
        def _head():
            c_ref[...] = h_ref[...]

    def row(i, r, carried):
        """Output row ``r`` of image ``i``; ``carried``: a static ``r``
        whose window starts in the carried rows."""
        acc = None
        for ki in range(k):
            j = s * r + ki               # the window's row
            for kj in range(k):
                cols = pl.ds(kj, w_out, stride=s) if s > 1 \
                    else pl.ds(kj, w_out)
                if carried and j < carry_rows:
                    v = c_ref[i, j, cols, :]
                else:
                    v = x_ref[i, j - carry_rows, cols, :]
                t = v.astype(jnp.float32) \
                    * w_ref[pl.ds(ki * k + kj, 1), :].astype(jnp.float32)
                acc = t if acc is None else acc + t
        if has_bias:
            acc = acc + b_ref[...].astype(jnp.float32)
        o_ref[i, r] = ACTIVATIONS[activation](acc).astype(o_ref.dtype)

    first = min(-(-carry_rows // s), tile_h)    # rows reading the carry

    def image(i, c):
        for r in range(first):
            row(i, r, True)
        if tile_h > first:
            lax.fori_loop(first, tile_h,
                          lambda r, cy: (row(i, r, False), cy)[1], 0)
        if strips > 1:
            # shadow registers: the last rows of this strip, for the next
            c_ref[i] = x_ref[i, s * tile_h - carry_rows:s * tile_h]
        return c

    lax.fori_loop(0, tile_n, image, 0)


def _rows(x: jax.Array, start: int, stop: int) -> jax.Array:
    """Rows ``[start, stop)`` of ``x``, zero past its end."""
    return lax.pad(x, jnp.zeros((), x.dtype),
                   ((0, 0, 0), (-start, stop - x.shape[1], 0), (0, 0, 0),
                    (0, 0, 0)))


@functools.partial(jax.jit, static_argnames=(
    "stride", "tile_h", "tile_c", "tile_n", "activation", "interpret",
    "name"))
def trim_depthwise_conv2d(x: jax.Array, w: jax.Array,
                          bias: jax.Array | None = None, *,
                          stride: int = 1, tile_h: int | None = None,
                          tile_c: int | None = None,
                          tile_n: int | None = None,
                          activation: str | None = None,
                          interpret: bool | None = None,
                          name: str | None = None) -> jax.Array:
    """Depthwise conv of the padded input ``x`` ``(N, H, W, C)`` with
    ``w`` ``(K, K, 1, C)`` (one filter per channel), fused bias
    ``(C,)`` and activation; no further padding.

    ``tile_h`` (output rows per strip), ``tile_c`` (channels per step)
    and ``tile_n`` (images per step) default to
    :meth:`DepthwisePlan.build`'s choice.  ``name`` names the kernel, as
    :func:`~repro.kernels.trim_conv2d.trim_conv2d`'s does.  Returns
    ``(N, H_out, W_out, C)``."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"choose from {sorted(ACTIVATIONS, key=str)}")
    k = w.shape[0]
    if w.shape != (k, k, 1, x.shape[3]):
        raise ValueError(f"depthwise weights must be (K, K, 1, C) for "
                         f"C={x.shape[3]}, got {w.shape}")
    interpret = resolve_interpret(interpret)
    plan = DepthwisePlan.build(x.shape, k, stride=stride,
                               dtype_bytes=x.dtype, tile_h=tile_h,
                               tile_c=tile_c, tile_n=tile_n)
    c, x_c = plan.c, x.shape[3]
    if c != x_c:
        # whole lane tiles for the strided loads: zero channels past C
        more = ((0, 0),) * 3 + ((0, c - x_c),)
        x, w = jnp.pad(x, more), jnp.pad(w, more)
        bias = None if bias is None else jnp.pad(bias, more[-1])
    cr = plan.carry_rows
    main = _rows(x, cr, cr + plan.main_rows)
    # a plan without carried rows still passes one (unread) head row
    head = _rows(x, 0, max(cr, 1))
    tc, tn = plan.tile_c, plan.tile_n
    in_specs = [
        pl.BlockSpec(plan.main_block, lambda ni, ci, g: (ni, g, 0, ci)),
        pl.BlockSpec((tn,) + head.shape[1:3] + (tc,),
                     lambda ni, ci, g: (ni, 0, 0, ci)),
        pl.BlockSpec((k * k, tc), lambda ni, ci, g: (0, ci)),
    ]
    inputs = [main, head, w.reshape(k * k, c)]
    if bias is not None:
        inputs.append(bias.reshape(1, c))
        in_specs.append(pl.BlockSpec((1, tc), lambda ni, ci, g: (0, ci)))
    strips = plan.g_tiles if cr else 1
    scratch = [pltpu.VMEM(plan.head_block, x.dtype)] if strips > 1 else []
    kernel = functools.partial(
        _kernel, k=k, s=plan.stride, tile_h=plan.tile_h, tile_n=tn,
        w_out=plan.w_out, carry_rows=cr, strips=strips,
        activation=activation, has_bias=bias is not None)
    compiler_params = None
    if not interpret:
        # the carry serializes the strips of a sweep
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=KERNEL_VMEM_LIMIT)
    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(plan.out_block,
                               lambda ni, ci, g: (ni, g, 0, ci)),
        out_shape=jax.ShapeDtypeStruct(plan.padded_output_shape, x.dtype),
        scratch_shapes=scratch,
        compiler_params=compiler_params,
        interpret=interpret,
        name=name,
    )(*inputs)
    if out.shape[1:] != (plan.h_out, plan.w_out, x_c):
        out = out[:, :plan.h_out, :, :x_c]
    return out
