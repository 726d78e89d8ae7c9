"""Public jit'd operator API over the Pallas kernels and their oracles.

Every op takes ``impl``:

  * ``"pallas"``  — the Pallas TPU kernel (interpret=True on CPU).
  * ``"ref"``     — the pure-jnp oracle (kernels/ref.py).
  * ``"chunked"`` — (attention only) FlashAttention algorithm expressed in
    pure jnp with a ``lax.scan`` over KV chunks: identical O(L) memory
    behaviour to the kernel, XLA-fusable, dry-run friendly.
  * ``"chunked_unroll"`` — same, with a Python loop instead of the scan.
    Used by the dry-run Δ-cost compiles, because XLA's HloCostAnalysis
    counts while-loop bodies once (verified on this backend) and would
    undercount scanned flops.

``conv2d`` applies the paper's §III kernel tiling for K > MAX_NATIVE_K:
the kernel is decomposed into 3x3-ish sub-kernels whose partial outputs
are accumulated — the adder-tree path.

``conv2d`` consults the autotune cache (``core/autotune.py``) by default:
any ``tile_h`` / ``tile_cout`` / ``dataflow`` knob the caller leaves unset
is filled from the persisted per-(shape, dtype, backend) record when one
exists.  ``pack_conv2d_weights`` performs the kernel's weight pad/reshape
once at load time; passing the resulting :class:`PackedConv2dWeights` as
``w`` skips the per-call packing in the hot path entirely.

``conv2d`` / ``depthwise_conv2d`` are fully differentiable
(DESIGN.md §5): a ``jax.custom_vjp`` runs both cotangents as TrIM
convolutions (``trim_conv2d_input_grad`` / ``trim_conv2d_weight_grad``),
consulting the autotune cache under the backward problems' own keys.
Packed weights receive packed-layout cotangents; the K > MAX_NATIVE_K
adder-tree path differentiates through each sub-kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import typing

from repro.core import autotune, guard
from repro.core.conv_plan import LANES, ConvPlan, input_grad_geometry
from repro.core.conv_shard import ShardedConvPlan, resolve_conv_mesh
from repro.core.tiling import subkernel_decomposition
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.trim_conv1d import trim_conv1d
from repro.kernels.trim_conv2d import (ACTIVATIONS, trim_conv2d,
                                       trim_conv2d_input_grad,
                                       trim_conv2d_weight_grad)
from repro.kernels.trim_conv2d_sharded import sharded_conv2d
from repro.kernels.trim_depthwise import trim_depthwise_conv2d

MAX_NATIVE_K = 8


# ---------------------------------------------------------------------------
# Convolutions
# ---------------------------------------------------------------------------

def _same_pads(size: int, k: int, s: int) -> tuple[int, int]:
    """XLA 'SAME' padding: out = ceil(size/s), possibly asymmetric."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return total // 2, total - total // 2


def conv_pads(h: int, w: int, k: int, stride: int, padding):
    """``((top, bottom), (left, right))`` zero padding of a conv.
    ``padding`` is ``"same"`` (XLA's: ``ceil(size/stride)`` outputs,
    asymmetric where the stride leaves a remainder), ``"valid"``, or an
    int ``p``: the symmetric ``(p, p)`` a network layer states (a
    3x3/s2 conv on an even input pads ``(1, 1)`` where 'same' pads
    ``(0, 1)``)."""
    if padding == "same":
        return _same_pads(h, k, stride), _same_pads(w, k, stride)
    if padding == "valid":
        return (0, 0), (0, 0)
    if isinstance(padding, int) and not isinstance(padding, bool) \
            and padding >= 0:
        return (padding, padding), (padding, padding)
    raise ValueError(f"padding={padding!r} must be 'same', 'valid' or a "
                     "symmetric pad >= 0")


def _prepad(x: jax.Array, k: int, stride: int, padding) -> jax.Array:
    """``x`` zero-padded in HBM as :func:`conv_pads` says."""
    ph, pw = conv_pads(x.shape[1], x.shape[2], k, stride, padding)
    if ph == pw == (0, 0):
        return x
    return jnp.pad(x, ((0, 0), ph, pw, (0, 0)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedConv2dWeights:
    """Conv weights pre-packed into the kernel's padded HBM layout.

    ``w`` is ``ConvPlan.padded_weight_shape`` for the frozen
    ``(groups, tile_cout)``; ``bias`` (optional) is the padded
    ``(1, groups * cout_padded_per_group)`` row the kernel streams
    per C_out tile.  ``tile_h`` / ``dataflow`` are optional tuned hints
    (e.g. from the autotune cache at pack time) applied when the call
    site doesn't override them.  Registered as a pytree (arrays are
    leaves, knobs are static) so packed params live in checkpointed /
    jitted parameter trees like any other weight.

    The int8 route (DESIGN.md §11) adds three quantization leaves,
    produced by :func:`quantize_conv2d_weights` /
    ``models.layers.calibrate_conv2d``: ``scale`` is the per-out-channel
    symmetric *weight* scale in the same padded ``(1, G * CoutP)`` row
    layout as ``bias`` (padded lanes hold 1.0 so the bias
    requantization of ``ref.dequant_params`` never divides by zero);
    ``zero_point`` / ``input_scale`` are the scalar per-tensor affine
    activation calibration.  A non-None ``scale`` is what routes
    ``ops.conv2d`` onto the quantized tier chain; ``w`` is then int8
    and ``bias`` stays the original f32 row (the effective int32 bias
    is derived per call).
    """

    w: jax.Array
    bias: jax.Array | None
    cout: int
    groups: int
    tile_cout: int
    tile_h: int | None = None
    dataflow: str | None = None
    scale: jax.Array | None = None
    zero_point: jax.Array | None = None
    input_scale: jax.Array | None = None

    def tree_flatten(self):
        return ((self.w, self.bias, self.scale, self.zero_point,
                 self.input_scale),
                (self.cout, self.groups, self.tile_cout, self.tile_h,
                 self.dataflow))

    @classmethod
    def tree_unflatten(cls, aux, children):
        w, bias, scale, zero_point, input_scale = children
        cout, groups, tile_cout, tile_h, dataflow = aux
        return cls(w=w, bias=bias, cout=cout, groups=groups,
                   tile_cout=tile_cout, tile_h=tile_h, dataflow=dataflow,
                   scale=scale, zero_point=zero_point,
                   input_scale=input_scale)


def pack_conv2d_weights(w: jax.Array, bias: jax.Array | None = None, *,
                        groups: int = 1, tile_cout: int | None = None,
                        tile_h: int | None = None,
                        dataflow: str | None = None,
                        x_shape=None, stride: int = 1,
                        padding: str | int = "same",
                        dtype: str | None = None,
                        op: str = "conv2d") -> PackedConv2dWeights:
    """Pad/reshape conv weights to the kernel layout once, at load time.

    w: (K, K, Cin/groups, Cout); bias: (Cout,) or None.  The packed
    layout is fixed by ``(groups, tile_cout)``; ``tile_cout`` defaults to
    the plan's MXU-friendly choice.  When ``x_shape`` is given and knobs
    are unset, the autotune cache is consulted (same key ``conv2d`` would
    use for that input) so the packed layout matches the tuned plan.
    ``dtype`` defaults to the *weight* dtype (the activations of a
    homogeneous network match it); pass it explicitly for mixed-dtype
    call sites so the cache consult keys on the activation dtype the
    conv will actually run with.  ``op`` picks the cache namespace
    (``"conv2d_q8"`` for the int8 route).
    """
    kh, kw, cin_pg, cout = w.shape
    if kh > MAX_NATIVE_K:
        raise ValueError(
            f"K={kh} > {MAX_NATIVE_K}: the kernel-tiled path re-slices "
            "weights per sub-kernel and cannot consume packed weights")
    if cout % groups:
        raise ValueError(f"groups={groups} must divide cout={cout}")
    if dtype is None:
        dtype = str(w.dtype)
    if x_shape is not None and (tile_cout is None or tile_h is None
                                or dataflow is None):
        xs, pad = kernel_input_shape(x_shape, kh, stride, padding)
        rec = autotune.knobs_for(xs, w.shape, stride=stride, pad=pad,
                                 groups=groups, dtype=dtype, op=op)
        if rec is not None:
            tile_cout = tile_cout if tile_cout is not None \
                else rec["tile_cout"]
            tile_h = tile_h if tile_h is not None else rec["tile_h"]
            dataflow = dataflow if dataflow is not None else rec["dataflow"]
    # the padded layout is the plan's, not a re-derivation (the spatial
    # dims are irrelevant to the weight layout — any kernel-sized input
    # yields the same padded_weight_shape)
    plan = ConvPlan.build((1, kh, kw, cin_pg * groups), w.shape,
                          groups=groups, tile_cout=tile_cout)
    tile_cout, cpp = plan.tile_cout, plan.cout_padded_per_group
    cout_pg = plan.cout_per_group
    wk = w.reshape(kh, kw, cin_pg, groups, cout_pg)
    wk = jnp.pad(wk, ((0, 0),) * 4 + ((0, cpp - cout_pg),))
    wk = wk.reshape(plan.padded_weight_shape)
    bp = None
    if bias is not None:
        bp = jnp.pad(bias.reshape(groups, cout_pg),
                     ((0, 0), (0, cpp - cout_pg))).reshape(1, groups * cpp)
    return PackedConv2dWeights(w=wk, bias=bp, cout=cout, groups=groups,
                               tile_cout=tile_cout, tile_h=tile_h,
                               dataflow=dataflow)


def quantize_conv2d_weights(w: jax.Array, bias: jax.Array | None = None, *,
                            x_scale, x_zero_point=0, groups: int = 1,
                            tile_cout: int | None = None,
                            tile_h: int | None = None,
                            dataflow: str | None = None,
                            x_shape=None, stride: int = 1,
                            padding: str | int = "same"
                            ) -> PackedConv2dWeights:
    """Quantize + pack conv weights for the int8 route (DESIGN.md §11).

    w: f32 (K, K, Cin/groups, Cout); bias: (Cout,) or None.
    Per-out-channel symmetric weight scales (``ref.weight_scales_int8``),
    per-tensor affine activation calibration ``(x_scale, x_zero_point)``
    — typically from ``models.layers.calibrate_conv2d`` over a sample
    batch.  Returns a :class:`PackedConv2dWeights` whose non-None
    ``scale`` routes ``ops.conv2d`` onto the quantized tier chain.
    """
    w_scale = ref.weight_scales_int8(w)
    w_q = ref.quantize_int8(w, w_scale[None, None, None, :])
    pk = pack_conv2d_weights(w_q, None, groups=groups, tile_cout=tile_cout,
                             tile_h=tile_h, dataflow=dataflow,
                             x_shape=x_shape, stride=stride,
                             padding=padding, dtype="int8", op="conv2d_q8")
    cpp = pk.w.shape[3] // groups
    cout_pg = pk.cout // groups
    # padded lanes hold scale 1.0 (not 0): ref.dequant_params divides the
    # real bias by the scale, and 0-scale lanes would round NaN to int32
    sp = jnp.pad(w_scale.reshape(groups, cout_pg),
                 ((0, 0), (0, cpp - cout_pg)),
                 constant_values=1.0).reshape(1, groups * cpp)
    bp = None
    if bias is not None:
        bp = jnp.pad(bias.astype(jnp.float32).reshape(groups, cout_pg),
                     ((0, 0), (0, cpp - cout_pg))).reshape(1, groups * cpp)
    return dataclasses.replace(
        pk, bias=bp, scale=sp,
        zero_point=jnp.asarray(x_zero_point, jnp.int32),
        input_scale=jnp.asarray(x_scale, jnp.float32))


def _fold_taps(x: jax.Array, kh: int, kw: int, stride: int,
               rows: int) -> jax.Array:
    """The tap-folded input of a ``folded`` :func:`conv_route` (an XLA
    im2col): the KH*KW shifted, strided views of the pre-padded ``x``,
    on the channel axis, tap-major, with zero rows below ``H_out`` up to
    ``rows`` (the 1x1 plan's ``rows_padded``: whole strips).

    The views are stacked channel-major, ``(N, KH*KW*C, rows, W_out)``,
    where each is a block of compact rows, and moved to the channel-last
    layout in one copy.  The barrier keeps XLA from choosing the
    kernel's 128-lane layout for the stack itself, which turned each
    view into a lane-padded copy (over 40 ms a step for U-Net's
    ``(8, 512, 512, 1)`` input on a v5e).  At stride 1 each view is a
    ``lax.slice``.  A stride > 1 would put a stride on W, the lane axis,
    which the chip moves at a few percent of its HBM bandwidth; so there
    the views are taken by :func:`_select_strided_taps`, two exact 0/1
    contractions on the MXU (DESIGN.md §15)."""
    h_out = (x.shape[1] - kh) // stride + 1
    w_out = (x.shape[2] - kw) // stride + 1
    if stride > 1:
        return _select_strided_taps(jnp.moveaxis(x, 3, 1), kh, kw, stride,
                                    rows, w_out)
    x = jnp.pad(jnp.moveaxis(x, 3, 1),
                ((0, 0), (0, 0), (0, (rows - h_out) * stride), (0, 0)))
    n, c = x.shape[:2]
    taps = jnp.concatenate(
        [jax.lax.slice(x, (0, 0, ki, kj),
                       (n, c, ki + (rows - 1) * stride + 1,
                        kj + (w_out - 1) * stride + 1),
                       (1, 1, stride, stride))
         for ki in range(kh) for kj in range(kw)], axis=1)
    return jnp.moveaxis(jax.lax.optimization_barrier(taps), 1, 3)


def _select_strided_taps(x: jax.Array, kh: int, kw: int, stride: int,
                         rows: int, w_out: int) -> jax.Array:
    """:func:`_fold_taps` of the channel-major ``x`` ``(N, C, H_p, W_p)``
    at ``stride > 1``, with no stride on the lane axis: a contraction of
    W with ``S`` ``(W_p, KW, W_out)``, ``S[w, kj, j] = 1`` iff ``w ==
    stride*j + kj``, takes the KW column phases lane-dense, and one of H
    with ``R`` ``(KH, rows, H_p)``, ``R[ki, i, h] = 1`` iff ``h ==
    stride*i + ki``, the KH row phases, written straight in the tap-major
    channel-last order; rows past ``H_p`` select nothing and stay zero,
    as the strided slices of the zero-padded input read them.  Each
    output sums one input value times 1 and zeros in f32, so at
    ``HIGHEST`` every value comes back exactly."""
    n, c, hp, wp = x.shape
    sel_w = np.equal.outer(np.arange(wp),
                           stride * np.arange(w_out) + np.arange(kw)[:, None])
    sel_h = np.equal.outer(stride * np.arange(rows) + np.arange(kh)[:, None],
                           np.arange(hp))
    hi = jax.lax.Precision.HIGHEST
    cols = jnp.einsum("nchw,wkj->nchkj", x, sel_w.astype(x.dtype),
                      precision=hi)
    taps = jnp.einsum("aih,nchkj->nijakc", sel_h.astype(x.dtype), cols,
                      precision=hi)
    return taps.reshape(n, rows, w_out, kh * kw * c)


# the autotune namespace each route kind reads and the tuner writes (the
# adder tree reads none: records describe the full-K problem)
_ROUTE_OPS = {"depthwise": autotune.DEPTHWISE_OP, "folded": "conv2d",
              "mxu": "conv2d", "tiled": None}


@dataclasses.dataclass(frozen=True)
class ConvRoute:
    """Which kernel runs a conv (``kind``, :func:`conv_route`) and the
    problem it runs: the folded stride-1 1x1 for ``"folded"``, the
    conv's own otherwise (DESIGN.md §16)."""

    kind: str        # "depthwise" | "folded" | "mxu" | "tiled"
    x_shape: tuple
    w_shape: tuple
    stride: int
    groups: int

    def key(self, dtype: str, backend: str | None = None) -> str | None:
        """The autotune key the route reads and the tuner writes."""
        op = _ROUTE_OPS[self.kind]
        return None if op is None else autotune.make_key(
            self.x_shape, self.w_shape, stride=self.stride, pad=0,
            groups=self.groups, dtype=dtype, backend=backend, op=op)

    def knobs(self, dtype: str) -> dict | None:
        """The validated record under :meth:`key`, or None."""
        if self.kind == "depthwise":
            return autotune.depthwise_knobs_for(
                self.x_shape, self.w_shape[0], stride=self.stride,
                dtype=dtype)
        op = _ROUTE_OPS[self.kind]
        return None if op is None else autotune.knobs_for(
            self.x_shape, self.w_shape, stride=self.stride, pad=0,
            groups=self.groups, dtype=dtype, op=op)


def conv_route(x_shape, w_shape, *, stride: int, groups: int,
               dtype) -> ConvRoute:
    """The one place that decides which kernel the single-device float
    path runs a conv on (DESIGN.md §16), over the kernel-seen input
    (:func:`kernel_input_shape`) and ``(K, K, Cin/groups, Cout)``
    weights, in order: ``depthwise`` (the VPU kernel, §14: ``groups ==
    C_in == C_out > 1``, float), ``folded`` (§13: ``groups == 1``,
    ``K > 1`` and ``K*K*Cin`` fits one lane tile, two at stride > 1,
    run as a 1x1 of ``(N, H_out, W_out, K*K*Cin)`` by ``(1, 1, K*K*Cin,
    Cout)``), ``mxu`` (the MXU strip kernel; strided taps at stride > 1,
    §17), each for ``K <= MAX_NATIVE_K``, else ``tiled`` (the adder
    tree)."""
    n, h, w, cin = x_shape
    k, kw, cin_pg, cout = w_shape
    fold_lanes = LANES if stride == 1 else 2 * LANES
    if k > MAX_NATIVE_K:
        kind = "tiled"
    elif (groups > 1 and cin_pg == 1 and groups == cin == cout
          and jnp.issubdtype(jnp.dtype(dtype), jnp.floating)):
        kind = "depthwise"
    elif groups == 1 and k > 1 and k * kw * cin <= fold_lanes:
        taps = k * kw * cin
        return ConvRoute("folded", (n, (h - k) // stride + 1,
                                    (w - kw) // stride + 1, taps),
                         (1, 1, taps, cout), 1, 1)
    else:
        kind = "mxu"
    return ConvRoute(kind, tuple(x_shape), tuple(w_shape), stride, groups)


def kernel_input_shape(x_shape, k: int, stride: int, padding):
    """(shape, residual_pad) the Pallas kernel actually sees: the
    padding (:func:`conv_pads`) is applied in HBM and the kernel called
    with pad=0.  This is the shape autotune cache keys are built over
    (used by ``benchmarks/hillclimb.py --write-cache``)."""
    n, h, w, cin = x_shape
    ph, pw = conv_pads(h, w, k, stride, padding)
    return (n, h + sum(ph), w + sum(pw), cin), 0


# ---------------------------------------------------------------------------
# Differentiable conv core (custom_vjp) — DESIGN.md §5
#
# Both cotangents are TrIM convolutions: the input gradient is a stride-1
# conv of the dilated/edge-padded cotangent with flipped/transposed
# weights (the forward kernel, dataflow axis and all), the weight
# gradient a dedicated spatially-contracting strip kernel.  The primal
# path (no differentiation) still runs the fully fused kernel; under
# jax.grad the fwd rule runs the epilogue unfused so the pre-activation
# is available as a residual.
# ---------------------------------------------------------------------------

class _ConvVjpConfig(typing.NamedTuple):
    """Static knobs of one differentiable conv call (hashable; a
    nondiff argument of the custom_vjp cores)."""

    stride: int
    pad: int
    groups: int
    activation: str | None
    tile_h: int | None
    tile_cout: int | None
    dataflow: str
    use_autotune_cache: bool
    packed_cout: int | None = None
    name: str | None = None          # the kernel's name (layer)


def _activation_bwd(activation: str | None, z: jax.Array | None,
                    gy: jax.Array) -> jax.Array:
    """Cotangent through the (jnp-level) epilogue activation."""
    if activation is None:
        return gy
    return jax.vjp(ACTIVATIONS[activation], z)[1](gy)[0]


def _backward_knobs(cfg: _ConvVjpConfig, x_shape, w_shape, dtype: str):
    """Tile/dataflow knobs for the two cotangent kernels: the autotune
    cache consulted under the backward problems' own keys (the
    input-grad conv under the plain ``conv2d`` key of its transformed
    shapes, the weight grad under ``conv2d_wgrad``), else defaults."""
    ig = dict(tile_h=None, tile_cout=None, dataflow="carry")
    wg = dict(tile_go=None, tile_cout=None)
    if cfg.use_autotune_cache:
        geo = input_grad_geometry(x_shape, w_shape, stride=cfg.stride,
                                  pad=cfg.pad, groups=cfg.groups)
        rec = autotune.knobs_for(geo["g_padded_shape"], geo["wt_shape"],
                                 stride=1, pad=0, groups=cfg.groups,
                                 dtype=dtype)
        if rec is not None:
            ig = dict(tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
                      dataflow=rec["dataflow"])
        wrec = autotune.weight_grad_knobs_for(
            x_shape, w_shape, stride=cfg.stride, pad=cfg.pad,
            groups=cfg.groups, dtype=dtype)
        if wrec is not None:
            wg = dict(tile_go=wrec["tile_go"],
                      tile_cout=wrec["tile_cout"])
    return ig, wg


def _conv_grads(cfg: _ConvVjpConfig, x, w, bias, z, gy):
    """Shared backward math: (dx, dw_logical, db_or_None, dz)."""
    dz = _activation_bwd(cfg.activation, z, gy)
    ig, wg = _backward_knobs(cfg, x.shape, w.shape, str(x.dtype))
    dx = trim_conv2d_input_grad(dz, w, x_shape=x.shape, stride=cfg.stride,
                                pad=cfg.pad, groups=cfg.groups, **ig)
    dw = trim_conv2d_weight_grad(x, dz, kernel_size=w.shape[:2],
                                 stride=cfg.stride, pad=cfg.pad,
                                 groups=cfg.groups, **wg)
    db = None if bias is None \
        else dz.sum((0, 1, 2)).astype(bias.dtype)
    return dx.astype(x.dtype), dw.astype(w.dtype), db, dz


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d_vjp_core(cfg: _ConvVjpConfig, x, w, bias):
    """Primal: the fully fused kernel (bias + activation in-epilogue)."""
    return trim_conv2d(x, w, bias, stride=cfg.stride, pad=cfg.pad,
                       tile_h=cfg.tile_h, tile_cout=cfg.tile_cout,
                       groups=cfg.groups, activation=cfg.activation,
                       dataflow=cfg.dataflow, name=cfg.name)


def _conv2d_vjp_fwd(cfg: _ConvVjpConfig, x, w, bias):
    z = trim_conv2d(x, w, bias, stride=cfg.stride, pad=cfg.pad,
                    tile_h=cfg.tile_h, tile_cout=cfg.tile_cout,
                    groups=cfg.groups, activation=None,
                    dataflow=cfg.dataflow, name=cfg.name)
    y = z if cfg.activation is None else ACTIVATIONS[cfg.activation](z)
    # z is only a residual when the activation needs it in the backward
    return y, (x, w, bias, z if cfg.activation is not None else None)


def _conv2d_vjp_bwd(cfg: _ConvVjpConfig, res, gy):
    x, w, bias, z = res
    dx, dw, db, _ = _conv_grads(cfg, x, w, bias, z, gy)
    return dx, dw, db


_conv2d_vjp_core.defvjp(_conv2d_vjp_fwd, _conv2d_vjp_bwd)


def _unpack_weights(wp: jax.Array, groups: int, cout: int) -> jax.Array:
    """Packed padded layout -> logical (K, K, Cin/g, Cout)."""
    kh, kw, cin_pg, gcpp = wp.shape
    cpp, cout_pg = gcpp // groups, cout // groups
    w = wp.reshape(kh, kw, cin_pg, groups, cpp)[..., :cout_pg]
    return w.reshape(kh, kw, cin_pg, cout)


def _pack_weight_grad(dw: jax.Array, groups: int, cpp: int) -> jax.Array:
    """Logical weight cotangent -> the packed padded layout (the
    cotangent of a PackedConv2dWeights.w leaf must match its shape)."""
    kh, kw, cin_pg, cout = dw.shape
    cout_pg = cout // groups
    dwp = dw.reshape(kh, kw, cin_pg, groups, cout_pg)
    dwp = jnp.pad(dwp, ((0, 0),) * 4 + ((0, cpp - cout_pg),))
    return dwp.reshape(kh, kw, cin_pg, groups * cpp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv2d_packed_vjp_core(cfg: _ConvVjpConfig, x, wp, bp):
    """Primal: the fused packed-weights kernel path."""
    return trim_conv2d(x, wp, bp, stride=cfg.stride, pad=cfg.pad,
                       tile_h=cfg.tile_h, tile_cout=cfg.tile_cout,
                       groups=cfg.groups, activation=cfg.activation,
                       dataflow=cfg.dataflow, packed_cout=cfg.packed_cout,
                       name=cfg.name)


def _conv2d_packed_vjp_fwd(cfg: _ConvVjpConfig, x, wp, bp):
    z = trim_conv2d(x, wp, bp, stride=cfg.stride, pad=cfg.pad,
                    tile_h=cfg.tile_h, tile_cout=cfg.tile_cout,
                    groups=cfg.groups, activation=None,
                    dataflow=cfg.dataflow, packed_cout=cfg.packed_cout,
                    name=cfg.name)
    y = z if cfg.activation is None else ACTIVATIONS[cfg.activation](z)
    return y, (x, wp, bp, z if cfg.activation is not None else None)


def _conv2d_packed_vjp_bwd(cfg: _ConvVjpConfig, res, gy):
    x, wp, bp, z = res
    w = _unpack_weights(wp, cfg.groups, cfg.packed_cout)
    dx, dw, _, dz = _conv_grads(cfg, x, w, None, z, gy)
    cpp = wp.shape[3] // cfg.groups
    dwp = _pack_weight_grad(dw, cfg.groups, cpp)
    dbp = None
    if bp is not None:
        db = dz.sum((0, 1, 2))                     # logical (Cout,)
        cout_pg = cfg.packed_cout // cfg.groups
        dbp = jnp.pad(db.reshape(cfg.groups, cout_pg),
                      ((0, 0), (0, cpp - cout_pg)))
        dbp = dbp.reshape(1, cfg.groups * cpp).astype(bp.dtype)
    return dx, dwp.astype(wp.dtype), dbp


_conv2d_packed_vjp_core.defvjp(_conv2d_packed_vjp_fwd,
                               _conv2d_packed_vjp_bwd)


class _DepthwiseConfig(typing.NamedTuple):
    """Static knobs of one depthwise VPU kernel call."""

    stride: int
    activation: str | None
    tile_h: int | None
    tile_c: int | None
    tile_n: int | None
    use_autotune_cache: bool
    name: str | None = None


def _depthwise_call(cfg: _DepthwiseConfig, x, w, bias, activation):
    return trim_depthwise_conv2d(x, w, bias, stride=cfg.stride,
                                 tile_h=cfg.tile_h, tile_c=cfg.tile_c,
                                 tile_n=cfg.tile_n, activation=activation,
                                 name=cfg.name)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _depthwise_vjp_core(cfg: _DepthwiseConfig, x, w, bias):
    """Primal: the depthwise VPU kernel, epilogue fused."""
    return _depthwise_call(cfg, x, w, bias, cfg.activation)


def _depthwise_vjp_fwd(cfg: _DepthwiseConfig, x, w, bias):
    z = _depthwise_call(cfg, x, w, bias, None)
    y = z if cfg.activation is None else ACTIVATIONS[cfg.activation](z)
    return y, (x, w, bias, z if cfg.activation is not None else None)


def _depthwise_vjp_bwd(cfg: _DepthwiseConfig, res, gy):
    # both cotangents run on the grouped TrIM backward kernels
    x, w, bias, z = res
    conv = _ConvVjpConfig(stride=cfg.stride, pad=0, groups=x.shape[3],
                          activation=cfg.activation, tile_h=None,
                          tile_cout=None, dataflow="carry",
                          use_autotune_cache=cfg.use_autotune_cache)
    dx, dw, db, _ = _conv_grads(conv, x, w, bias, z, gy)
    return dx, dw, db


_depthwise_vjp_core.defvjp(_depthwise_vjp_fwd, _depthwise_vjp_bwd)


def _depthwise_pallas(x: jax.Array, w: jax.Array, route: ConvRoute, *,
                      bias: jax.Array | None, activation: str | None,
                      use_autotune_cache: bool,
                      name: str | None) -> jax.Array:
    """The depthwise VPU kernel over the pre-padded ``x``, its knobs
    from the route's ``conv2d_dw`` record (defaults without one)."""
    knobs = dict(tile_h=None, tile_c=None, tile_n=None)
    if use_autotune_cache:
        rec = route.knobs(str(x.dtype))
        if rec is not None:
            knobs = {key: rec[key] for key in knobs}
    cfg = _DepthwiseConfig(stride=route.stride, activation=activation,
                           use_autotune_cache=use_autotune_cache,
                           name=name, **knobs)
    return _depthwise_vjp_core(cfg, x, w, bias)


def conv2d(x: jax.Array, w, *, stride: int = 1,
           padding: str | int = "same", impl: str = "pallas",
           feature_group_count: int = 1, bias: jax.Array | None = None,
           activation: str | None = None,
           tile_h: int | None = None, tile_cout: int | None = None,
           dataflow: str | None = None,
           use_autotune_cache: bool = True,
           mesh=None, rules: dict | None = None,
           layer: str | None = None) -> jax.Array:
    """(Grouped) 2D convolution with optional fused bias + activation.

    x: (N, H, W, Cin); w: (K, K, Cin/groups, Cout) or a
    :class:`PackedConv2dWeights`; bias: (Cout,) or None;
    ``padding``: ``"same"``, ``"valid"`` or a symmetric int pad
    (:func:`conv_pads`); ``feature_group_count=Cin`` gives depthwise
    convolution.  The Pallas path fuses the epilogue into the kernel's
    accumulator store.

    Tile/dataflow knobs left as ``None`` are filled from the autotune
    cache (``core/autotune.py``) when a record exists for this problem
    (disable with ``use_autotune_cache=False`` or
    ``REPRO_CONV_AUTOTUNE=0``), falling back to the plan defaults.  The
    K > MAX_NATIVE_K kernel-tiled path honors explicit knobs on every
    sub-kernel but never consults the cache (records describe the full-K
    problem, not the sub-kernel geometry).

    ``mesh`` (with optional conv ``rules``, default
    ``distributed.sharding.CONV_RULES``) selects the sharded execution
    path (DESIGN.md §6): batch shards over the rules' ``"batch"`` axis,
    output H-strips over ``"strips"``, with a ``ppermute`` neighbor halo
    exchange of the K-1 boundary rows before the per-shard kernel.  The
    sharded path consults the autotune cache under device-count
    namespaced keys (``conv2d_shard:<ndev>:``) so single- and
    multi-device tunings never alias.

    Execution is *guarded* (DESIGN.md §9): the tier chain
    ``sharded -> pallas -> ref`` fails soft — a lowering/compile/runtime
    error in a fast tier demotes the call to the next tier, records a
    structured event (``core.guard.events()``), and memoizes the broken
    ``(problem, tier)`` pair so it is never re-attempted.  The final
    ``ref`` tier runs unguarded, so a genuinely invalid problem still
    raises.  ``REPRO_CONV_GUARD=1`` additionally finite-checks tier
    outputs (eager only) and demotes on NaN/Inf; ``layer`` names the
    producing layer in those events, and names the layer's Pallas
    kernel (a profiler trace shows it as ``%<layer>.<n>``; autotune and
    guard keys never see it).

    Runnable quickstart snippets for every path (dataflows, packing,
    autotune, ``mesh=``, guard) live in README.md and are executed by CI
    (``tools/doclint.py``); whole-topology execution is
    ``models/layers.py cnn_apply_from_layers`` (DESIGN.md §7).
    """
    # invalid *arguments* are rejected here, before the guarded chain:
    # they are caller errors, not tier faults, and must raise the same
    # actionable ValueError from every tier (the ref oracle would
    # otherwise surface them as KeyErrors after a pointless demotion)
    if activation is not None and activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"choose from {sorted(ACTIVATIONS, key=str)}")
    if dataflow is not None and dataflow not in autotune.DATAFLOWS:
        raise ValueError(f"unknown dataflow {dataflow!r}; "
                         f"choose from {autotune.DATAFLOWS}")
    if isinstance(w, PackedConv2dWeights):
        if mesh is not None:
            raise ValueError(
                "sharded conv2d takes raw (K, K, Cin/g, Cout) weights; "
                "packed weights freeze a single-device layout")
        return _conv2d_packed(x, w, stride=stride, padding=padding,
                              impl=impl, bias=bias, activation=activation,
                              tile_h=tile_h, dataflow=dataflow,
                              use_autotune_cache=use_autotune_cache,
                              layer=layer)
    cin, (cin_pg, cout) = x.shape[3], w.shape[2:]
    if cin_pg * feature_group_count != cin:
        raise ValueError(
            f"weights expect cin/groups={cin_pg} with "
            f"groups={feature_group_count}, input has cin={cin}")
    if cout % feature_group_count:
        raise ValueError(f"groups={feature_group_count} must divide "
                         f"cout={cout}")
    if impl == "ref":
        # the oracle computes the same global math regardless of mesh
        return ref.conv2d(x, w, stride=stride, padding=padding,
                          feature_group_count=feature_group_count,
                          bias=bias, activation=activation)

    def _pallas_tier():
        return _conv2d_pallas(x, w, stride=stride, padding=padding,
                              feature_group_count=feature_group_count,
                              bias=bias, activation=activation,
                              tile_h=tile_h, tile_cout=tile_cout,
                              dataflow=dataflow,
                              use_autotune_cache=use_autotune_cache,
                              name=layer)

    def _ref_tier():
        return ref.conv2d(x, w, stride=stride, padding=padding,
                          feature_group_count=feature_group_count,
                          bias=bias, activation=activation)

    tiers = [("pallas", _pallas_tier), ("ref", _ref_tier)]
    if mesh is not None:
        def _sharded_tier():
            return _conv2d_sharded(x, w, stride=stride, padding=padding,
                                   feature_group_count=feature_group_count,
                                   bias=bias, activation=activation,
                                   tile_h=tile_h, tile_cout=tile_cout,
                                   dataflow=dataflow,
                                   use_autotune_cache=use_autotune_cache,
                                   mesh=mesh, rules=rules, name=layer)
        tiers.insert(0, ("sharded", _sharded_tier))
    key = guard.problem_key("conv2d", x.shape, w.shape, stride=stride,
                            padding=padding, groups=feature_group_count,
                            dtype=str(x.dtype))
    return guard.run_chain(key, tiers, layer=layer)


def _conv2d_pallas(x: jax.Array, w: jax.Array, *, stride: int,
                   padding: str | int, feature_group_count: int,
                   bias: jax.Array | None, activation: str | None,
                   tile_h: int | None, tile_cout: int | None,
                   dataflow: str | None,
                   use_autotune_cache: bool,
                   name: str | None = None) -> jax.Array:
    """The single-device Pallas tier: the pre-pad (:func:`conv_pads`),
    then the kernel :func:`conv_route` picks — the depthwise VPU kernel
    (the MXU kernel's tile and dataflow knobs do not apply to it), the
    MXU kernel over the folded or the plain problem (autotune-cache knob
    fill, differentiable kernel core), or the K > MAX_NATIVE_K adder-tree
    decomposition (every sub-kernel named ``name``)."""
    k = w.shape[0]
    x = _prepad(x, k, stride, padding)
    route = conv_route(x.shape, w.shape, stride=stride,
                       groups=feature_group_count, dtype=x.dtype)
    if route.kind == "depthwise":
        return _depthwise_pallas(x, w, route, bias=bias,
                                 activation=activation,
                                 use_autotune_cache=use_autotune_cache,
                                 name=name)
    if route.kind != "tiled":
        if use_autotune_cache and (tile_h is None or tile_cout is None
                                   or dataflow is None):
            rec = route.knobs(str(x.dtype))
            if rec is not None:
                tile_h = tile_h if tile_h is not None else rec["tile_h"]
                tile_cout = tile_cout if tile_cout is not None \
                    else rec["tile_cout"]
                dataflow = dataflow if dataflow is not None \
                    else rec["dataflow"]
        if route.kind == "folded":
            # outside the custom_vjp core: autodiff runs through the
            # fold's views, then the 1x1 kernel's own backward kernels.
            # The fold also writes the kernel's bottom rows up to whole
            # strips, so no second pad is left.
            plan = ConvPlan.build(route.x_shape, route.w_shape,
                                  dtype_bytes=x.dtype, tile_h=tile_h,
                                  tile_cout=tile_cout,
                                  dataflow=dataflow or "carry")
            x = _fold_taps(x, k, k, stride, plan.rows_padded)
            w = w.reshape(route.w_shape)
            tile_h, tile_cout = plan.tile_h, plan.tile_cout
        cfg = _ConvVjpConfig(stride=route.stride, pad=0,
                             groups=route.groups,
                             activation=activation, tile_h=tile_h,
                             tile_cout=tile_cout,
                             dataflow=dataflow or "carry",
                             use_autotune_cache=use_autotune_cache,
                             name=name)
        out = _conv2d_vjp_core(cfg, x, w, bias)
        if route.kind == "mxu":
            return out
        return out[:, :route.x_shape[1]]
    # Kernel tiling (paper §III): split K x K into sub-kernels, accumulate.
    # The epilogue is applied once, after the adder tree.  Explicit tile
    # knobs apply to every sub-kernel; the autotune cache is NOT consulted
    # here (its records describe the full-K problem, not the sub-kernel
    # geometry).
    h_out = (x.shape[1] - k) // stride + 1
    w_out = (x.shape[2] - k) // stride + 1
    out = None
    cfg = _ConvVjpConfig(stride=stride, pad=0,
                         groups=feature_group_count, activation=None,
                         tile_h=tile_h, tile_cout=tile_cout,
                         dataflow=dataflow or "carry",
                         use_autotune_cache=use_autotune_cache, name=name)
    for r0, c0, kh, kw in subkernel_decomposition(k, native_k=3):
        zs = x[:, r0:r0 + (h_out - 1) * stride + kh,
               c0:c0 + (w_out - 1) * stride + kw, :]
        # each sub-kernel is a differentiable core call, so the whole
        # adder-tree path (slices + sum) autodiffs through the same
        # backward kernels
        part = _conv2d_vjp_core(cfg, zs, w[r0:r0 + kh, c0:c0 + kw], None)
        out = part if out is None else out + part   # adder tree
    return ref.epilogue(out, bias, activation)


def _conv2d_sharded(x: jax.Array, w: jax.Array, *, stride: int,
                    padding: str | int, feature_group_count: int,
                    bias: jax.Array | None, activation: str | None,
                    tile_h: int | None, tile_cout: int | None,
                    dataflow: str | None, use_autotune_cache: bool,
                    mesh, rules: dict | None,
                    name: str | None = None) -> jax.Array:
    """The shard_map path (DESIGN.md §6): resolve the shard grid from
    the mesh + conv rules, plan with :class:`ShardedConvPlan`, and run
    the halo-exchange schedule with the *differentiable* conv core as
    the per-shard kernel — gradients transpose the halo shuffle and
    psum the replicated weight/bias cotangents automatically."""
    k = w.shape[0]
    if k > MAX_NATIVE_K:
        raise ValueError(
            f"sharded conv2d supports K <= {MAX_NATIVE_K}; decompose "
            "large kernels before sharding (ops.conv2d adder-tree path)")
    x = _prepad(x, k, stride, padding)
    ba, bs, sa, ss = resolve_conv_mesh(mesh, rules)
    if use_autotune_cache and (tile_h is None or tile_cout is None
                               or dataflow is None):
        rec = autotune.sharded_knobs_for(
            x.shape, w.shape, batch_shards=bs, spatial_shards=ss,
            stride=stride, pad=0, groups=feature_group_count,
            dtype=str(x.dtype))
        if rec is not None:
            tile_h = tile_h if tile_h is not None else rec["tile_h"]
            tile_cout = tile_cout if tile_cout is not None \
                else rec["tile_cout"]
            dataflow = dataflow if dataflow is not None \
                else rec["dataflow"]
    plan = ShardedConvPlan.build(
        x.shape, w.shape, stride=stride, pad=0,
        groups=feature_group_count, dtype_bytes=x.dtype.itemsize,
        tile_h=tile_h, tile_cout=tile_cout, dataflow=dataflow or "carry",
        batch_shards=bs, spatial_shards=ss, batch_axis=ba,
        spatial_axis=sa)
    cfg = _ConvVjpConfig(stride=stride, pad=0,
                         groups=feature_group_count,
                         activation=activation, tile_h=tile_h,
                         tile_cout=tile_cout,
                         dataflow=dataflow or "carry",
                         use_autotune_cache=use_autotune_cache, name=name)
    return sharded_conv2d(x, w, bias, plan=plan, mesh=mesh,
                          local_conv=functools.partial(_conv2d_vjp_core,
                                                       cfg))


def _conv2d_packed(x: jax.Array, pk: PackedConv2dWeights, *,
                   stride: int, padding: str | int, impl: str,
                   bias: jax.Array | None, activation: str | None,
                   tile_h: int | None, dataflow: str | None,
                   use_autotune_cache: bool,
                   layer: str | None = None) -> jax.Array:
    """The pre-packed fast path: no per-call weight pad/reshape.

    Guarded like :func:`conv2d`: the ``ref`` fallback unpacks the padded
    layout back to logical ``(K, K, Cin/g, Cout)`` weights + ``(Cout,)``
    bias, so demotion preserves the packed API."""
    if bias is not None:
        raise ValueError("bias is packed inside PackedConv2dWeights; "
                         "pass it to pack_conv2d_weights instead")
    if impl != "pallas":
        raise ValueError(f"packed weights require impl='pallas', "
                         f"got {impl!r}")
    if pk.scale is not None:
        # quantized packed weights (quantize_conv2d_weights /
        # calibrate_conv2d): the int8 tier chain
        return _conv2d_q8(x, pk, stride=stride, padding=padding,
                          activation=activation, tile_h=tile_h,
                          dataflow=dataflow,
                          use_autotune_cache=use_autotune_cache,
                          layer=layer)
    k = pk.w.shape[0]

    def _pallas_tier():
        return _conv2d_packed_pallas(
            x, pk, stride=stride, padding=padding, activation=activation,
            tile_h=tile_h, dataflow=dataflow,
            use_autotune_cache=use_autotune_cache, name=layer)

    def _ref_tier():
        w_logical = _unpack_weights(pk.w, pk.groups, pk.cout)
        b_logical = None
        if pk.bias is not None:
            cpp = pk.w.shape[3] // pk.groups
            cout_pg = pk.cout // pk.groups
            b_logical = pk.bias.reshape(pk.groups, cpp)[:, :cout_pg] \
                .reshape(pk.cout)
        return ref.conv2d(x, w_logical, stride=stride, padding=padding,
                          feature_group_count=pk.groups, bias=b_logical,
                          activation=activation)

    key = guard.problem_key("conv2d_packed", x.shape,
                            (k, pk.w.shape[1], pk.w.shape[2], pk.cout),
                            stride=stride, padding=padding,
                            groups=pk.groups, dtype=str(x.dtype))
    return guard.run_chain(key, [("pallas", _pallas_tier),
                                 ("ref", _ref_tier)], layer=layer)


def _conv2d_packed_pallas(x: jax.Array, pk: PackedConv2dWeights, *,
                          stride: int, padding: str | int,
                          activation: str | None, tile_h: int | None,
                          dataflow: str | None,
                          use_autotune_cache: bool,
                          name: str | None = None) -> jax.Array:
    k = pk.w.shape[0]
    x = _prepad(x, k, stride, padding)
    tile_h = tile_h if tile_h is not None else pk.tile_h
    dataflow = dataflow if dataflow is not None else pk.dataflow
    if use_autotune_cache and (tile_h is None or dataflow is None):
        # the packed layout freezes tile_cout; tile_h/dataflow may still
        # come from the cache (logical weight shape keys the record)
        w_shape = (k, pk.w.shape[1], pk.w.shape[2], pk.cout)
        rec = autotune.knobs_for(x.shape, w_shape, stride=stride, pad=0,
                                 groups=pk.groups, dtype=str(x.dtype))
        if rec is not None and rec["tile_cout"] == pk.tile_cout:
            tile_h = tile_h if tile_h is not None else rec["tile_h"]
            dataflow = dataflow if dataflow is not None \
                else rec["dataflow"]
    cfg = _ConvVjpConfig(stride=stride, pad=0, groups=pk.groups,
                         activation=activation, tile_h=tile_h,
                         tile_cout=pk.tile_cout,
                         dataflow=dataflow or "carry",
                         use_autotune_cache=use_autotune_cache,
                         packed_cout=pk.cout, name=name)
    return _conv2d_packed_vjp_core(cfg, x, pk.w, pk.bias)


def _unpack_cout_row(row: jax.Array, groups: int, cout: int) -> jax.Array:
    """Packed padded ``(1, G*CoutP)`` row -> logical ``(Cout,)``."""
    cpp, cout_pg = row.shape[1] // groups, cout // groups
    return row.reshape(groups, cpp)[:, :cout_pg].reshape(cout)


def _q8_forward(x_q: jax.Array, pk: PackedConv2dWeights, *, stride: int,
                activation: str | None, tile_h: int | None,
                dataflow: str | None,
                use_autotune_cache: bool,
                name: str | None = None) -> jax.Array:
    """The int8 Pallas tier: exact int32 MXU accumulation with the fused
    dequant epilogue.  ``x_q`` is already quantized and 'same'-pre-padded
    with the activation zero point.  Module-level so the fault harness
    (``testing/faults.py``) can patch it as the ``"q8"`` tier target.
    """
    s_row, b_q = ref.dequant_params(pk.w, pk.scale, pk.input_scale,
                                    pk.zero_point, pk.bias)
    tile_h = tile_h if tile_h is not None else pk.tile_h
    dataflow = dataflow if dataflow is not None else pk.dataflow
    if use_autotune_cache and (tile_h is None or dataflow is None):
        # int8 tunings live in their own conv2d_q8: namespace — an f32
        # record for the same geometry must never leak knobs in here
        w_shape = (pk.w.shape[0], pk.w.shape[1], pk.w.shape[2], pk.cout)
        rec = autotune.knobs_for(x_q.shape, w_shape, stride=stride, pad=0,
                                 groups=pk.groups, dtype="int8",
                                 op="conv2d_q8")
        if rec is not None and rec["tile_cout"] == pk.tile_cout:
            tile_h = tile_h if tile_h is not None else rec["tile_h"]
            dataflow = dataflow if dataflow is not None \
                else rec["dataflow"]
    return trim_conv2d(x_q, pk.w, b_q.reshape(1, -1),
                       s_row.reshape(1, -1), stride=stride, pad=0,
                       tile_h=tile_h, tile_cout=pk.tile_cout,
                       groups=pk.groups, activation=activation,
                       dataflow=dataflow or "carry", packed_cout=pk.cout,
                       name=name)


def _conv2d_q8(x: jax.Array, pk: PackedConv2dWeights, *, stride: int,
               padding: str | int, activation: str | None,
               tile_h: int | None, dataflow: str | None,
               use_autotune_cache: bool,
               layer: str | None = None) -> jax.Array:
    """The quantized tier chain (DESIGN.md §11): ``q8 -> pallas -> ref``.

    ``q8`` runs the int8 kernel; a fault demotes to ``pallas``, the f32
    kernel over the *dequantized* weights (same quantization error, fast
    path); ``ref`` is the ``conv2d_quantized`` oracle.  x may be f32
    (quantized here against the packed calibration) or already int8.
    """
    k = pk.w.shape[0]
    if jnp.issubdtype(x.dtype, jnp.integer):
        x_q = x
    else:
        x_q = ref.quantize_int8(x, pk.input_scale, pk.zero_point)
    ph, pw = conv_pads(x.shape[1], x.shape[2], k, stride, padding)
    zp = pk.zero_point.astype(x_q.dtype)
    x_pad = jax.lax.pad(x_q, zp, ((0, 0, 0), (*ph, 0), (*pw, 0),
                                  (0, 0, 0)))

    def _q8_tier():
        return _q8_forward(x_pad, pk, stride=stride, activation=activation,
                           tile_h=tile_h, dataflow=dataflow,
                           use_autotune_cache=use_autotune_cache,
                           name=layer)

    w_q = _unpack_weights(pk.w, pk.groups, pk.cout)
    w_scale = _unpack_cout_row(pk.scale, pk.groups, pk.cout)
    b_logical = None if pk.bias is None \
        else _unpack_cout_row(pk.bias, pk.groups, pk.cout)

    def _pallas_tier():
        # f32 kernel over the dequantized weights and quantized-dequantized
        # input: same quantization error as the int8 tier, fast fallback
        x_dq = (x_q.astype(jnp.float32)
                - pk.zero_point.astype(jnp.float32)) * pk.input_scale
        w_dq = w_q.astype(jnp.float32) * w_scale
        return _conv2d_pallas(x_dq, w_dq, stride=stride, padding=padding,
                              feature_group_count=pk.groups,
                              bias=b_logical, activation=activation,
                              tile_h=tile_h, tile_cout=pk.tile_cout,
                              dataflow=dataflow,
                              use_autotune_cache=use_autotune_cache,
                              name=layer)

    def _ref_tier():
        return ref.conv2d_quantized(
            x_q, w_q, x_scale=pk.input_scale, x_zero_point=pk.zero_point,
            w_scale=w_scale, bias=b_logical, stride=stride,
            padding=padding, feature_group_count=pk.groups,
            activation=activation)

    key = guard.problem_key("conv2d_q8", x.shape,
                            (k, pk.w.shape[1], pk.w.shape[2], pk.cout),
                            stride=stride, padding=padding,
                            groups=pk.groups, dtype=str(x.dtype))
    return guard.run_chain(key, [("q8", _q8_tier),
                                 ("pallas", _pallas_tier),
                                 ("ref", _ref_tier)], layer=layer)


def depthwise_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
                     padding: str | int = "same", impl: str = "pallas",
                     bias: jax.Array | None = None,
                     activation: str | None = None,
                     mesh=None, rules: dict | None = None,
                     layer: str | None = None) -> jax.Array:
    """Depthwise 2D convolution (the MobileNet scenario of the paper's
    OPs/Access comparison).

    Sugar for :func:`conv2d` with ``feature_group_count == Cin``: each
    input channel is convolved with its own ``(K, K)`` filter(s).
    x: (N, H, W, Cin); w: (K, K, 1, Cin * multiplier).  Everything else
    — fused bias/activation epilogue, autotune-cache consultation, the
    ``custom_vjp`` backward kernels, the ``mesh=`` sharded path — is
    inherited from :func:`conv2d`.  On one device a multiplier-1
    depthwise conv runs the VPU kernel (:func:`conv_route`); a
    higher multiplier rides the MXU kernel's group grid axis.
    """
    return conv2d(x, w, stride=stride, padding=padding, impl=impl,
                  feature_group_count=x.shape[-1], bias=bias,
                  activation=activation, mesh=mesh, rules=rules,
                  layer=layer)


def depthwise_conv1d(x: jax.Array, w: jax.Array, *,
                     impl: str = "pallas") -> jax.Array:
    """Causal depthwise conv1d.  x: (B, L, D); w: (K, D)."""
    if impl == "ref" or w.shape[0] < 2:
        return ref.depthwise_conv1d(x, w)
    return trim_conv1d(x, w)


depthwise_conv1d_step = ref.depthwise_conv1d_step


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def _chunk_update(q, kc, vc, carry, *, k_start, lk, sm_scale, causal,
                  soft_cap, window, lq_off):
    """Online-softmax update for one KV chunk.

    q: (B, Hkv, G, Lq, D); kc/vc: (B, C, Hkv, D);
    carry = (m, l, acc) with m/l: (B, Hkv, G, Lq, 1), acc like q.
    """
    m_prev, l_prev, acc = carry
    s = jnp.einsum("bhgqd,bchd->bhgqc", q.astype(jnp.float32),
                   kc.astype(jnp.float32)) * sm_scale
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    lq, c = q.shape[3], kc.shape[1]
    q_pos = jnp.arange(lq) + lq_off
    k_pos = jnp.arange(c) + k_start
    mask = jnp.broadcast_to((k_pos < lk)[None, :], (lq, c))
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask, s, -1e30)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.einsum("bhgqc,bchd->bhgqd", p,
                                       vc.astype(jnp.float32))
    return m_new, l_new, acc_new


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, soft_cap: float | None = None,
                      window: int | None = None, chunk: int = 1024,
                      unroll: bool = False) -> jax.Array:
    """FlashAttention schedule in pure jnp (KV streamed chunk by chunk).

    q: (B, Lq, Hq, D); k/v: (B, Lk, Hkv, D).
    """
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    group = hq // hkv
    chunk = min(chunk, lk)
    nk = math.ceil(lk / chunk)
    lkp = nk * chunk
    sm_scale = 1.0 / math.sqrt(d)
    lq_off = lk - lq   # queries right-aligned (decode/prefill continuation)

    qg = q.transpose(0, 2, 1, 3).reshape(b, hkv, group, lq, d)
    kp = jnp.pad(k, ((0, 0), (0, lkp - lk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, lkp - lk), (0, 0), (0, 0)))

    m0 = jnp.full((b, hkv, group, lq, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, lq, 1), jnp.float32)
    a0 = jnp.zeros((b, hkv, group, lq, d), jnp.float32)

    # One chunk is checkpointed: the backward recomputes that chunk's
    # logits instead of saving them — the FlashAttention-bwd structure.
    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def _update(carry, kc, vc, ic):
        return _chunk_update(qg, kc, vc, carry, k_start=ic * chunk, lk=lk,
                             sm_scale=sm_scale, causal=causal,
                             soft_cap=soft_cap, window=window,
                             lq_off=lq_off)

    def step(carry, ic):
        kc = jax.lax.dynamic_slice_in_dim(kp, ic * chunk, chunk, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(vp, ic * chunk, chunk, axis=1)
        return _update(carry, kc, vc, ic), None

    if unroll:
        carry = (m0, l0, a0)
        for ic in range(nk):
            # skip chunks that are fully masked (causal / local window)
            if causal and ic * chunk > lq_off + lq - 1:
                continue
            if window is not None and (ic + 1) * chunk - 1 < lq_off - window + 1:
                continue
            carry, _ = step(carry, ic)
        m, l, acc = carry
    else:
        (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), jnp.arange(nk))

    out = (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)
    return out.reshape(b, hq, lq, d).transpose(0, 2, 1, 3)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, soft_cap: float | None = None,
              window: int | None = None, impl: str = "chunked",
              chunk: int = 1024) -> jax.Array:
    """Multi-head GQA attention.  q: (B, Lq, Hq, D); k/v: (B, Lk, Hkv, D)."""
    if impl == "ref":
        return ref.attention(q, k, v, causal=causal,
                             logits_soft_cap=soft_cap, window=window)
    if impl == "pallas":
        return flash_attention(q, k, v, causal=causal, soft_cap=soft_cap,
                               window=window)
    if impl == "chunked":
        return chunked_attention(q, k, v, causal=causal, soft_cap=soft_cap,
                                 window=window, chunk=chunk)
    if impl == "chunked_unroll":
        return chunked_attention(q, k, v, causal=causal, soft_cap=soft_cap,
                                 window=window, chunk=chunk, unroll=True)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     cache_len, *, soft_cap: float | None = None,
                     window: int | None = None) -> jax.Array:
    """One-token attention over a KV cache.

    q: (B, 1, Hq, D); caches: (B, Lmax, Hkv, D); cache_len: () or (B,) —
    number of valid cache entries (including the current token).
    """
    b, _, hq, d = q.shape
    _, lmax, hkv, _ = k_cache.shape
    group = hq // hkv
    qg = q.reshape(b, hkv, group, d)
    s = jnp.einsum("bhgd,bkhd->bhgk", qg.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / math.sqrt(d)
    if soft_cap is not None:
        s = soft_cap * jnp.tanh(s / soft_cap)
    k_pos = jnp.arange(lmax)
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))
    if window is not None:
        valid &= k_pos[None, :] >= jnp.reshape(cache_len, (-1, 1)) - window
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, hq, d).astype(q.dtype)
