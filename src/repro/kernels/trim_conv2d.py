"""3D-TrIM convolution as a TPU Pallas kernel.

TPU-native re-expression of the paper's dataflow (DESIGN.md §2, §4):

* **Input-stationary strips.**  The padded ifmap is tiled into
  non-overlapping strips of ``TH`` rows.  A strip is fetched from HBM
  exactly once and stays resident in VMEM while every C_out tile consumes
  it — the grid order is ``(N, group, strip, cout)`` with the input
  BlockSpec index map *ignoring the cout axis*, which is the BlockSpec
  image of the paper's P_O slices sharing one Input Recycling Buffer.

* **Two dataflows for the strip boundary** (``dataflow=`` knob, DESIGN.md
  §4).  ``"carry"`` is the paper's shadow registers: the ``K-1`` boundary
  rows a strip needs from its predecessor ride across *sequential* grid
  steps in a VMEM scratch (``carry_ref``) — zero halo traffic, serialized
  strips.  ``"halo"`` is the TrIM baseline re-expressed at strip level:
  every strip over-fetches its ``K-1`` predecessor rows through an
  overlapping (element-indexed) BlockSpec — it pays the halo bytes the shadow
  registers eliminate, but has no cross-step state, so batch / group /
  strip / cout grid axes can execute in any order (parallelizable).  The
  autotuner (``core/autotune.py``) picks per layer.

* **Weight-stationary MXU taps.**  The K x K spatial taps are unrolled into
  K^2 dense matmuls ``(TH_out * W_out, Cin) x (Cin, TCout)`` against the
  stationary weight tile — the triangular PE movement re-shaped for a
  128 x 128 systolic MXU instead of a 3 x 3 scalar PE slice.

* **Adder tree + fused epilogue.**  Tap/channel partial sums accumulate in
  an fp32 register accumulator (the in-kernel analogue of the P_O adder
  trees); an optional bias + activation epilogue is applied to the
  accumulator before the single store to HBM, so inference layers pay no
  extra output round-trip.

* **Grouped / depthwise.**  ``groups > 1`` adds a group axis to the grid;
  each group sweeps its own channel slice with its own carry, covering the
  MobileNet-style depthwise workloads of the paper's OPs/Access study.

* **Pre-packed weights.**  ``packed_cout`` signals that ``w`` (and
  ``bias``) already sit in the plan's padded layouts
  (``ops.pack_conv2d_weights``), so the per-call pad/reshape in the hot
  path is skipped — the load-time packing of ``models/layers.py``.

* **Backward kernels** (DESIGN.md §5).  Both conv cotangents are TrIM
  convolutions: ``trim_conv2d_input_grad`` re-expresses dx as a
  stride-1 forward problem (dilated/edge-padded cotangent x
  flipped/transposed weights) through this very kernel — dataflow axis
  included — and ``trim_conv2d_weight_grad`` is a dedicated kernel that
  contracts the spatial axes: cotangent strips stay resident with their
  overlapping ifmap window while the K x K taps accumulate into a
  weight-shaped fp32 output block revisited across the (batch, strip)
  sweep.  ``ops.conv2d`` wires them into a ``jax.custom_vjp``.

All geometry (strips, carry, halo windows, grid, padded layouts) comes
from ``core.conv_plan.ConvPlan`` — the same object that produces the
analytical HBM traffic numbers, so the kernel and the model cannot
disagree.  Supports arbitrary K and stride (kernel tiling for huge K is
provided by ``ops.conv2d``); validated in interpret mode against
``ref.conv2d``.  ``interpret=None`` auto-detects the backend: the same
call site lowers natively on a real TPU and interprets elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.conv_plan import (KERNEL_VMEM_LIMIT, ConvPlan,
                                   input_grad_geometry)
from repro.kernels.runtime import resolve_interpret

F32_DOT_PRECISION = jax.lax.Precision.HIGHEST

ACTIVATIONS = {
    None: lambda a: a,
    "relu": lambda a: jnp.maximum(a, 0.0),
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


def _element_window(block, index_map, groups: int):
    """BlockSpec of an overlapping ``(1, rows, W, C/groups)`` input window.

    The row offset is an element offset (successive windows overlap), so
    every dim is element-indexed: Mosaic takes all-``Element`` or
    no-``Element`` blocks, with the batch dim squeezed.  ``index_map``
    returns ``(n, row, group)``; the channel offset is a literal 0
    without groups so the compiler can prove it lane-aligned."""
    _, rows, w, c = block

    def element_index(*grid):
        n, row, gr = index_map(*grid)
        return (n, row, 0, gr * c if groups > 1 else 0)

    return pl.BlockSpec((None, pl.Element(rows), pl.Element(w),
                         pl.Element(c)), element_index)


def _tap_matmuls(window, w_ref, *, kh: int, kw: int, stride: int,
                 th_out: int, w_out: int, n_out: int):
    """The K x K taps: triangular movement as K^2 shifted views of the
    resident window, each a dense MXU matmul.  ``window`` holds the strip
    plus its K-1 predecessor rows (from the carry scratch or the halo
    over-fetch — identical contents either way)."""
    s = stride
    r = (kh - 1) % s  # static in-window row offset (ConvPlan.row_offset)
    cin = window.shape[-1]
    # int8 inputs accumulate exactly in int32 on the MXU; floats in fp32.
    # f32 operands ask for full-precision products: Mosaic's default
    # rounds them to bf16 (2.4e-3 relative error on a v5e)
    integer = jnp.issubdtype(window.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    precision = F32_DOT_PRECISION if window.dtype == jnp.float32 else None
    acc = jnp.zeros((th_out * w_out, n_out), acc_dtype)
    for ki in range(kh):
        for kj in range(kw):
            rows = window[ki + r: ki + r + (th_out - 1) * s + 1: s,
                          kj: kj + (w_out - 1) * s + 1: s, :]
            acc += jnp.dot(rows.reshape(th_out * w_out, cin),
                           w_ref[ki, kj], precision=precision,
                           preferred_element_type=acc_dtype)
    return acc


def _strided_tap_matmuls(load, w_ref, *, kh: int, kw: int, stride: int,
                         th_out: int, w_out: int, n_out: int, chunks: int):
    """The K x K taps at ``stride > 1``, read by strided ref loads.

    The chip's compiler slices no loaded value with a stride on the
    sublane axis ("Only 2D gather is supported"), but loads a ref with a
    stride on any axis whose base buffer is at most one lane tile wide.
    So the window comes as ``chunks`` refs of at most 128 channels each
    and ``load(c, rows, cols)`` reads chunk ``c`` of tap ``(ki, kj)``'s
    rows ``ki + r + s*i`` and columns ``kj + s*j``; the chunks are joined
    along the lanes into one ``(TH_out * W_out, Cin)`` MXU operand."""
    s = stride
    r = (kh - 1) % s
    integer = jnp.issubdtype(w_ref.dtype, jnp.integer)
    acc_dtype = jnp.int32 if integer else jnp.float32
    precision = F32_DOT_PRECISION if w_ref.dtype == jnp.float32 else None
    acc = jnp.zeros((th_out * w_out, n_out), acc_dtype)
    for ki in range(kh):
        for kj in range(kw):
            rows = pl.ds(ki + r, th_out, stride=s)
            cols = pl.ds(kj, w_out, stride=s)
            parts = [load(c, rows, cols) for c in range(chunks)]
            v = parts[0] if chunks == 1 else jnp.concatenate(parts, axis=-1)
            acc += jnp.dot(v.reshape(th_out * w_out, -1), w_ref[ki, kj],
                           precision=precision,
                           preferred_element_type=acc_dtype)
    return acc


def _epilogue_store(acc, s_ref, b_ref, o_ref, *, th_out: int, w_out: int,
                    activation: str | None):
    """Fused epilogue: (dequant) + bias + activation on the accumulator,
    then the single store to the output block.

    ``s_ref`` (int8 route) holds the per-out-channel dequant scale row
    and ``b_ref`` the *requantized int32 bias* — the int32 accumulator
    becomes f32 via exactly ``(acc + bias_q) * scale``: an exact integer
    add followed by one correctly-rounded multiply, the same operations
    as ``ref.dequant_params`` / ``ref.conv2d_quantized`` with no mul+add
    pair a backend could contract into an FMA, which is what makes the
    quantized kernel bit-exact against the oracle."""
    if s_ref is not None:
        if b_ref is not None:
            acc = acc + b_ref[0]       # int32 + int32: exact
        acc = acc.astype(jnp.float32) * s_ref[0].astype(jnp.float32)
    elif b_ref is not None:
        acc = acc + b_ref[0].astype(jnp.float32)
    acc = ACTIVATIONS[activation](acc)
    o_ref[0] = acc.reshape(th_out, w_out, -1).astype(o_ref.dtype)


def _carry_kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int,
                  th_out: int, w_out: int, n_cout_tiles: int,
                  activation: str | None, has_bias: bool,
                  has_scale: bool = False):
    """One grid step: strip ``g`` of (image ``n``, group) x cout tile,
    with the K-1 boundary rows carried across sequential strips."""
    s_ref = rest[0] if has_scale else None
    b_ref = rest[has_scale] if has_bias else None
    o_ref, carry_ref = rest[has_scale + has_bias:]
    g = pl.program_id(2)
    co = pl.program_id(3)

    if kh > 1:
        @pl.when(jnp.logical_and(g == 0, co == 0))
        def _reset_carry():
            # First strip of a (batch, group) sweep: no predecessor, the
            # carry region is zero padding.
            carry_ref[...] = jnp.zeros_like(carry_ref)

        window = jnp.concatenate([carry_ref[...], x_ref[0]], axis=0)
    else:
        window = x_ref[0]

    acc = _tap_matmuls(window, w_ref, kh=kh, kw=kw, stride=stride,
                       th_out=th_out, w_out=w_out, n_out=o_ref.shape[-1])
    _epilogue_store(acc, s_ref, b_ref, o_ref, th_out=th_out, w_out=w_out,
                    activation=activation)

    if kh > 1:
        @pl.when(co == n_cout_tiles - 1)
        def _update_carry():
            # Shadow registers: keep the last K-1 rows for the next strip.
            carry_ref[...] = window[-(kh - 1):]


def _halo_kernel(x_ref, w_ref, *rest, kh: int, kw: int, stride: int,
                 th_out: int, w_out: int, activation: str | None,
                 has_bias: bool, has_scale: bool = False):
    """One grid step of the halo dataflow: the overlapping input window
    already contains the K-1 predecessor rows — no scratch, no cross-step
    dependency, any grid order."""
    s_ref = rest[0] if has_scale else None
    b_ref = rest[has_scale] if has_bias else None
    (o_ref,) = rest[has_scale + has_bias:]
    acc = _tap_matmuls(x_ref[...], w_ref, kh=kh, kw=kw, stride=stride,
                       th_out=th_out, w_out=w_out, n_out=o_ref.shape[-1])
    _epilogue_store(acc, s_ref, b_ref, o_ref, th_out=th_out, w_out=w_out,
                    activation=activation)


def _strided_carry_kernel(*refs, chunks: int, kh: int, kw: int,
                          stride: int, tile_h: int, th_out: int, w_out: int,
                          activation: str | None, has_bias: bool,
                          has_scale: bool = False):
    """The carry dataflow at ``stride > 1``: the window is staged in a
    VMEM scratch (``win_ref``, one slab per channel chunk) so the taps
    can load it with a stride.  Its top K-1 rows are the shadow
    registers: at the first cout tile of a strip they take the previous
    window's last K-1 rows (zeros for the first strip), and the strip's
    fresh rows go below them."""
    x_refs, w_ref = refs[:chunks], refs[chunks]
    rest = refs[chunks + 1:]
    s_ref = rest[0] if has_scale else None
    b_ref = rest[has_scale] if has_bias else None
    o_ref = rest[has_scale + has_bias]
    if kh > 1:
        win_ref = rest[has_scale + has_bias + 1]
        g = pl.program_id(2)
        co = pl.program_id(3)

        @pl.when(jnp.logical_and(co == 0, g == 0))
        def _first_strip():
            win_ref[:, :kh - 1] = jnp.zeros(
                (chunks, kh - 1) + win_ref.shape[2:], win_ref.dtype)

        @pl.when(jnp.logical_and(co == 0, g > 0))
        def _shadow_rows():
            win_ref[:, :kh - 1] = win_ref[:, tile_h:tile_h + kh - 1]

        @pl.when(co == 0)
        def _fresh_rows():
            for c in range(chunks):
                win_ref[c, kh - 1:] = x_refs[c][0]

        def load(c, rows, cols):
            return win_ref[c, rows, cols, :]
    else:
        def load(c, rows, cols):
            return x_refs[c][0, rows, cols, :]

    acc = _strided_tap_matmuls(load, w_ref, kh=kh, kw=kw, stride=stride,
                               th_out=th_out, w_out=w_out,
                               n_out=o_ref.shape[-1], chunks=chunks)
    _epilogue_store(acc, s_ref, b_ref, o_ref, th_out=th_out, w_out=w_out,
                    activation=activation)


def _strided_halo_kernel(*refs, chunks: int, kh: int, kw: int, stride: int,
                         th_out: int, w_out: int, activation: str | None,
                         has_bias: bool, has_scale: bool = False):
    """The halo dataflow at ``stride > 1``: each chunk's overlapping
    window is loaded with a stride straight from its input ref."""
    x_refs, w_ref = refs[:chunks], refs[chunks]
    rest = refs[chunks + 1:]
    s_ref = rest[0] if has_scale else None
    b_ref = rest[has_scale] if has_bias else None
    (o_ref,) = rest[has_scale + has_bias:]
    acc = _strided_tap_matmuls(
        lambda c, rows, cols: x_refs[c][rows, cols, :], w_ref, kh=kh,
        kw=kw, stride=stride, th_out=th_out, w_out=w_out,
        n_out=o_ref.shape[-1], chunks=chunks)
    _epilogue_store(acc, s_ref, b_ref, o_ref, th_out=th_out, w_out=w_out,
                    activation=activation)


def _pad_channels(a, axis: int, groups: int, cin_pg: int, padded: int):
    """``a`` with each group's ``cin_pg`` channels on ``axis`` padded
    with zeros to ``padded``."""
    if padded == cin_pg:
        return a
    shape = a.shape[:axis] + (groups, cin_pg) + a.shape[axis + 1:]
    a = a.reshape(shape)
    pads = [(0, 0)] * len(shape)
    pads[axis + 1] = (0, padded - cin_pg)
    a = jnp.pad(a, pads)
    return a.reshape(a.shape[:axis] + (groups * padded,)
                     + a.shape[axis + 2:])


def make_plan(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
              groups: int = 1, dtype_bytes: int = 4,
              tile_h: int | None = None,
              tile_cout: int | None = None,
              dataflow: str = "carry") -> ConvPlan:
    """The exact plan :func:`trim_conv2d` executes for these arguments."""
    return ConvPlan.build(x_shape, w_shape, stride=stride, pad=pad,
                          groups=groups, dtype_bytes=dtype_bytes,
                          tile_h=tile_h, tile_cout=tile_cout,
                          dataflow=dataflow)


@functools.partial(jax.jit, static_argnames=(
    "stride", "pad", "tile_h", "tile_cout", "groups", "activation",
    "dataflow", "packed_cout", "interpret", "name"))
def trim_conv2d(x: jax.Array, w: jax.Array, bias: jax.Array | None = None,
                scale: jax.Array | None = None,
                *, stride: int = 1, pad: int = 0, tile_h: int | None = None,
                tile_cout: int | None = None, groups: int = 1,
                activation: str | None = None,
                dataflow: str = "carry",
                packed_cout: int | None = None,
                interpret: bool | None = None,
                name: str | None = None) -> jax.Array:
    """Strided (grouped) 2D convolution with fused bias + activation.

    x: (N, H, W, Cin); w: (K, K, Cin/groups, Cout); bias: (Cout,) or None.
    ``pad`` is symmetric zero padding (use ``(K-1)//2`` for 'same');
    ``activation`` is one of ``None | "relu" | "gelu" | "silu"``;
    ``dataflow`` selects the strip-boundary schedule (DESIGN.md §4):
    ``"carry"`` (shadow-register scratch, serialized strips, zero halo) or
    ``"halo"`` (overlapping strip fetch, order-independent grid).

    ``scale`` enables the int8 route (DESIGN.md §11): x and w are int8,
    the K x K taps run as int8 MXU matmuls with exact int32 accumulation,
    and the fused epilogue dequantizes ``(acc + bias) * scale`` in f32 —
    ``scale`` is the per-out-channel ``x_scale * w_scale`` row of
    ``ref.dequant_params`` (shape ``(Cout,)``; the packed layout when
    ``packed_cout``), ``bias`` the *requantized int32 bias* from the same
    helper (zero-point correction plus the real bias on the scale grid),
    and the caller pre-pads 'same' inputs with the activation zero point
    (``pad=0`` here).  The output is f32.

    ``packed_cout``: when not None, ``w`` is already in the plan's
    ``padded_weight_shape`` (and ``bias``/``scale``, if given, in the
    padded ``(1, groups * cout_padded)`` layout) as produced by
    ``ops.pack_conv2d_weights`` with the same ``tile_cout``;
    ``packed_cout`` is the *logical* C_out the caller gets back.

    ``interpret=None`` auto-detects the backend (native on TPU).
    ``name`` (the conv's layer name) names the kernel: it becomes the
    custom call's instruction name in the compiled program, so a
    profiler trace shows the kernel as ``%<name>.<n>``.
    Returns (N, H_out, W_out, Cout).
    """
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; "
                         f"choose from {sorted(ACTIVATIONS, key=str)}")
    quantized = scale is not None
    if jnp.issubdtype(x.dtype, jnp.integer) != quantized:
        raise ValueError(
            "the int8 route requires BOTH integer inputs and a dequant "
            f"scale: got x.dtype={x.dtype}, scale "
            f"{'given' if quantized else 'missing'}")
    if quantized and not jnp.issubdtype(w.dtype, jnp.integer):
        raise ValueError(f"quantized conv needs integer weights, "
                         f"got {w.dtype}")
    if quantized and bias is not None \
            and not jnp.issubdtype(bias.dtype, jnp.integer):
        raise ValueError(
            "quantized conv takes the requantized int32 bias of "
            f"ref.dequant_params, got {bias.dtype}")
    interpret = resolve_interpret(interpret)
    if packed_cout is None:
        w_shape = w.shape
    else:
        if tile_cout is None:
            raise ValueError("packed weights require the tile_cout they "
                             "were packed for")
        w_shape = (w.shape[0], w.shape[1], w.shape[2], packed_cout)
    plan = make_plan(x.shape, w_shape, stride=stride, pad=pad,
                     groups=groups, dtype_bytes=x.dtype,
                     tile_h=tile_h, tile_cout=tile_cout, dataflow=dataflow)

    # --- layout: pad once in HBM, tile into non-overlapping strips ---------
    z = jnp.pad(x, ((0, 0), (pad, max(plan.pad_bottom, 0)), (pad, pad),
                    (0, 0)))
    if plan.pad_bottom < 0:
        z = z[:, :plan.rows_padded]
    assert z.shape == plan.padded_input_shape, (z.shape, plan)
    assert plan.wp >= (plan.w_out - 1) * plan.stride + plan.kw

    cpp, cout_pg = plan.cout_padded_per_group, plan.cout_per_group
    if packed_cout is None:
        wk = w.reshape(plan.kh, plan.kw, plan.cin_per_group, groups,
                       cout_pg)
        wk = jnp.pad(wk, ((0, 0),) * 4 + ((0, cpp - cout_pg),))
        wk = wk.reshape(plan.padded_weight_shape)
    else:
        assert w.shape == plan.padded_weight_shape, \
            (w.shape, plan.padded_weight_shape)
        wk = w

    co_tiles = plan.co_tiles
    if plan.stride > 1:
        # strided taps (DESIGN.md §17): the window in chunks of at most
        # one lane tile, each chunk a ref of its own
        lanes, chunks = plan.lane_chunks
        cin_pad = lanes * chunks
        z = _pad_channels(z, 3, groups, plan.cin_per_group, cin_pad)
        wk = _pad_channels(wk, 2, 1, plan.cin_per_group, cin_pad)
        common = dict(chunks=chunks, kh=plan.kh, kw=plan.kw,
                      stride=plan.stride, th_out=plan.th_out,
                      w_out=plan.w_out, activation=activation,
                      has_bias=bias is not None, has_scale=quantized)
        if plan.dataflow == "halo":
            z = jnp.pad(z, ((0, 0), (plan.kh - 1, 0), (0, 0), (0, 0)))
            th = plan.tile_h
            block = plan.halo_in_block[:3] + (lanes,)
            in_specs = [
                pl.BlockSpec((None,) + tuple(map(pl.Element, block[1:])),
                             lambda ni, gr, g, co, c=c: (
                                 ni, g * th, 0,
                                 (gr * chunks + c) * lanes if groups > 1
                                 else c * lanes))
                for c in range(chunks)]
            kernel = functools.partial(_strided_halo_kernel, **common)
            scratch_shapes = []
        else:
            in_specs = [
                pl.BlockSpec(plan.in_block[:3] + (lanes,),
                             lambda ni, gr, g, co, c=c: (
                                 ni, g, 0, gr * chunks + c))
                for c in range(chunks)]
            kernel = functools.partial(_strided_carry_kernel,
                                       tile_h=plan.tile_h, **common)
            scratch_shapes = [] if plan.kh == 1 else [pltpu.VMEM(
                (chunks, plan.tile_h + plan.kh - 1, plan.wp, lanes),
                x.dtype)]
        in_specs.append(pl.BlockSpec(
            plan.w_block[:2] + (cin_pad, plan.tile_cout),
            lambda ni, gr, g, co: (0, 0, 0, gr * co_tiles + co)))
        inputs = [z] * chunks + [wk]
    elif plan.dataflow == "halo":
        # Overlapping strip windows (element-indexed row axis):
        # strip g reads rows [g*TH, g*TH + TH + K-1) of the halo-padded
        # input, whose K-1 extra top zero rows are this strip-level image
        # of TrIM's re-fetched boundary — the halo bytes ConvPlan bills as
        # mode="trim".
        z = jnp.pad(z, ((0, 0), (plan.kh - 1, 0), (0, 0), (0, 0)))
        assert z.shape == plan.halo_padded_input_shape
        th = plan.tile_h
        in_specs = [
            _element_window(plan.halo_in_block,
                            lambda ni, gr, g, co: (ni, g * th, gr), groups),
        ]
        kernel = functools.partial(
            _halo_kernel, kh=plan.kh, kw=plan.kw, stride=plan.stride,
            th_out=plan.th_out, w_out=plan.w_out, activation=activation,
            has_bias=bias is not None, has_scale=quantized)
        scratch_shapes = []
    else:
        in_specs = [
            # fresh strip: index map ignores `co` -> fetched once per
            # strip, shared by every cout tile (IRB sharing); one channel
            # slice per group
            pl.BlockSpec(plan.in_block,
                         lambda ni, gr, g, co: (ni, g, 0, gr)),
        ]
        kernel = functools.partial(
            _carry_kernel, kh=plan.kh, kw=plan.kw, stride=plan.stride,
            th_out=plan.th_out, w_out=plan.w_out, n_cout_tiles=co_tiles,
            activation=activation, has_bias=bias is not None,
            has_scale=quantized)
        scratch_shapes = [pltpu.VMEM(plan.carry_shape, x.dtype)]

    if plan.stride == 1:
        # stationary weight tile of this group's cout block
        in_specs.append(pl.BlockSpec(
            plan.w_block,
            lambda ni, gr, g, co: (0, 0, 0, gr * co_tiles + co)))
        inputs = [z, wk]

    def _cout_row(v):
        """Pad a per-out-channel row (bias / dequant scale) to the plan's
        ``(1, groups * cout_padded)`` layout and give it the cout-tile
        BlockSpec."""
        if packed_cout is None:
            vp = jnp.pad(v.reshape(groups, cout_pg),
                         ((0, 0), (0, cpp - cout_pg)))
            vp = vp.reshape(1, groups * cpp)
        else:
            assert v.shape == (1, groups * cpp), v.shape
            vp = v
        inputs.append(vp)
        in_specs.append(pl.BlockSpec(
            (1, plan.tile_cout),
            lambda ni, gr, g, co: (0, gr * co_tiles + co)))

    if quantized:
        _cout_row(scale.astype(jnp.float32))
    if bias is not None:
        _cout_row(bias)

    compiler_params = None
    if not interpret:
        # carry: every axis is "arbitrary" (the scratch serializes the
        # sweep); halo: no cross-step state, all axes parallelizable.
        semantics = ("parallel",) * 4 if plan.dataflow == "halo" \
            else ("arbitrary",) * 4
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=KERNEL_VMEM_LIMIT)

    out_padded = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            plan.out_block,
            lambda ni, gr, g, co: (ni, g, 0, gr * co_tiles + co)),
        out_shape=jax.ShapeDtypeStruct(
            plan.padded_output_shape,
            jnp.float32 if quantized else x.dtype),
        scratch_shapes=scratch_shapes,
        compiler_params=compiler_params,
        interpret=interpret,
        name=name,
    )(*inputs)

    out = out_padded[:, plan.delta:plan.delta + plan.h_out]
    if cpp != cout_pg:
        out = out.reshape(plan.n, plan.h_out, plan.w_out, groups, cpp)
        out = out[..., :cout_pg].reshape(plan.n, plan.h_out, plan.w_out,
                                         plan.cout)
    return out


# ---------------------------------------------------------------------------
# Backward kernels (DESIGN.md §5) — both cotangents are TrIM convolutions
# ---------------------------------------------------------------------------

def make_weight_grad_plan(x_shape, w_shape, *, stride: int = 1,
                          pad: int = 0, groups: int = 1,
                          dtype_bytes: int = 4,
                          tile_go: int | None = None,
                          tile_cout: int | None = None):
    """The exact plan :func:`trim_conv2d_weight_grad` executes."""
    return ConvPlan.build_weight_grad(
        x_shape, w_shape, stride=stride, pad=pad, groups=groups,
        dtype_bytes=dtype_bytes, tile_go=tile_go, tile_cout=tile_cout)


def transpose_conv_weights(w: jax.Array, groups: int = 1) -> jax.Array:
    """Flip the spatial taps and swap the channel roles per group:
    ``(KH, KW, Cin/g, Cout) -> (KH, KW, Cout/g, Cin)`` with the output
    (= forward input) channels group-major — the weight tensor of the
    input-gradient convolution."""
    kh, kw, cin_pg, cout = w.shape
    wt = w[::-1, ::-1].reshape(kh, kw, cin_pg, groups, cout // groups)
    return wt.transpose(0, 1, 4, 3, 2).reshape(kh, kw, cout // groups,
                                               groups * cin_pg)


@functools.partial(jax.jit, static_argnames=(
    "x_shape", "stride", "pad", "groups", "tile_h", "tile_cout",
    "dataflow", "interpret"))
def trim_conv2d_input_grad(g: jax.Array, w: jax.Array, *,
                           x_shape: tuple, stride: int = 1, pad: int = 0,
                           groups: int = 1, tile_h: int | None = None,
                           tile_cout: int | None = None,
                           dataflow: str = "carry",
                           interpret: bool | None = None) -> jax.Array:
    """Input cotangent of ``trim_conv2d`` — itself a TrIM convolution.

    g: (N, H_out, W_out, Cout) output cotangent; w: (KH, KW, Cin/g, Cout)
    the forward weights; ``x_shape``/``stride``/``pad`` describe the
    FORWARD problem.  The cotangent is stride-dilated, edge-padded by
    ``K-1-pad`` (plus the ``(dim+2p-K) % s`` residual on the low edges'
    opposite sides) and convolved at stride 1 with the flipped/transposed
    weights through the ordinary forward kernel — dataflow/tile knobs and
    traffic accounting apply unchanged (``ConvPlan.build_input_grad``).
    Returns dx with shape ``x_shape``.
    """
    geo = input_grad_geometry(x_shape, w.shape, stride=stride, pad=pad,
                              groups=groups)
    if stride > 1:
        gd = jnp.zeros(geo["g_dilated_shape"], g.dtype)
        gd = gd.at[:, ::stride, ::stride, :].set(g)
    else:
        gd = g
    gp = jnp.pad(gd, ((0, 0), geo["pad_h"], geo["pad_w"], (0, 0)))
    wt = transpose_conv_weights(w, groups)
    return trim_conv2d(gp, wt, stride=1, pad=0, tile_h=tile_h,
                       tile_cout=tile_cout, groups=groups,
                       dataflow=dataflow, interpret=interpret)


def _weight_grad_kernel(x_ref, g_ref, o_ref, *, kh: int, kw: int,
                        stride: int, tile_go: int, w_out: int):
    """One grid step: strip of cotangent rows x its overlapping ifmap
    window; the K x K taps are dense MXU matmuls accumulated into the
    weight-shaped fp32 output block, which is revisited (and therefore
    stays resident) across the sequential (batch, strip) sweep."""
    ni = pl.program_id(2)
    gs = pl.program_id(3)

    @pl.when(jnp.logical_and(ni == 0, gs == 0))
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    window = x_ref[...]                    # (window_rows, Wp, Cin/g)
    cin = window.shape[-1]
    s = stride
    gv = g_ref[0].reshape(tile_go * w_out, -1)   # (TGo*Wo, TCout)
    for ki in range(kh):
        for kj in range(kw):
            rows = window[ki: ki + (tile_go - 1) * s + 1: s,
                          kj: kj + (w_out - 1) * s + 1: s, :]
            acc = jnp.dot(rows.reshape(tile_go * w_out, cin).T, gv,
                          precision=F32_DOT_PRECISION
                          if window.dtype == jnp.float32 else None,
                          preferred_element_type=jnp.float32)
            o_ref[ki, kj] = o_ref[ki, kj] + acc


@functools.partial(jax.jit, static_argnames=(
    "kernel_size", "stride", "pad", "groups", "tile_go", "tile_cout",
    "interpret"))
def trim_conv2d_weight_grad(x: jax.Array, g: jax.Array, *,
                            kernel_size: tuple, stride: int = 1,
                            pad: int = 0, groups: int = 1,
                            tile_go: int | None = None,
                            tile_cout: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Weight cotangent of ``trim_conv2d`` — the conv of ifmap over
    cotangent, with the spatial axes contracted.

    x: (N, H, W, Cin) the forward input; g: (N, H_out, W_out, Cout) the
    output cotangent; ``kernel_size`` = (KH, KW) of the forward weights
    (not derivable from the shapes when ``(dim+2p-K) % s > 0``);
    ``stride``/``pad``/``groups`` as in the forward call.
    Returns dw with shape (KH, KW, Cin/groups, Cout) in ``x.dtype``.

    All geometry comes from ``ConvPlan.build_weight_grad``; grouped /
    depthwise problems run in the same single ``pallas_call`` (group is
    a grid axis, exactly as in the forward kernel).
    """
    interpret = resolve_interpret(interpret)
    n, h, w_in, cin = x.shape
    _, h_out, w_out, cout = g.shape
    kh, kw = kernel_size
    if (h_out != (h + 2 * pad - kh) // stride + 1
            or w_out != (w_in + 2 * pad - kw) // stride + 1):
        raise ValueError(
            f"cotangent shape {g.shape[1:3]} does not match the forward "
            f"geometry of x={x.shape[1:3]} K=({kh}, {kw}) "
            f"stride={stride} pad={pad}")
    plan = make_weight_grad_plan(
        x.shape, (kh, kw, cin // groups, cout), stride=stride, pad=pad,
        groups=groups, dtype_bytes=x.dtype, tile_go=tile_go,
        tile_cout=tile_cout)

    # --- layout: fold pad into HBM, round rows up to whole strips ----------
    bottom = plan.x_rows_padded - h - pad
    xp = jnp.pad(x, ((0, 0), (pad, max(bottom, 0)), (pad, pad), (0, 0)))
    if bottom < 0:
        xp = xp[:, :plan.x_rows_padded]
    assert xp.shape == plan.padded_x_shape, (xp.shape, plan)

    cpp, cout_pg = plan.cout_padded_per_group, plan.cout_per_group
    gk = g.reshape(n, h_out, w_out, groups, cout_pg)
    gk = jnp.pad(gk, ((0, 0), (0, plan.go_rows_padded - h_out), (0, 0),
                      (0, 0), (0, cpp - cout_pg)))
    gk = gk.reshape(plan.padded_g_shape)

    co_tiles, cin_pg = plan.co_tiles, plan.cin_per_group
    tgo_s = plan.tile_go * plan.stride
    in_specs = [
        # overlapping ifmap window of the strip's receptive field
        # (element offsets: successive windows share KH - s rows)
        _element_window(plan.x_block,
                        lambda gr, co, ni, gs: (ni, gs * tgo_s, gr), groups),
        pl.BlockSpec(plan.g_block,
                     lambda gr, co, ni, gs: (ni, gs, 0,
                                             gr * co_tiles + co)),
    ]
    kernel = functools.partial(
        _weight_grad_kernel, kh=plan.kh, kw=plan.kw, stride=plan.stride,
        tile_go=plan.tile_go, w_out=plan.w_out)

    compiler_params = None
    if not interpret:
        # the weight-shaped output block accumulates across (N, strip):
        # every axis is cross-step state -> all arbitrary
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 4,
            vmem_limit_bytes=KERNEL_VMEM_LIMIT)

    dw_padded = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            plan.out_block,
            lambda gr, co, ni, gs: (0, 0, 0, gr * co_tiles + co)),
        out_shape=jax.ShapeDtypeStruct(plan.padded_out_shape, jnp.float32),
        compiler_params=compiler_params,
        interpret=interpret,
    )(xp, gk)

    dw = dw_padded.reshape(kh, kw, cin_pg, groups, cpp)[..., :cout_pg]
    return dw.reshape(kh, kw, cin_pg, cout).astype(x.dtype)


def hbm_traffic_model(n, h, width, cin, cout, k, stride=1, pad=0,
                      tile_h=8, tile_cout=128, dtype_bytes=4,
                      mode: str = "3dtrim") -> dict:
    """Analytical HBM bytes for the kernel — thin wrapper over
    ``ConvPlan.hbm_bytes`` kept for API compatibility.

    ``mode='trim'`` models strips that re-fetch their K-1 halo rows from
    HBM (no carry scratch) — the overhead the shadow registers eliminate,
    i.e. exactly what the ``dataflow="halo"`` kernel pays.
    """
    plan = ConvPlan(n=n, h=h, w=width, cin=cin, cout=cout, kh=k, kw=k,
                    stride=stride, pad=pad, dtype_bytes=dtype_bytes,
                    tile_h=tile_h, tile_cout=tile_cout)
    return plan.hbm_bytes(mode)
