"""The single source of truth for TrIM convolution planning.

Every consumer of the 3D-TrIM dataflow used to re-derive the same tile
math independently: the Pallas kernel computed strip geometry inline, the
kernel module carried its own ``hbm_traffic_model``, and ``core/model.py``
had a third analytical model.  They could silently disagree, which made
perf hillclimbing against the analytical traffic numbers untrustworthy.

This module owns all of it (DESIGN.md §3):

* :class:`ConvPlan` — geometry plan for one 2D convolution: strip tiling,
  shadow-register carry sizes, Pallas grid shape, padded HBM layouts, and
  the analytical HBM byte counts in ``mode="3dtrim"`` (carry resident in
  VMEM, zero halo traffic) vs ``mode="trim"`` (K-1 halo rows re-fetched
  per strip — the overhead the paper's shadow registers eliminate).
  ``kernels/trim_conv2d.py`` builds its ``pallas_call`` from the plan;
  ``core/roofline.py`` and ``benchmarks/*`` read traffic and arithmetic
  intensity from the same object.

  The plan carries a ``dataflow`` axis (DESIGN.md §4) selecting which of
  the two schedules the kernel executes:

  * ``"carry"`` — the paper's shadow registers: strips are
    non-overlapping and the K-1 boundary rows ride in a VMEM scratch
    across *sequential* grid steps.  Zero halo traffic
    (``mode="3dtrim"`` accounting) but the (N, group, strip) axes must
    execute in order.
  * ``"halo"`` — TrIM-style over-fetch: every strip re-reads its K-1
    predecessor rows through an overlapping BlockSpec.  Pays the
    ``mode="trim"`` halo bytes but has no cross-step state, so every
    grid axis is order-independent (parallelizable / reorderable).

  The autotuner (``core/autotune.py``) picks the dataflow per layer from
  exactly these numbers.

* **Backward planning** — training runs the two conv cotangents as TrIM
  convolutions themselves (DESIGN.md §5), and their geometry comes from
  the same single source of truth:

  * :func:`input_grad_geometry` / :meth:`ConvPlan.build_input_grad` —
    the input cotangent is a *stride-1* TrIM convolution of the
    stride-dilated, edge-padded output cotangent with the spatially
    flipped, channel-transposed weights.  ``build_input_grad`` returns
    the ordinary :class:`ConvPlan` that conv executes, so the backward
    pass inherits the full ``carry``/``halo`` dataflow axis, the strip
    math and the HBM accounting of the forward kernel.
  * :class:`WeightGradPlan` / :meth:`ConvPlan.build_weight_grad` — the
    weight cotangent is a conv of the ifmap over the cotangent with the
    *spatial* axes contracted: strips of cotangent rows stay resident
    with their overlapping ifmap window (a halo-style fetch) while the
    K x K taps accumulate into a weight-shaped output revisited across
    the (batch, strip) sweep.  The plan owns the strip/grid/padded
    layouts and the analytical HBM bytes of that schedule.

* :class:`DepthwisePlan` — a depthwise conv (one filter per channel) on
  the VPU kernel ``kernels/trim_depthwise.py`` (DESIGN.md §14): strips
  of output rows with the ``K - stride`` carried input rows, channel
  and image tiles, its VMEM model and its HBM bytes, which
  ``core/autotune.py`` ranks its knobs by.

* :class:`Conv1dPlan` — the 1D image of the same plan, consumed by
  ``kernels/trim_conv1d.py``.

* :func:`slice_reads_per_channel` — the paper-level per-slice external
  read count (Fig. 1), consumed by ``core/model.py`` (Fig. 6 accounting)
  and validated cycle-by-cycle by ``core/dataflow.TrimSliceSim``.

Grouped / depthwise convolution (``groups`` > 1, the MobileNet scenario
of the paper's OPs-per-access comparison) is a first-class plan axis: the
weight tensor is ``(K, K, Cin/groups, Cout)`` and every derived quantity
(carry width, weight blocks, MACs, traffic) accounts for the reduced
per-group fan-in.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

from repro.core.roofline import HBM_BW, dtype_width

# TPU v5e has 128 MiB of VMEM per TensorCore; a Mosaic kernel may use
# its scoped limit, 16 MiB unless the kernel asks for more.  Every TrIM
# kernel asks for KERNEL_VMEM_LIMIT (``CompilerParams(vmem_limit_bytes=
# ...)``), and every plan that the default strip choice or the tuner
# admits keeps its modeled resident set (``vmem_resident_bytes``) under
# KERNEL_VMEM_BUDGET, leaving a quarter of the limit to Mosaic's internal
# scratch and the temporaries the model does not itemize.
KERNEL_VMEM_LIMIT = 32 << 20
KERNEL_VMEM_BUDGET = KERNEL_VMEM_LIMIT * 3 // 4

LANES = 128

# An f32 matmul at full precision (the kernels' f32 taps) runs as bf16
# passes over split operands; Mosaic keeps the splits and partial
# products in VMEM.  Counted as this many f32 buffers of the wider
# matmul operand — measured on the v5e compiler for the VGG-16 layers:
# at most ~11 (C=64..128), fewer for C=512.
F32_MATMUL_BUFFERS = 12


def vmem_tile_bytes(shape, dtype_bytes: int) -> int:
    """Bytes of one VMEM buffer of ``shape``: the last two dims padded to
    the TPU's (sublane, lane) tile — 8 x 128 for 32-bit values, with
    narrower dtypes packing 16 or 32 rows per sublane tile."""
    *major, rows, lanes = shape
    sub = 8 * max(4 // dtype_bytes, 1)
    return (math.prod(major) * -(-rows // sub) * sub
            * -(-lanes // LANES) * LANES * dtype_bytes)


def _largest_fitting(lo: int, hi: int, fits) -> int:
    """Largest ``m`` in ``[lo, hi]`` with ``fits(m)`` (monotone: true up
    to some point, false after), or ``lo`` when none fits."""
    if fits(hi):
        return hi
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid
    return lo


def resolve_dtype_bytes(dtype_bytes) -> int:
    """Normalize a plan's ``dtype_bytes`` argument.

    Plain ints pass through; anything dtype-like (``"bfloat16"``, ``"s8"``,
    ``np.dtype``, an array's ``.dtype``) is priced through the shared
    :func:`repro.core.roofline.dtype_width` table so plan traffic and
    roofline HLO parsing can never disagree on a width.
    """
    if isinstance(dtype_bytes, int):
        return dtype_bytes
    return dtype_width(dtype_bytes)


# ---------------------------------------------------------------------------
# Paper-level slice model (Fig. 1) — consumed by core/model and core/dataflow
# ---------------------------------------------------------------------------

def slice_reads_per_channel(height: int, width: int, kernel: int,
                            stride: int = 1, *, shadow: bool) -> int:
    """External reads of one ifmap channel for one pass of a TrIM slice.

    The sliding-window band advances by ``stride`` rows per output row.
    With shadow registers (3D-TrIM) every real activation is read exactly
    once.  Without them (TrIM), every band advance re-reads the last
    ``K-1`` activations of each of the ``K - stride`` re-used rows.
    """
    ideal = height * width
    if shadow:
        return ideal
    out_rows = (height - kernel) // stride + 1
    band_advances = max(out_rows - 1, 0)
    reused_rows = max(kernel - stride, 0)
    rereads_per_advance = reused_rows * (kernel - 1)
    return ideal + band_advances * rereads_per_advance


# ---------------------------------------------------------------------------
# 2D plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvPlan:
    """Geometry + traffic plan for one strided (grouped) 2D convolution.

    Shapes follow the kernel convention: input ``(N, H, W, Cin)``, weights
    ``(KH, KW, Cin/groups, Cout)``, symmetric zero padding ``pad``.  All
    derived quantities — strip geometry, carry size, grid, padded layouts,
    HBM bytes — are pure functions of these fields, so a plan printed by a
    benchmark is bit-identical to the one the kernel executes.
    """

    n: int
    h: int
    w: int
    cin: int
    cout: int
    kh: int
    kw: int
    stride: int = 1
    pad: int = 0
    groups: int = 1
    dtype_bytes: int = 4
    tile_h: int = 8            # strip height in *input* rows
    tile_cout: int = 128       # C_out tile per grid step (per group)
    dataflow: str = "carry"    # "carry" (shadow regs) | "halo" (over-fetch)
    vmem_budget: int = KERNEL_VMEM_BUDGET

    def __post_init__(self):
        if self.dataflow not in ("carry", "halo"):
            raise ValueError(
                f"dataflow={self.dataflow!r} must be 'carry' or 'halo'")
        if self.cin % self.groups or self.cout % self.groups:
            raise ValueError(
                f"groups={self.groups} must divide cin={self.cin} and "
                f"cout={self.cout}")
        if self.tile_h % self.stride:
            raise ValueError(
                f"tile_h={self.tile_h} must be a multiple of the stride "
                f"{self.stride}")
        if self.tile_h < 1 or self.tile_cout < 1:
            raise ValueError(
                f"tile_h={self.tile_h} / tile_cout={self.tile_cout} "
                "must be >= 1")
        if self.h_out < 1 or self.w_out < 1:
            raise ValueError("empty output: input smaller than kernel")
        # Canonicalize oversized strips (DESIGN.md §6): any tile_h beyond
        # the full-height strip (one strip covering h_out + delta output
        # rows) is clamped to it, so plans built with tile_h > H_out are
        # identical — same padding, same grid, same traffic — instead of
        # billing/padding ever more rows that neither dataflow reads.
        full = (self.h_out + self.delta) * self.stride
        if self.tile_h > full:
            object.__setattr__(self, "tile_h", full)

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, x_shape, w_shape, *, stride: int = 1, pad: int = 0,
              groups: int = 1, dtype_bytes: int = 4,
              tile_h: int | None = None, tile_cout: int | None = None,
              dataflow: str = "carry",
              vmem_budget: int = KERNEL_VMEM_BUDGET) -> "ConvPlan":
        """Plan from array shapes, auto-choosing tiles when not given.

        ``tile_cout`` defaults to an MXU-friendly 128 when it divides the
        per-group C_out, else the whole per-group C_out.  ``tile_h`` is the
        largest stride multiple whose modeled resident set
        (:attr:`vmem_resident_bytes`) fits ``vmem_budget``.
        """
        n, h, w, cin = x_shape
        kh, kw, cin_pg, cout = w_shape
        if cin_pg * groups != cin:
            raise ValueError(
                f"weights expect cin/groups={cin_pg} with groups={groups}, "
                f"input has cin={cin}")
        dtype_bytes = resolve_dtype_bytes(dtype_bytes)
        s = stride
        cout_pg = cout // groups
        if tile_cout is None:
            tile_cout = min(cout_pg, 128 if cout_pg % 128 == 0 else cout_pg)

        def plan(th: int) -> "ConvPlan":
            return cls(n=n, h=h, w=w, cin=cin, cout=cout, kh=kh, kw=kw,
                       stride=s, pad=pad, groups=groups,
                       dtype_bytes=dtype_bytes, tile_h=th,
                       tile_cout=tile_cout, dataflow=dataflow,
                       vmem_budget=vmem_budget)

        if tile_h is None:
            full = plan(s)
            m = _largest_fitting(
                1, full.h_out + full.delta,
                lambda m: plan(m * s).vmem_resident_bytes <= vmem_budget)
            tile_h = m * s
        return plan(tile_h)

    @classmethod
    def from_layer(cls, layer, *, n: int = 1, dtype_bytes: int = 4,
                   tile_h: int | None = None, tile_cout: int | None = None,
                   dataflow: str = "carry",
                   vmem_budget: int = KERNEL_VMEM_BUDGET) -> "ConvPlan":
        """Plan from a ``core.model.ConvLayer`` description (duck-typed)."""
        groups = getattr(layer, "groups", 1)
        return cls.build(
            (n, layer.ifmap, layer.ifmap, layer.in_channels),
            (layer.kernel, layer.kernel, layer.in_channels // groups,
             layer.out_channels),
            stride=layer.stride, pad=layer.padding, groups=groups,
            dtype_bytes=dtype_bytes, tile_h=tile_h, tile_cout=tile_cout,
            dataflow=dataflow, vmem_budget=vmem_budget)

    @classmethod
    def build_input_grad(cls, x_shape, w_shape, *, stride: int = 1,
                         pad: int = 0, groups: int = 1,
                         dtype_bytes: int = 4, tile_h: int | None = None,
                         tile_cout: int | None = None,
                         dataflow: str = "carry",
                         vmem_budget: int = KERNEL_VMEM_BUDGET
                         ) -> "ConvPlan":
        """Plan for the *input-gradient* conv of a forward problem.

        ``x_shape`` / ``w_shape`` / ``stride`` / ``pad`` describe the
        FORWARD convolution (the shapes the forward kernel saw).  The
        returned plan is the ordinary stride-1 ConvPlan that the input
        cotangent executes: input = the stride-dilated, ``K-1-pad``
        edge-padded output cotangent ``(N, ·, ·, Cout)``; weights = the
        flipped/transposed ``(KH, KW, Cout/groups, Cin)`` tensor.  Every
        dataflow/tile knob of the forward kernel applies unchanged.
        """
        geo = input_grad_geometry(x_shape, w_shape, stride=stride,
                                  pad=pad, groups=groups)
        return cls.build(geo["g_padded_shape"], geo["wt_shape"], stride=1,
                         pad=0, groups=groups, dtype_bytes=dtype_bytes,
                         tile_h=tile_h, tile_cout=tile_cout,
                         dataflow=dataflow, vmem_budget=vmem_budget)

    @classmethod
    def build_weight_grad(cls, x_shape, w_shape, *, stride: int = 1,
                          pad: int = 0, groups: int = 1,
                          dtype_bytes: int = 4,
                          tile_go: int | None = None,
                          tile_cout: int | None = None,
                          vmem_budget: int = KERNEL_VMEM_BUDGET
                          ) -> "WeightGradPlan":
        """Plan for the *weight-gradient* conv of a forward problem.

        Arguments describe the FORWARD convolution; the returned
        :class:`WeightGradPlan` owns the strip/grid/traffic math of the
        spatially-contracted conv (ifmap over cotangent) the weight
        cotangent kernel executes.
        """
        n, h, w, cin = x_shape
        kh, kw, cin_pg, cout = w_shape
        if cin_pg * groups != cin:
            raise ValueError(
                f"weights expect cin/groups={cin_pg} with groups={groups}, "
                f"input has cin={cin}")
        dtype_bytes = resolve_dtype_bytes(dtype_bytes)
        h_out = (h + 2 * pad - kh) // stride + 1
        cout_pg = cout // groups
        if tile_cout is None:
            tile_cout = cout_pg

        def plan(tg: int) -> "WeightGradPlan":
            return WeightGradPlan(
                n=n, h=h, w=w, cin=cin, cout=cout, kh=kh, kw=kw,
                stride=stride, pad=pad, groups=groups,
                dtype_bytes=dtype_bytes, tile_go=min(tg, h_out),
                tile_cout=min(tile_cout, cout_pg), vmem_budget=vmem_budget)

        if tile_go is None:
            tile_go = _largest_fitting(
                1, h_out,
                lambda t: plan(t).vmem_resident_bytes <= vmem_budget)
        return plan(tile_go)

    # -- problem geometry --------------------------------------------------

    @property
    def cin_per_group(self) -> int:
        return self.cin // self.groups

    @property
    def cout_per_group(self) -> int:
        return self.cout // self.groups

    @property
    def h_out(self) -> int:
        return (self.h + 2 * self.pad - self.kh) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w + 2 * self.pad - self.kw) // self.stride + 1

    # -- strip geometry (DESIGN.md §2) -------------------------------------

    @property
    def th_out(self) -> int:
        """Output rows produced per strip."""
        return self.tile_h // self.stride

    @property
    def delta(self) -> int:
        """Top rows of the padded output that are sliced off."""
        return (self.kh - 1) // self.stride

    @property
    def row_offset(self) -> int:
        """Static in-window row offset ``(KH-1) mod stride``."""
        return (self.kh - 1) % self.stride

    @property
    def g_tiles(self) -> int:
        """Number of input strips (grid steps along H)."""
        return math.ceil((self.h_out + self.delta) / self.th_out)

    @property
    def rows_padded(self) -> int:
        """Input rows after bottom padding to a whole number of strips."""
        return self.g_tiles * self.tile_h

    @property
    def pad_bottom(self) -> int:
        """Bottom zero padding (negative: the input is cropped)."""
        return self.rows_padded - self.h - self.pad

    @property
    def wp(self) -> int:
        """Padded input width."""
        return self.w + 2 * self.pad

    @property
    def co_tiles(self) -> int:
        """C_out tiles per group (grid steps along C_out)."""
        return math.ceil(self.cout_per_group / self.tile_cout)

    @property
    def cout_padded_per_group(self) -> int:
        return self.co_tiles * self.tile_cout

    # -- pallas_call layout ------------------------------------------------

    @property
    def grid(self) -> tuple[int, int, int, int]:
        """(N, groups, strips, C_out tiles) — C_out innermost so a strip is
        fetched once and reused by every C_out tile (shared-IRB image)."""
        return (self.n, self.groups, self.g_tiles, self.co_tiles)

    @property
    def padded_input_shape(self) -> tuple[int, int, int, int]:
        return (self.n, self.rows_padded, self.wp, self.cin)

    @property
    def padded_weight_shape(self) -> tuple[int, int, int, int]:
        return (self.kh, self.kw, self.cin_per_group,
                self.groups * self.cout_padded_per_group)

    @property
    def padded_output_shape(self) -> tuple[int, int, int, int]:
        return (self.n, self.g_tiles * self.th_out, self.w_out,
                self.groups * self.cout_padded_per_group)

    @property
    def in_block(self) -> tuple[int, int, int, int]:
        return (1, self.tile_h, self.wp, self.cin_per_group)

    @property
    def w_block(self) -> tuple[int, int, int, int]:
        return (self.kh, self.kw, self.cin_per_group, self.tile_cout)

    @property
    def out_block(self) -> tuple[int, int, int, int]:
        return (1, self.th_out, self.w_out, self.tile_cout)

    @property
    def carry_shape(self) -> tuple[int, int, int]:
        """Shadow-register scratch: the K-1 boundary rows carried across
        strips (per group).  Only allocated by the ``"carry"`` dataflow."""
        return (max(self.kh - 1, 1), self.wp, self.cin_per_group)

    # -- halo dataflow layout (overlapping strips, no carry) ---------------

    @property
    def halo_in_block(self) -> tuple[int, int, int, int]:
        """Input window of one halo grid step: the strip *plus* its K-1
        predecessor rows, fetched through an overlapping BlockSpec."""
        return (1, self.tile_h + self.kh - 1, self.wp, self.cin_per_group)

    @property
    def lane_chunks(self) -> tuple[int, int]:
        """``(lanes, chunks)`` of a ``stride > 1`` window (DESIGN.md
        §17): the chip loads a ref with a stride only where its base
        buffer is at most one lane tile wide, so the kernel takes the
        window as ``chunks`` refs of ``lanes`` channels — one lane tile
        (128) each, or the whole group where it is narrower — padding
        the channels with zeros up to ``lanes * chunks``."""
        lanes = min(self.cin_per_group, LANES)
        return lanes, -(-self.cin_per_group // lanes)

    @property
    def halo_padded_input_shape(self) -> tuple[int, int, int, int]:
        """Padded input with K-1 extra zero rows on top so strip 0's
        overlapping window starts at element row 0."""
        return (self.n, self.kh - 1 + self.rows_padded, self.wp, self.cin)

    @property
    def vmem_resident_bytes(self) -> int:
        """Modeled VMEM of one grid step, as Mosaic allocates it.

        Every buffer is padded to the (sublane, 128-lane) tile
        (:func:`vmem_tile_bytes`).  The pipelined blocks — input strip
        (``tile_h`` rows for ``"carry"``, ``tile_h + K-1`` for the
        overlapping ``"halo"`` window), weight tile, output tile and the
        bias / dequant-scale rows — are double-buffered.  On top: the
        ``"carry"`` scratch of K-1 rows, the ``tile_h + K-1``-row window
        value the taps slice, one tap's matmul operand and the f32 (or
        int32) accumulator; for f32 operands the full-precision matmul's
        split operands and partial products (:data:`F32_MATMUL_BUFFERS`).
        The int8 route writes f32 outputs.  At ``stride > 1`` the taps
        are strided loads (the window value is then the carry's staged
        scratch), and Mosaic holds every tap's loaded view at once: one
        ``(TH_out, W_out, lanes)`` buffer per tap and lane chunk
        (measured on the v5e compiler: the 7x7/s2 stem needs 14, 29 and
        53 MiB at ``tile_h`` 8, 16 and 28).
        """
        db = self.dtype_bytes
        cin, tc = self.cin_per_group, self.tile_cout
        in_block = self.halo_in_block if self.dataflow == "halo" \
            else self.in_block
        rows_out = self.th_out * self.w_out
        total = (
            2 * vmem_tile_bytes(in_block[1:], db)
            + 2 * vmem_tile_bytes(self.w_block, db)
            + 2 * vmem_tile_bytes(self.out_block[1:], 4 if db == 1 else db)
            + 2 * 2 * vmem_tile_bytes((1, tc), 4)
            + vmem_tile_bytes((self.tile_h + self.kh - 1, self.wp, cin), db)
            + vmem_tile_bytes((rows_out, cin), db)
            + vmem_tile_bytes((rows_out, tc), 4))
        if self.dataflow == "carry":
            total += vmem_tile_bytes(self.carry_shape, db)
        if self.stride > 1:
            lanes, chunks = self.lane_chunks
            total += self.kh * self.kw * chunks * vmem_tile_bytes(
                (self.th_out, self.w_out, lanes), db)
        if db == 4:
            total += F32_MATMUL_BUFFERS * vmem_tile_bytes(
                (rows_out, max(cin, tc)), 4)
        return total

    # -- arithmetic --------------------------------------------------------

    @property
    def macs(self) -> int:
        return (self.n * self.h_out * self.w_out * self.cout
                * self.kh * self.kw * self.cin_per_group)

    @property
    def flops(self) -> int:
        return 2 * self.macs

    # -- analytical HBM traffic -------------------------------------------

    @property
    def traffic_mode(self) -> str:
        """The accounting mode this plan's dataflow actually pays:
        ``"carry"`` moves the ``"3dtrim"`` bytes, ``"halo"`` the
        ``"trim"`` bytes."""
        return "3dtrim" if self.dataflow == "carry" else "trim"

    def halo_rows(self, mode: str | None = None) -> int:
        """Input rows re-fetched from HBM across one (N, group) sweep.

        ``"3dtrim"``: the K-1 boundary rows live in the VMEM carry scratch
        — zero halo.  ``"trim"``: every strip after the first re-fetches
        its K-1 predecessor rows, the overhead of Fig. 1 at strip level.
        ``None`` uses the plan's own ``dataflow`` accounting.
        """
        mode = self.traffic_mode if mode is None else mode
        if mode == "3dtrim":
            return 0
        if mode == "trim":
            return (self.g_tiles - 1) * (self.kh - 1)
        raise ValueError(f"unknown mode {mode!r}")

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Analytical HBM bytes moved by the kernel's schedule.

        ``input`` in ``"3dtrim"`` mode equals exactly the padded-input
        array size (each strip fetched once, shared by all C_out tiles);
        ``weights`` are re-streamed once per strip; ``output`` counts the
        useful (un-padded) result.  ``mode=None`` accounts the plan's own
        ``dataflow`` (carry -> "3dtrim", halo -> "trim").
        """
        db = self.dtype_bytes
        halo = self.halo_rows(mode)
        in_bytes = self.n * (self.rows_padded + halo) * self.wp \
            * self.cin * db
        w_bytes = (self.kh * self.kw * self.cin_per_group * self.cout
                   * db * self.g_tiles)
        out_bytes = self.n * self.h_out * self.w_out * self.cout * db
        return dict(input=in_bytes, weights=w_bytes, output=out_bytes,
                    total=in_bytes + w_bytes + out_bytes,
                    overhead_pct=100.0 * halo / max(self.rows_padded, 1))

    def arithmetic_intensity(self, mode: str | None = None) -> float:
        """FLOPs per HBM byte — the roofline x-coordinate.  ``mode=None``
        uses the plan's own ``dataflow`` accounting."""
        return self.flops / max(self.hbm_bytes(mode)["total"], 1)

    def as_dict(self) -> dict:
        t = self.hbm_bytes()
        return dict(grid=self.grid, tile_h=self.tile_h,
                    tile_cout=self.tile_cout, dataflow=self.dataflow,
                    th_out=self.th_out,
                    g_tiles=self.g_tiles, co_tiles=self.co_tiles,
                    carry_shape=self.carry_shape,
                    vmem_resident_bytes=self.vmem_resident_bytes,
                    flops=self.flops, hbm_total=t["total"],
                    arithmetic_intensity=self.arithmetic_intensity())


# ---------------------------------------------------------------------------
# Backward geometry (DESIGN.md §5)
# ---------------------------------------------------------------------------

def input_grad_geometry(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                        groups: int = 1) -> dict:
    """Geometry of the input-gradient conv for one forward problem.

    The input cotangent of ``y = conv(x, w, stride, pad)`` is itself a
    *stride-1, valid* convolution:

        dx = conv(dilate_s(dy) edge-padded by K-1-pad, flip_hw(w)^T)

    where the bottom/right padding carries ``(dim + 2*pad - K) % stride``
    extra zeros so the result lands exactly back on ``x``'s shape.
    Requires ``pad <= K-1`` on both axes (true for 'same' and 'valid').

    Returns a dict with the dilated cotangent shape (``g_dilated_shape``),
    the padded conv input (``g_padded_shape``), the per-axis pad tuples
    (``pad_h``/``pad_w``) and the transposed weight shape (``wt_shape``
    = ``(KH, KW, Cout/groups, Cin)``).
    """
    n, h, w, cin = x_shape
    kh, kw, cin_pg, cout = w_shape
    if cin_pg * groups != cin:
        raise ValueError(
            f"weights expect cin/groups={cin_pg} with groups={groups}, "
            f"input has cin={cin}")
    if pad > kh - 1 or pad > kw - 1:
        raise ValueError(
            f"input-grad conv requires pad <= K-1, got pad={pad} "
            f"for K=({kh}, {kw})")
    s = stride
    h_out = (h + 2 * pad - kh) // s + 1
    w_out = (w + 2 * pad - kw) // s + 1
    hd = (h_out - 1) * s + 1
    wd = (w_out - 1) * s + 1
    r_h = (h + 2 * pad - kh) % s
    r_w = (w + 2 * pad - kw) % s
    pad_h = (kh - 1 - pad, kh - 1 - pad + r_h)
    pad_w = (kw - 1 - pad, kw - 1 - pad + r_w)
    return dict(
        h_out=h_out, w_out=w_out, stride=s,
        g_dilated_shape=(n, hd, wd, cout),
        g_padded_shape=(n, hd + sum(pad_h), wd + sum(pad_w), cout),
        pad_h=pad_h, pad_w=pad_w,
        wt_shape=(kh, kw, cout // groups, cin),
    )


@dataclass(frozen=True)
class WeightGradPlan:
    """Geometry + traffic plan for one weight-gradient conv.

    The weight cotangent contracts the *spatial* axes:

        dw[ki, kj, ci, co] = sum_{n, oy, ox}
            x_pad[n, oy*s + ki, ox*s + kj, ci] * dy[n, oy, ox, co]

    The kernel schedule (``kernels/trim_conv2d.trim_conv2d_weight_grad``)
    keeps ``tile_go`` cotangent rows resident per grid step together with
    their overlapping ifmap window of ``(tile_go-1)*s + KH`` rows (a
    halo-style fetch — successive windows share ``KH - s`` rows), runs the
    K x K taps as dense MXU matmuls ``(Cin/g, TGo*W_out) x (TGo*W_out,
    TCout)``, and accumulates into a weight-shaped fp32 output block
    revisited across the sequential (batch, strip) sweep — the
    shadow-register idea applied to a weight-stationary drain.

    All fields describe the FORWARD problem (``h``/``w`` already include
    any 'same' pre-padding folded by the caller; ``pad`` is the residual
    symmetric padding, normally 0).
    """

    n: int
    h: int
    w: int
    cin: int
    cout: int
    kh: int
    kw: int
    stride: int = 1
    pad: int = 0
    groups: int = 1
    dtype_bytes: int = 4
    tile_go: int = 8           # cotangent rows resident per grid step
    tile_cout: int = 128       # C_out tile per grid step (per group)
    vmem_budget: int = KERNEL_VMEM_BUDGET

    def __post_init__(self):
        if self.cin % self.groups or self.cout % self.groups:
            raise ValueError(
                f"groups={self.groups} must divide cin={self.cin} and "
                f"cout={self.cout}")
        if self.tile_go < 1:
            raise ValueError(f"tile_go={self.tile_go} must be >= 1")
        if self.h_out < 1 or self.w_out < 1:
            raise ValueError("empty output: input smaller than kernel")
        # same canonical clamp as ConvPlan.tile_h: a cotangent strip
        # taller than the whole cotangent is the full-height strip
        if self.tile_go > self.h_out:
            object.__setattr__(self, "tile_go", self.h_out)

    # -- problem geometry --------------------------------------------------

    @property
    def cin_per_group(self) -> int:
        return self.cin // self.groups

    @property
    def cout_per_group(self) -> int:
        return self.cout // self.groups

    @property
    def h_out(self) -> int:
        """Cotangent rows (the forward output height)."""
        return (self.h + 2 * self.pad - self.kh) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w + 2 * self.pad - self.kw) // self.stride + 1

    @property
    def wp(self) -> int:
        """Padded ifmap width (as the forward kernel sees it)."""
        return self.w + 2 * self.pad

    # -- strip geometry ----------------------------------------------------

    @property
    def go_tiles(self) -> int:
        """Cotangent strips (grid steps along the output-row axis)."""
        return math.ceil(self.h_out / self.tile_go)

    @property
    def go_rows_padded(self) -> int:
        return self.go_tiles * self.tile_go

    @property
    def window_rows(self) -> int:
        """Ifmap rows resident per grid step (overlapping halo window)."""
        return (self.tile_go - 1) * self.stride + self.kh

    @property
    def x_rows_padded(self) -> int:
        """Ifmap rows after bottom zero-padding so the last strip's
        window is in bounds (padded rows only ever meet zero cotangent
        rows, so they contribute nothing)."""
        return (self.go_rows_padded - 1) * self.stride + self.kh

    @property
    def co_tiles(self) -> int:
        return math.ceil(self.cout_per_group / self.tile_cout)

    @property
    def cout_padded_per_group(self) -> int:
        return self.co_tiles * self.tile_cout

    # -- pallas_call layout ------------------------------------------------

    @property
    def grid(self) -> tuple[int, int, int, int]:
        """(groups, C_out tiles, N, strips) — (N, strip) innermost so the
        revisited weight-shaped output block sees its whole accumulation
        sweep on consecutive grid steps."""
        return (self.groups, self.co_tiles, self.n, self.go_tiles)

    @property
    def padded_x_shape(self) -> tuple[int, int, int, int]:
        return (self.n, self.x_rows_padded, self.wp, self.cin)

    @property
    def padded_g_shape(self) -> tuple[int, int, int, int]:
        return (self.n, self.go_rows_padded, self.w_out,
                self.groups * self.cout_padded_per_group)

    @property
    def x_block(self) -> tuple[int, int, int, int]:
        """Element-indexed (overlapping) window: the strip's cotangent rows'
        receptive field."""
        return (1, self.window_rows, self.wp, self.cin_per_group)

    @property
    def g_block(self) -> tuple[int, int, int, int]:
        return (1, self.tile_go, self.w_out, self.tile_cout)

    @property
    def out_block(self) -> tuple[int, int, int, int]:
        return (self.kh, self.kw, self.cin_per_group, self.tile_cout)

    @property
    def padded_out_shape(self) -> tuple[int, int, int, int]:
        return (self.kh, self.kw, self.cin_per_group,
                self.groups * self.cout_padded_per_group)

    @property
    def vmem_resident_bytes(self) -> int:
        """Modeled VMEM of one grid step, tile-padded as in
        :attr:`ConvPlan.vmem_resident_bytes`: the double-buffered ifmap
        window, cotangent strip and f32 weight-shaped output block, plus
        the window value, one tap's operand and its transpose, the
        flattened cotangent, one tap's f32 partial and, for f32
        operands, the full-precision matmul's buffers."""
        db = self.dtype_bytes
        cin, tc = self.cin_per_group, self.tile_cout
        rows = self.tile_go * self.w_out
        split = 0 if db != 4 else F32_MATMUL_BUFFERS * max(
            vmem_tile_bytes((cin, rows), 4), vmem_tile_bytes((rows, tc), 4))
        return (split + 2 * vmem_tile_bytes(self.x_block[1:], db)
                + 2 * vmem_tile_bytes(self.g_block[1:], db)
                + 2 * vmem_tile_bytes(self.out_block, 4)
                + vmem_tile_bytes(self.x_block[1:], db)
                + vmem_tile_bytes((rows, cin), db)
                + vmem_tile_bytes((cin, rows), db)
                + vmem_tile_bytes((rows, tc), db)
                + vmem_tile_bytes((cin, tc), 4))

    # -- arithmetic / analytical HBM traffic --------------------------------

    @property
    def macs(self) -> int:
        """Same MAC count as the forward conv (each forward MAC has
        exactly one weight-grad image)."""
        return (self.n * self.h_out * self.w_out * self.cout
                * self.kh * self.kw * self.cin_per_group)

    @property
    def flops(self) -> int:
        return 2 * self.macs

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Analytical HBM bytes of the kernel's schedule.  The ifmap is
        streamed window-by-window — successive windows overlap by
        ``KH - stride`` rows (the halo this schedule pays) — and the whole
        sweep repeats per C_out tile; the cotangent is read once per
        C_out-tile sweep; the output is the padded weight block written
        once.  ``mode`` is accepted for interface parity with
        :class:`ConvPlan` (the schedule is fixed)."""
        db = self.dtype_bytes
        in_bytes = (self.n * self.go_tiles * self.window_rows * self.wp
                    * self.cin * db * self.co_tiles)
        # each (group, co) sweep reads only its own cotangent channel
        # slice, so the full padded cotangent moves exactly once
        g_bytes = (self.n * self.go_rows_padded * self.w_out
                   * self.groups * self.cout_padded_per_group * db)
        out_bytes = self.kh * self.kw * self.cin_per_group \
            * self.groups * self.cout_padded_per_group * 4
        ideal = self.n * self.x_rows_padded * self.wp * self.cin * db
        return dict(input=in_bytes, weights=g_bytes, output=out_bytes,
                    total=in_bytes + g_bytes + out_bytes,
                    overhead_pct=100.0 * max(in_bytes - ideal, 0)
                    / max(ideal, 1))

    def arithmetic_intensity(self, mode: str | None = None) -> float:
        return self.flops / max(self.hbm_bytes(mode)["total"], 1)

    def as_dict(self) -> dict:
        t = self.hbm_bytes()
        return dict(grid=self.grid, tile_go=self.tile_go,
                    tile_cout=self.tile_cout, go_tiles=self.go_tiles,
                    co_tiles=self.co_tiles, window_rows=self.window_rows,
                    vmem_resident_bytes=self.vmem_resident_bytes,
                    flops=self.flops, hbm_total=t["total"],
                    arithmetic_intensity=self.arithmetic_intensity())


# ---------------------------------------------------------------------------
# Depthwise 2D plan (the VPU kernel, kernels/trim_depthwise.py)
# ---------------------------------------------------------------------------

#: Fixed cost of one Pallas grid step on a TPU (pipeline bookkeeping and
#: DMA issue), the term that lets the depthwise tuner weigh many small
#: blocks against fewer large ones.
GRID_STEP_S = 0.35e-6


def _divisors(m: int) -> list[int]:
    return [d for d in range(1, m + 1) if m % d == 0]


@dataclass(frozen=True)
class DepthwisePlan:
    """Geometry + traffic plan of one depthwise conv (``groups == C``,
    one filter per channel) on the VPU kernel (DESIGN.md §14).

    ``(n, h, w, c)`` is the input as the kernel sees it, already padded.
    A strip is ``tile_h`` *output* rows, ``stride * tile_h`` fresh input
    rows; the ``carry_rows`` = ``K - stride`` input rows above them come
    from a VMEM scratch the previous strip wrote (the shadow registers;
    the first strip of a sweep reads them from the input's ``head``
    rows).  Channels ride the lanes in tiles of ``tile_c``; ``tile_n``
    images share a grid step.  A strided conv reads its columns with
    strided loads, which the chip's compiler gives one lane tile at
    most: ``tile_c <= 128`` there.
    """

    n: int
    h: int
    w: int
    c: int
    k: int
    stride: int = 1
    dtype_bytes: int = 4
    tile_h: int = 8            # output rows per strip
    tile_c: int = 128          # channels per grid step
    tile_n: int = 1            # images per grid step
    vmem_budget: int = KERNEL_VMEM_BUDGET

    def __post_init__(self):
        if self.h_out < 1 or self.w_out < 1:
            raise ValueError("empty output: input smaller than kernel")
        if self.tile_h < 1:
            raise ValueError(f"tile_h={self.tile_h} must be >= 1")
        if self.c % self.tile_c or (self.tile_c % LANES
                                    and self.tile_c != self.c):
            raise ValueError(
                f"tile_c={self.tile_c} must divide C={self.c} and be a "
                f"multiple of {LANES} lanes or the whole C")
        if self.stride > 1 and self.tile_c > LANES:
            raise ValueError(
                f"tile_c={self.tile_c}: strided loads take at most "
                f"{LANES} lanes")
        if self.n % self.tile_n:
            raise ValueError(f"tile_n={self.tile_n} must divide "
                             f"N={self.n}")
        # one strip covering every output row is the largest there is
        if self.tile_h > self.h_out:
            object.__setattr__(self, "tile_h", self.h_out)
        if self.g_tiles > 1 and self.strip_rows < self.carry_rows:
            raise ValueError(f"tile_h={self.tile_h} reads fewer fresh rows "
                             f"than the {self.carry_rows} carried")

    @classmethod
    def build(cls, x_shape, k: int, *, stride: int = 1,
              dtype_bytes: int = 4, tile_h: int | None = None,
              tile_c: int | None = None, tile_n: int | None = None,
              vmem_budget: int = KERNEL_VMEM_BUDGET) -> "DepthwisePlan":
        """Plan for the padded input ``x_shape`` ``(N, H, W, C)``.
        Defaults: one image, 128-lane channel tiles (the whole C when it
        is not a multiple of 128) and the tallest strip that divides the
        output rows and fits ``vmem_budget``.  A strided conv over more
        than one lane tile of channels, but not a whole number of them,
        runs on whole tiles: ``c`` is then rounded up (the kernel pads
        zero channels and drops them)."""
        n, h, w, c = x_shape
        dtype_bytes = resolve_dtype_bytes(dtype_bytes)
        if stride > 1 and c > LANES and c % LANES:
            c = -(-c // LANES) * LANES
        if tile_c is None:
            tile_c = LANES if c % LANES == 0 else c

        def plan(th: int) -> "DepthwisePlan":
            return cls(n=n, h=h, w=w, c=c, k=k, stride=stride,
                       dtype_bytes=dtype_bytes, tile_h=th, tile_c=tile_c,
                       tile_n=tile_n or 1, vmem_budget=vmem_budget)

        if tile_h is None:
            h_out = (h - k) // stride + 1
            fits = [d for d in _divisors(h_out)
                    if (d * stride >= k - stride or d == h_out)
                    and plan(d).vmem_resident_bytes <= vmem_budget]
            tile_h = max(fits) if fits else h_out
        return plan(tile_h)

    # -- geometry ----------------------------------------------------------

    @property
    def h_out(self) -> int:
        return (self.h - self.k) // self.stride + 1

    @property
    def w_out(self) -> int:
        return (self.w - self.k) // self.stride + 1

    @property
    def carry_rows(self) -> int:
        """Input rows a strip shares with its predecessor."""
        return max(self.k - self.stride, 0)

    @property
    def strip_rows(self) -> int:
        """Fresh input rows of one strip."""
        return self.stride * self.tile_h

    @property
    def g_tiles(self) -> int:
        return math.ceil(self.h_out / self.tile_h)

    @property
    def main_rows(self) -> int:
        """Input rows after the head, in whole strips."""
        return self.g_tiles * self.strip_rows

    @property
    def grid(self) -> tuple[int, int, int]:
        """(image tiles, channel tiles, strips) — strips innermost, so
        the carry is valid within one (images, channels) sweep."""
        return (self.n // self.tile_n, self.c // self.tile_c, self.g_tiles)

    @property
    def main_block(self) -> tuple[int, int, int, int]:
        return (self.tile_n, self.strip_rows, self.w, self.tile_c)

    @property
    def head_block(self) -> tuple[int, int, int, int]:
        return (self.tile_n, self.carry_rows, self.w, self.tile_c)

    @property
    def out_block(self) -> tuple[int, int, int, int]:
        return (self.tile_n, self.tile_h, self.w_out, self.tile_c)

    @property
    def padded_output_shape(self) -> tuple[int, int, int, int]:
        return (self.n, self.g_tiles * self.tile_h, self.w_out, self.c)

    @property
    def vmem_resident_bytes(self) -> int:
        """Modeled VMEM of one grid step, every buffer padded to the
        (sublane, lane) tile: the double-buffered strip, head rows,
        weight taps, bias row and output block; the carry scratch (only
        with more than one strip); and the row temporaries of the taps
        (loaded window row, product, f32 accumulator, epilogue)."""
        db = self.dtype_bytes
        total = (2 * vmem_tile_bytes(self.main_block, db)
                 + 2 * vmem_tile_bytes((self.k * self.k, self.tile_c), db)
                 + 2 * vmem_tile_bytes((1, self.tile_c), db)
                 + 2 * vmem_tile_bytes(self.out_block, db)
                 + 4 * vmem_tile_bytes((self.w_out, self.tile_c), 4))
        if self.carry_rows:
            total += 2 * vmem_tile_bytes(self.head_block, db)
            if self.g_tiles > 1:
                total += vmem_tile_bytes(self.head_block, db)
        return total

    # -- arithmetic and traffic -------------------------------------------

    @property
    def flops(self) -> int:
        return 2 * self.n * self.h_out * self.w_out * self.c * self.k ** 2

    def hbm_bytes(self) -> dict:
        """HBM bytes of the schedule: every input row read once (the
        carry re-reads nothing), the taps and bias once per (image tile,
        channel tile) sweep, the output written once."""
        db = self.dtype_bytes
        in_bytes = (self.n * (self.carry_rows + self.main_rows) * self.w
                    * self.c * db)
        w_bytes = (self.k * self.k + 1) * self.c * db * self.grid[0]
        out_bytes = self.n * self.h_out * self.w_out * self.c * db
        return dict(input=in_bytes, weights=w_bytes, output=out_bytes,
                    total=in_bytes + w_bytes + out_bytes)

    @property
    def model_step_time_s(self) -> float:
        """What the tuner ranks by: the HBM time of the schedule (the
        VPU's K*K multiply-adds per output take far less), the first
        block's copy that nothing overlaps, and the fixed cost of every
        grid step."""
        first = (vmem_tile_bytes(self.main_block, self.dtype_bytes)
                 / HBM_BW)
        return (self.hbm_bytes()["total"] / HBM_BW + first
                + math.prod(self.grid) * GRID_STEP_S)


# ---------------------------------------------------------------------------
# 1D plan (depthwise causal conv — Mamba / RG-LRU temporal mixing)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Conv1dPlan:
    """Plan for the depthwise causal conv1d kernel: chunks of ``tile_l``
    timesteps with a ``K-1`` carry, channel axis tiled for the VPU lanes."""

    b: int
    length: int
    d: int
    k: int
    dtype_bytes: int = 4
    tile_l: int = 512
    tile_d: int = 1024

    @classmethod
    def build(cls, x_shape, w_shape, *, dtype_bytes: int = 4,
              tile_l: int | None = None,
              tile_d: int | None = None) -> "Conv1dPlan":
        b, length, d = x_shape
        k, _ = w_shape
        dtype_bytes = resolve_dtype_bytes(dtype_bytes)
        if tile_l is None:
            tile_l = min(length, 512)
        if tile_d is None:
            tile_d = min(d, 1024 if d % 128 == 0 else d)
        return cls(b=b, length=length, d=d, k=k, dtype_bytes=dtype_bytes,
                   tile_l=tile_l, tile_d=tile_d)

    @property
    def g_tiles(self) -> int:
        return math.ceil(self.length / self.tile_l)

    @property
    def d_tiles(self) -> int:
        return math.ceil(self.d / self.tile_d)

    @property
    def length_padded(self) -> int:
        return self.g_tiles * self.tile_l

    @property
    def grid(self) -> tuple[int, int, int]:
        """(B, channel tiles, chunks) — chunks innermost so the carry is
        valid within one (batch, channel) sweep."""
        return (self.b, self.d_tiles, self.g_tiles)

    @property
    def padded_input_shape(self) -> tuple[int, int, int]:
        return (self.b, self.length_padded, self.d)

    @property
    def in_block(self) -> tuple[int, int, int]:
        return (1, self.tile_l, self.tile_d)

    @property
    def w_block(self) -> tuple[int, int]:
        return (self.k, self.tile_d)

    @property
    def carry_shape(self) -> tuple[int, int]:
        return (max(self.k - 1, 1), self.tile_d)

    @property
    def flops(self) -> int:
        return 2 * self.b * self.length * self.d * self.k

    def hbm_bytes(self, mode: str = "3dtrim") -> dict:
        db = self.dtype_bytes
        if mode == "3dtrim":
            halo = 0
        elif mode == "trim":
            halo = self.b * self.d * (self.g_tiles - 1) * (self.k - 1)
        else:
            raise ValueError(f"unknown mode {mode!r}")
        in_bytes = (self.b * self.length_padded * self.d + halo) * db
        w_bytes = self.k * self.d * db * self.b * self.g_tiles
        out_bytes = self.b * self.length * self.d * db
        return dict(input=in_bytes, weights=w_bytes, output=out_bytes,
                    total=in_bytes + w_bytes + out_bytes)

    def arithmetic_intensity(self, mode: str = "3dtrim") -> float:
        return self.flops / max(self.hbm_bytes(mode)["total"], 1)
