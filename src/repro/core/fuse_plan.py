"""Residency-group fusion planning (DESIGN.md §8).

``NetworkPlan`` (§7) *decides* which inter-layer boundaries keep their
pooled ofmap resident in VMEM, but the execution engine still ran every
layer as its own ``pallas_call`` — the ofmap round-tripped through HBM
and the measured trim-vs-3dtrim traffic ratio sat at ~1.0009x while the
model claimed ~3.3x.  This module turns those residency decisions into
an executable partition:

* :class:`FusedGroupPlan` — partitions a network topology into
  *residency groups*: conv→[pool]→conv chains whose every interior
  boundary the ``NetworkPlan`` marked resident AND that the fused
  megakernel (``kernels/trim_conv2d_fused.py``) can execute in one
  pipelined ``pallas_call``.  The partition is a shortest-path dynamic
  program over executed HBM bytes, so the chosen grouping is the
  cheapest legal one — and since the all-singletons partition is always
  a candidate, ``executed_hbm_bytes() <= never_hbm_bytes()`` holds
  structurally.  Groups of depth 1 fall back to the ordinary per-layer
  path, so ``max_depth=1`` reduces *exactly* to per-layer execution and
  its byte accounting.

* :class:`FusedStage` / :class:`FusedGroup` — the static per-stage
  strip geometry the kernel executes.  Stage *i+1*'s K-1 halo rows
  constrain how many rows stage *i* must produce ahead: the same
  carry/halo machinery :class:`~repro.core.conv_plan.ConvPlan` owns for
  one layer, chained backwards through the group.  For a strip of
  ``strip_rows`` pooled output rows of the *last* stage, each stage's
  input/conv/pool row ranges are affine in the strip index ``g``
  (``start + g*step``, ``rows`` wide), derived by the backward
  recursion in :func:`_strip_geometry`.

* Traffic pricing — a fused group moves only the stage-0 input windows
  (the halo overlap is billed), each stage's weights streamed tap-by-tap
  from HBM once per strip, and the final pooled output.  Every interior
  activation — including interior *pooling* — stays in VMEM and moves
  zero HBM bytes.  The per-layer baseline is billed as the per-layer
  engine actually executes: the conv writes its full ofmap, a separate
  pooling op re-reads it and writes the pooled result (``NetworkPlan``'s
  ``fold_pooling=True`` models the paper's ASIC, not this engine).

The megakernel keeps activations resident but *streams* weights: each
stage's weight tensor stays in HBM (``pl.ANY``) and one (Cin, Cout)
tap slice at a time is DMA'd into a VMEM scratch buffer — so a group's
VMEM working set is the stage-0 window + the per-stage fp32
accumulators + one tap slice per stage, never the full weight chain.
That is what makes 512-channel VGG-16 tails fusable at all, and it is
why the feasibility check below counts windows and accumulators but
only a single tap per stage.  The working set is compared against
``FUSED_VMEM_BUDGET`` (the fused kernel owns the core while it runs;
the residency *decision* still uses ``netplan.RESIDENCY_BUDGET``).

The group-level tuning knob (fuse depth x strip height) lives in
``core/autotune.py`` under the ``conv2d_fused:`` key namespace; the
plan consults it via ``use_autotune_cache=True``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

from repro.core import roofline
from repro.core.conv_plan import KERNEL_VMEM_LIMIT
from repro.core.netplan import (NetworkPlan, RESIDENCY_BUDGET, graph_nodes,
                                infer_pools, layer_kernel_problem,
                                layer_route, network_layers, pool_between,
                                pooled_out_size)

# The megakernel's working-set budget: half of the kernels' scoped-VMEM
# limit (16 MiB).  Its resident model is not yet checked against the
# chip's compiler (the megakernels do not compile on the chip yet).
FUSED_VMEM_BUDGET = KERNEL_VMEM_LIMIT // 2


# ---------------------------------------------------------------------------
# Static per-stage description + strip geometry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FusedStage:
    """One conv[+pool] stage of a fused group, with its strip geometry.

    All row ranges are affine in the strip index ``g``: a strip covers
    rows ``[start + g*step, start + g*step + rows)`` in the *global*
    (unpadded) coordinates of that tensor.  ``in_*`` ranges address the
    stage's input (== the previous stage's pooled output), ``conv_*``
    the conv output, ``pool_*`` the pooled output.  Rows outside the
    valid extent (``h_in`` / ``h_conv`` / ``h_pool``) are zeros — the
    kernel's post-pool mask makes them so, and they double as the next
    stage's H-padding.
    """

    name: str
    # problem geometry (square spatial dims)
    h_in: int
    w_in: int
    cin: int
    cout: int
    kernel: int
    stride: int
    pad_lo: int          # H/W pad: the layer's symmetric padding
    pad_hi: int
    h_conv: int          # valid conv output rows (== layer.out_size)
    w_conv: int
    pool_stride: int     # (1, 1) == no pooling
    pool_window: int
    h_pool: int
    w_pool: int
    # strip geometry (affine in the strip index g)
    in_start: int
    in_step: int
    in_rows: int
    conv_start: int
    conv_step: int
    conv_rows: int
    pool_start: int
    pool_step: int
    pool_rows: int

    @property
    def weight_shape(self) -> tuple[int, int, int, int]:
        return (self.kernel, self.kernel, self.cin, self.cout)

    def weight_bytes(self, dtype_bytes: int) -> int:
        k = self.kernel
        return k * k * self.cin * self.cout * dtype_bytes

    def tap_bytes(self, dtype_bytes: int) -> int:
        """One streamed (Cin, Cout) weight tap slice."""
        return self.cin * self.cout * dtype_bytes

    @property
    def pooled(self) -> bool:
        return self.pool_stride > 1 or self.pool_window > 1

    @property
    def signature(self) -> str:
        """Stage signature for the ``conv2d_fused:`` autotune key."""
        return (f"h{self.h_in}c{self.cin}f{self.cout}k{self.kernel}"
                f"s{self.stride}p{self.pad_lo}.{self.pad_hi}"
                f"q{self.pool_stride}x{self.pool_window}")


def _stage_problems(layers, pools):
    """Per-layer (layer, pad_lo, pad_hi, h_conv, ps, pw, h_pool) tuples,
    validating each layer's padding is executable.  The pads are the
    layer's symmetric ``(p, p)``, as the per-layer pre-pad
    (``ops.conv_pads``) applies them: the fused kernel's in-kernel
    padding has to reproduce it bit-for-bit."""
    probs = []
    for layer, (ps, pw) in zip(layers, pools):
        layer_kernel_problem(layer)     # raises on pads it cannot run
        lo = hi = layer.padding
        h_conv = layer.out_size
        probs.append((layer, lo, hi, h_conv, ps, pw,
                      pooled_out_size(h_conv, ps, pw)))
    return probs


def _strip_geometry(probs, strip_rows):
    """Backward recursion: from ``strip_rows`` pooled rows of the last
    stage, derive every stage's affine (start, step, rows) ranges.

    A pooled range needs conv rows ``[a*ps, a*ps + (c-1)*ps + pw)``; a
    conv range needs padded-input rows ``[a*s, a*s + (c-1)*s + K)``;
    un-padding subtracts the top pad.  The resulting stage-0
    input range is what one grid step fetches from HBM.
    """
    stages = []
    a, b, c = 0, strip_rows, strip_rows          # last stage pooled range
    for layer, lo, hi, h_conv, ps, pw, h_pool in reversed(probs):
        pa, pb, pc = a, b, c                      # pooled-out range
        a, b, c = a * ps, b * ps, (c - 1) * ps + pw          # conv-out
        ca, cb, cc = a, b, c
        s, k = layer.stride, layer.kernel
        a, b, c = a * s - lo, b * s, (c - 1) * s + k         # input
        stages.append(FusedStage(
            name=layer.name, h_in=layer.ifmap, w_in=layer.ifmap,
            cin=layer.in_channels, cout=layer.out_channels,
            kernel=k, stride=s, pad_lo=lo, pad_hi=hi,
            h_conv=h_conv, w_conv=h_conv,
            pool_stride=ps, pool_window=pw,
            h_pool=h_pool, w_pool=h_pool,
            in_start=a, in_step=b, in_rows=c,
            conv_start=ca, conv_step=cb, conv_rows=cc,
            pool_start=pa, pool_step=pb, pool_rows=pc))
    stages.reverse()
    return tuple(stages)


# ---------------------------------------------------------------------------
# A fused residency group
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FusedGroup:
    """One residency group: ``depth`` consecutive layers executed as a
    single megakernel (depth >= 2) or via the per-layer path (depth 1,
    where the strip geometry is unused)."""

    start: int                          # index of the first layer
    stages: tuple[FusedStage, ...]
    n: int = 1
    strip_rows: int = 1                 # pooled rows of the LAST stage/strip
    dtype_bytes: int = 4

    @property
    def depth(self) -> int:
        return len(self.stages)

    @property
    def fused(self) -> bool:
        return self.depth >= 2

    @property
    def last(self) -> FusedStage:
        return self.stages[-1]

    @property
    def n_strips(self) -> int:
        return math.ceil(self.last.h_pool / self.strip_rows)

    # -- stage-0 HBM layout ------------------------------------------------

    @property
    def extra_top(self) -> int:
        """Zero rows prepended to the HBM input so strip 0's (negative-
        starting) window begins at element row 0."""
        return max(0, -self.stages[0].in_start)

    @property
    def pad_bottom(self) -> int:
        """Zero rows appended so the last strip's window is in bounds."""
        s0 = self.stages[0]
        need = s0.in_start + (self.n_strips - 1) * s0.in_step + s0.in_rows
        return max(0, need - s0.h_in)

    def in_row_offset(self, g: int) -> int:
        """Element row offset of strip ``g``'s window in the padded HBM
        input (non-negative by construction)."""
        return self.stages[0].in_start + self.extra_top \
            + g * self.stages[0].in_step

    @property
    def padded_input_shape(self) -> tuple[int, int, int, int]:
        s0 = self.stages[0]
        return (self.n, self.extra_top + s0.h_in + self.pad_bottom,
                s0.w_in, s0.cin)

    @property
    def padded_output_shape(self) -> tuple[int, int, int, int]:
        lt = self.last
        return (self.n, self.n_strips * self.strip_rows, lt.w_pool, lt.cout)

    @property
    def out_shape(self) -> tuple[int, int, int, int]:
        lt = self.last
        return (self.n, lt.h_pool, lt.w_pool, lt.cout)

    # -- arithmetic / working set / traffic --------------------------------

    @property
    def macs(self) -> int:
        return sum(self.n * st.h_conv * st.w_conv * st.cout
                   * st.kernel * st.kernel * st.cin for st in self.stages)

    @property
    def flops(self) -> int:
        return 2 * self.macs

    @property
    def vmem_resident_bytes(self) -> int:
        """Resident set of one grid step: the stage-0 input window, each
        stage's fp32 conv accumulator (interior activations live inside
        this footprint), plus one streamed weight tap slice and the bias
        per stage.  Full weight tensors are NOT resident — the kernel
        DMAs them tap-by-tap from HBM."""
        db = self.dtype_bytes
        s0 = self.stages[0]
        window = s0.in_rows * s0.w_in * s0.cin * db
        taps = sum(st.tap_bytes(db) + st.cout * db for st in self.stages)
        accs = sum(st.conv_rows * st.w_conv * st.cout * 4
                   for st in self.stages)
        return window + taps + accs

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Executed HBM bytes of the megakernel's schedule: overlapping
        stage-0 windows (the halo overlap is billed in full), weights
        streamed once per strip, one pooled output write.  Interior
        activations and pooling move zero bytes.  ``mode`` is accepted
        for interface parity with ``ConvPlan`` (the schedule is fixed)."""
        db = self.dtype_bytes
        s0, lt = self.stages[0], self.last
        in_bytes = self.n * self.n_strips * s0.in_rows * s0.w_in \
            * s0.cin * db
        w_bytes = sum(st.weight_bytes(db) for st in self.stages) \
            * self.n_strips
        out_bytes = self.n * lt.h_pool * lt.w_pool * lt.cout * db
        return dict(input=in_bytes, weights=w_bytes, output=out_bytes,
                    total=in_bytes + w_bytes + out_bytes)

    def arithmetic_intensity(self, mode: str | None = None) -> float:
        return self.flops / max(self.hbm_bytes(mode)["total"], 1)

    @property
    def signature(self) -> str:
        return "-".join(st.signature for st in self.stages)

    def as_dict(self) -> dict:
        return dict(start=self.start, depth=self.depth, fused=self.fused,
                    layers=[st.name for st in self.stages],
                    strip_rows=self.strip_rows, n_strips=self.n_strips,
                    vmem_resident_bytes=self.vmem_resident_bytes,
                    flops=self.flops,
                    hbm_total=self.hbm_bytes()["total"])


def build_group(layers, start, *, n=1, strip_rows=1, dtype_bytes=4,
                pools=None):
    """A :class:`FusedGroup` over ``layers`` — the constructor used by
    the plan and by tests that need a hand-rolled group.  ``pools``
    defaults to :func:`infer_pools` over ``layers`` *as given* (pass the
    whole-network pools to keep a trailing group's final pool)."""
    if pools is None:
        pools = infer_pools(list(layers))
    probs = _stage_problems(list(layers), list(pools))
    stages = _strip_geometry(probs, strip_rows)
    return FusedGroup(start=start, stages=stages, n=n,
                      strip_rows=strip_rows, dtype_bytes=dtype_bytes)


# ---------------------------------------------------------------------------
# Whole-network partition
# ---------------------------------------------------------------------------

def _layer_eligible(layer, dtype: str) -> bool:
    """Can this layer run *inside* a fused megakernel at all?  Only an
    ``mxu`` route (``ops.conv_route``) without groups: the stages run
    the taps as native MXU matmuls, and a conv whose taps fold into the
    lanes (DESIGN.md §13) runs per layer as a 1x1 over its folded input,
    whose one dot sums in another order than the megakernel's K*K tap
    dots and would break the bitwise guarantee."""
    if layer.groups != 1:
        return False
    if layer.stride > 1 and layer.out_size == 1:
        # A strided stage collapsing to a single output row fuses as a
        # strided interior-row gather whose dot lowers with a different
        # reduction association than the per-layer kernel (observed
        # one-ULP drift), breaking the bitwise guarantee — and a
        # one-strip output gains nothing from strip fusion anyway.
        return False
    try:
        return layer_route(layer, dtype=dtype).kind == "mxu"
    except ValueError:
        return False


def _strip_candidates(h_pool_last: int):
    """Candidate strip heights: powers of two up to the full pooled
    height (the full-height strip is always included)."""
    t, cands = 1, []
    while t < h_pool_last:
        cands.append(t)
        t *= 2
    cands.append(h_pool_last)
    return cands


@dataclass(frozen=True)
class FusedGroupPlan:
    """Partition of a network into residency groups, with executed-byte
    accounting for the fused schedule vs the per-layer baseline."""

    groups: tuple[FusedGroup, ...]
    n: int
    dtype_bytes: int
    residency: str
    vmem_budget: int
    layer_exec_bytes: tuple   # per-layer executed byte dicts (see below)

    @classmethod
    def build(cls, network, *, n: int = 1, dtype_bytes: int | None = None,
              residency: str = "auto",
              residency_budget: int = RESIDENCY_BUDGET,
              vmem_budget: int = FUSED_VMEM_BUDGET,
              max_depth: int | None = None,
              strip_rows: int | None = None,
              use_autotune_cache: bool = False,
              dtype: str = "float32", backend: str | None = None,
              dataflow: str = "carry") -> "FusedGroupPlan":
        """Partition ``network`` (name or layer list) into residency
        groups.

        A range ``[i, j]`` may form one fused group iff every interior
        boundary's pooled ofmap is marked resident by the
        :class:`NetworkPlan` ``residency`` policy, every layer is
        kernel-eligible, and some strip height keeps the working set
        under ``vmem_budget``.  Among all legal partitions the build
        picks the one with minimal executed HBM bytes (shortest-path
        DP); ``max_depth`` caps group depth (``max_depth=1`` ==
        per-layer execution); ``strip_rows`` forces the strip height
        instead of tuning/modelling it.
        """
        if dtype_bytes is None:
            dtype_bytes = roofline.dtype_width(dtype)
        layers = list(network_layers(network))
        pools = list(infer_pools(layers))
        nplan = NetworkPlan.build(layers, n=n, dtype_bytes=dtype_bytes,
                                  dataflow=dataflow, residency=residency,
                                  residency_budget=residency_budget)
        exec_bytes = cls._per_layer_exec_bytes(
            layers, pools, n=n, dtype_bytes=dtype_bytes, dataflow=dataflow)

        cap = len(layers) if max_depth is None else max(1, max_depth)

        def group_cost(i, j):
            """Best fused group over layers[i..j] and its bytes, or
            (None, inf) when the range can't fuse."""
            if j > i:
                if not all(_layer_eligible(layers[k], dtype)
                           for k in range(i, j + 1)):
                    return None, math.inf
                if not all(nplan.steps[k].resident_out
                           for k in range(i, j)):
                    return None, math.inf
                g = cls._tune_group(
                    layers, pools, i, j - i + 1, n=n,
                    dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
                    strip_rows=strip_rows,
                    use_autotune_cache=use_autotune_cache,
                    dtype=dtype, backend=backend)
                if g is None:
                    return None, math.inf
                return g, g.hbm_bytes()["total"]
            g = build_group(layers[i:i + 1], i, n=n, strip_rows=1,
                            dtype_bytes=dtype_bytes, pools=pools[i:i + 1])
            return g, exec_bytes[i]["total"]

        # shortest path over layer boundaries: best[j] = cheapest bytes
        # for layers[0..j-1]; the all-singletons path is always legal,
        # so the optimum never exceeds the per-layer baseline.
        best = [0.0] + [math.inf] * len(layers)
        choice: list = [None] * (len(layers) + 1)
        for j in range(1, len(layers) + 1):
            for i in range(max(0, j - cap), j):
                g, cost = group_cost(i, j - 1)
                if g is not None and best[i] + cost < best[j]:
                    best[j] = best[i] + cost
                    choice[j] = g
        groups: list[FusedGroup] = []
        j = len(layers)
        while j > 0:
            g = choice[j]
            groups.append(g)
            j = g.start
        groups.reverse()
        return cls(groups=tuple(groups), n=n, dtype_bytes=dtype_bytes,
                   residency=residency, vmem_budget=vmem_budget,
                   layer_exec_bytes=exec_bytes)

    @staticmethod
    def _per_layer_exec_bytes(layers, pools, *, n, dtype_bytes, dataflow):
        """What the per-layer engine actually moves for each layer: the
        conv's ``residency="never"`` bytes with the FULL ofmap written
        (``fold_pooling=False``), plus the separate pooling op's
        read-back of that ofmap and write of the pooled result."""
        never = NetworkPlan.build(list(layers), n=n,
                                  dtype_bytes=dtype_bytes,
                                  dataflow=dataflow, residency="never",
                                  fold_pooling=False)
        out = []
        for st, (ps, pw) in zip(never.steps, pools):
            b = dict(st.hbm_bytes())
            if ps > 1 or pw > 1:
                layer = st.layer
                db = dtype_bytes
                full = n * layer.out_size ** 2 * layer.out_channels * db
                pooled = n * pooled_out_size(layer.out_size, ps, pw) ** 2 \
                    * layer.out_channels * db
                b["pool"] = full + pooled
                b["total"] += b["pool"]
            else:
                b["pool"] = 0
            out.append(b)
        return tuple(out)

    @classmethod
    def _tune_group(cls, layers, pools, start, depth, *, n, dtype_bytes,
                    vmem_budget, strip_rows, use_autotune_cache, dtype,
                    backend):
        """Best VMEM-feasible group over ``layers[start:start+depth]``,
        or ``None`` when no strip height fits the budget.  Consults the
        ``conv2d_fused:`` cache first, then the byte model."""
        sub = layers[start:start + depth]
        subpools = pools[start:start + depth]

        def make(t):
            return build_group(sub, start, n=n, strip_rows=t,
                               dtype_bytes=dtype_bytes, pools=subpools)

        if strip_rows is not None:
            g = make(strip_rows)
            return g if g.vmem_resident_bytes <= vmem_budget else None

        probe = make(1)
        if use_autotune_cache:
            from repro.core import autotune
            rec = autotune.fused_knobs_for(
                probe.signature, n=n, dtype=dtype, backend=backend)
            if rec is not None:
                g = make(rec["strip_rows"])
                if g.vmem_resident_bytes <= vmem_budget:
                    return g
        best = None
        for t in _strip_candidates(probe.last.h_pool):
            g = make(t)
            if g.vmem_resident_bytes > vmem_budget:
                continue
            if best is None or g.hbm_bytes()["total"] \
                    < best.hbm_bytes()["total"]:
                best = g
        return best

    # -- accounting --------------------------------------------------------

    @property
    def depth(self) -> int:
        return max(g.depth for g in self.groups)

    @property
    def flops(self) -> int:
        return sum(g.flops for g in self.groups)

    @property
    def macs(self) -> int:
        return sum(g.macs for g in self.groups)

    @property
    def vmem_resident_bytes(self) -> int:
        return max(g.vmem_resident_bytes for g in self.groups)

    def executed_hbm_bytes(self) -> dict:
        """HBM bytes the fused execution actually moves: megakernel
        accounting for fused groups, per-layer-engine accounting
        (separate pooling op included) for depth-1 groups."""
        tot = dict(input=0, weights=0, output=0, pool=0, total=0)
        for g in self.groups:
            b = g.hbm_bytes() if g.fused else self.layer_exec_bytes[g.start]
            for k in tot:
                tot[k] += b.get(k, 0)
        return tot

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Alias so the plan duck-types ``ConvPlan`` for the roofline."""
        return self.executed_hbm_bytes()

    def never_hbm_bytes(self) -> int:
        """The per-layer baseline: every boundary spills to HBM and
        every pool is a separate read-modify-write op."""
        return sum(b["total"] for b in self.layer_exec_bytes)

    def executed_ratio(self) -> float:
        """Per-layer executed bytes over fused executed bytes — the
        measured counterpart of the modeled trim-vs-3dtrim ratio."""
        return self.never_hbm_bytes() \
            / max(self.executed_hbm_bytes()["total"], 1)

    def arithmetic_intensity(self, mode: str | None = None) -> float:
        return self.flops / max(self.executed_hbm_bytes()["total"], 1)

    def as_rows(self) -> list[dict]:
        return [g.as_dict() for g in self.groups]

    def summary(self) -> dict:
        return dict(groups=len(self.groups), max_depth=self.depth,
                    fused_layers=sum(g.depth for g in self.groups
                                     if g.fused),
                    executed_bytes=self.executed_hbm_bytes()["total"],
                    per_layer_bytes=self.never_hbm_bytes(),
                    executed_ratio=self.executed_ratio())


# ---------------------------------------------------------------------------
# DAG segmentation: fusable linear runs between joins
# ---------------------------------------------------------------------------

def graph_segments(nodes) -> list[tuple[tuple[str, ...], tuple]]:
    """Maximal fusable linear runs of a DAG topology, as ``(names,
    layers)`` tuples: the covered node names (conv nodes plus absorbed
    single-consumer pool nodes, in topological order) and the run's
    ``ConvLayer`` chain.

    A run extends from conv to conv only while the intermediate tensor
    has exactly one consumer (joins, skip taps and network outputs end
    runs — their tensor must materialize) and the boundary's pooling is
    exactly re-inferable from the spatial dims by
    :func:`~repro.core.netplan.pool_between` — ``infer_pools``' chain
    convention, so each run IS one of today's linear chains and
    ``FusedGroupPlan`` / ``cnn_apply_from_layers`` apply unchanged.  A
    trailing conv-node epilogue pool is *not* part of the run (the graph
    executor applies it after the run)."""
    nodes = list(nodes)
    by = {nd.name: nd for nd in nodes}
    cons: dict[str, list[str]] = {nd.name: [] for nd in nodes}
    for nd in nodes:
        for s in nd.inputs:
            cons[s].append(nd.name)
    used: set[str] = set()
    segments: list[tuple[tuple[str, ...], tuple]] = []
    for nd in nodes:
        if nd.op != "conv" or nd.name in used:
            continue
        names, layers = [nd.name], [nd.layer]
        used.add(nd.name)
        cur = nd
        while True:
            nxts = cons[cur.name]
            if len(nxts) != 1:
                break
            nxt = by[nxts[0]]
            absorbed: list[str] = []
            if nxt.op == "pool":
                if cur.pool > 1 or cur.pool_window > 1:
                    break        # stacked pools: not dims-recoverable
                pc = cons[nxt.name]
                if len(pc) != 1:
                    break        # pooled tensor has other consumers
                cand = by[pc[0]]
                expected = (nxt.pool, nxt.pool_window)
                absorbed = [nxt.name]
            elif nxt.op == "conv":
                cand = nxt
                expected = (cur.pool, cur.pool_window)
            else:
                break            # add / concat / upsample end the run
            if cand.op != "conv":
                break
            try:
                if pool_between(cur.layer, cand.layer) != expected:
                    break        # dims would re-infer a different pool
            except ValueError:
                break
            names.extend(absorbed)
            names.append(cand.name)
            layers.append(cand.layer)
            used.update(absorbed)
            used.add(cand.name)
            cur = cand
        segments.append((tuple(names), tuple(layers)))
    return segments


@dataclass(frozen=True)
class GraphFusePlan:
    """Fusion partition of a DAG topology: each fusable linear segment
    between joins is planned as today's chain (its own
    :class:`FusedGroupPlan`); joins and skip taps stay un-fused — their
    tensors must materialize, so they bound the segments.

    ``executed_ratio()`` compares segment-sum executed bytes against the
    all-per-layer baseline over the same segments; join traffic is
    identical on both sides of that comparison and is accounted by
    :class:`~repro.core.netplan.NetworkGraph`, not here."""

    name: str
    segments: tuple              # (names, FusedGroupPlan) pairs
    n: int
    dtype_bytes: int
    residency: str

    @classmethod
    def build(cls, graph, *, n: int = 1, dtype_bytes: int | None = None,
              residency: str = "auto",
              residency_budget: int = RESIDENCY_BUDGET,
              vmem_budget: int = FUSED_VMEM_BUDGET,
              max_depth: int | None = None,
              strip_rows: int | None = None,
              use_autotune_cache: bool = False,
              dtype: str = "float32", backend: str | None = None,
              dataflow: str = "carry") -> "GraphFusePlan":
        if dtype_bytes is None:
            dtype_bytes = roofline.dtype_width(dtype)
        nodes = graph_nodes(graph)
        segs = []
        for names, layers in graph_segments(nodes):
            plan = FusedGroupPlan.build(
                list(layers), n=n, dtype_bytes=dtype_bytes,
                residency=residency, residency_budget=residency_budget,
                vmem_budget=vmem_budget, max_depth=max_depth,
                strip_rows=strip_rows,
                use_autotune_cache=use_autotune_cache, dtype=dtype,
                backend=backend, dataflow=dataflow)
            segs.append((names, plan))
        nm = graph if isinstance(graph, str) else "custom"
        return cls(name=nm, segments=tuple(segs), n=n,
                   dtype_bytes=dtype_bytes, residency=residency)

    @property
    def n_segments(self) -> int:
        return len(self.segments)

    @property
    def groups(self) -> tuple[FusedGroup, ...]:
        return tuple(g for _, p in self.segments for g in p.groups)

    @property
    def flops(self) -> int:
        return sum(p.flops for _, p in self.segments)

    @property
    def macs(self) -> int:
        return sum(p.macs for _, p in self.segments)

    @property
    def vmem_resident_bytes(self) -> int:
        return max(p.vmem_resident_bytes for _, p in self.segments)

    def executed_hbm_bytes(self) -> dict:
        tot = dict(input=0, weights=0, output=0, pool=0, total=0)
        for _, p in self.segments:
            b = p.executed_hbm_bytes()
            for k in tot:
                tot[k] += b.get(k, 0)
        return tot

    def hbm_bytes(self, mode: str | None = None) -> dict:
        return self.executed_hbm_bytes()

    def never_hbm_bytes(self) -> int:
        return sum(p.never_hbm_bytes() for _, p in self.segments)

    def executed_ratio(self) -> float:
        return self.never_hbm_bytes() \
            / max(self.executed_hbm_bytes()["total"], 1)

    def as_rows(self) -> list[dict]:
        rows = []
        for names, p in self.segments:
            for g in p.groups:
                d = g.as_dict()
                d["segment"] = list(names)
                rows.append(d)
        return rows

    def summary(self) -> dict:
        return dict(segments=self.n_segments,
                    groups=sum(len(p.groups) for _, p in self.segments),
                    max_depth=max(p.depth for _, p in self.segments),
                    fused_layers=sum(g.depth for g in self.groups
                                     if g.fused),
                    executed_bytes=self.executed_hbm_bytes()["total"],
                    per_layer_bytes=self.never_hbm_bytes(),
                    executed_ratio=self.executed_ratio())
