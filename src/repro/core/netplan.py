"""Whole-network execution + evaluation planning (DESIGN.md §7).

Everything below `ConvPlan` models ONE convolution at a time.  The
paper's headline claim, however, is a *network-level* number: 3D-TrIM
delivers up to 3.37x more operations per memory access (Ops/MAcc) than
TrIM on full CNN topologies like VGG-16 and AlexNet (arXiv:2502.18983
SV; the per-layer accounting follows TrIM's analytical-modelling
companion paper, arXiv:2408.01254).  This module chains the per-layer
plans into that network view:

* :class:`LayerStep` — one conv layer of a topology: its
  :class:`~repro.core.conv_plan.ConvPlan` (or
  :class:`~repro.core.conv_shard.ShardedConvPlan` when the network is
  sharded over a device mesh) plus the *inter-layer* decisions that the
  single-layer plan cannot see: whether the ifmap arrives from on-chip
  residency instead of HBM, whether the (pooled) ofmap stays on-chip
  for the next layer, and the pooling factor folded into the epilogue.

* :class:`NetworkPlan` — the chained topology.  It decides inter-layer
  residency (``residency="auto"``: an ofmap stays on-chip iff the
  pooled activation fits the residency budget; ``"never"`` /
  ``"always"`` override), aggregates whole-network HBM traffic, MACs
  and the paper's Ops/MAcc metric for ``mode="trim"`` vs ``"3dtrim"``,
  and carries the cross-device halo terms of sharded plans as a
  separate wire-traffic column.

* :func:`network_layers` / :func:`scale_layers` / :func:`infer_pools`
  — topology helpers shared with the execution path
  (``models/layers.py cnn_*_from_layers``) and the benchmarks.

Counting conventions (DESIGN.md §7, tying back to §1): the Ops/MAcc
denominator counts **ifmap reads + weight reads** in elements
(accesses = bytes / dtype_bytes); output writes and psums are excluded,
exactly as in the paper's metric.  One OP = one multiply or add
(MAC = 2 OPs).  Residency and pooling folding therefore change the
HBM *traffic* totals and the input side of Ops/MAcc, never the OPs.

`autotune.tune_network` tunes every layer of a topology in one sweep so
the execution engine (``examples/cnn_inference.py --net vgg16``) runs
the whole forward pass on tuned, packed plans.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

from dataclasses import replace as _dc_replace

from repro.core import roofline
from repro.core.conv_plan import KERNEL_VMEM_LIMIT, ConvPlan
from repro.core.conv_shard import ShardedConvPlan
from repro.core.model import (ConvLayer, GraphNode, alexnet_layers,
                              mobilenet_layers, resnet18_graph, unet_graph,
                              vgg16_layers)

NETWORKS = {"vgg16": vgg16_layers, "alexnet": alexnet_layers,
            "mobilenet": mobilenet_layers}

# DAG topologies: name -> builder returning list[GraphNode] (topological
# order).  Linear chains from NETWORKS are also valid NetworkGraph inputs
# via linear_graph_nodes().
GRAPHS = {"resnet18": resnet18_graph, "unet": unet_graph}

# Default budget for keeping an inter-layer activation on chip: a quarter
# of the kernels' scoped-VMEM limit (8 MiB) — the rest is committed to
# the consumer's working set.
RESIDENCY_BUDGET = KERNEL_VMEM_LIMIT // 4


def network_layers(network) -> list[ConvLayer]:
    """Resolve a topology: a name from :data:`NETWORKS` ("vgg16",
    "alexnet", "mobilenet") or an explicit ``list[ConvLayer]`` passed
    through unchanged."""
    if isinstance(network, str):
        if network not in NETWORKS:
            raise ValueError(
                f"unknown network {network!r}; have {sorted(NETWORKS)}")
        return NETWORKS[network]()
    return list(network)


def scale_layers(layers, scale: int) -> list[ConvLayer]:
    """Shrink a topology's channel counts by ``scale`` (spatial dims and
    kernels unchanged) — the reduced configuration the CPU examples
    execute while the accounting uses the full-scale plans.  The first
    layer's input channels (the image) are kept; grouped layers keep
    ``groups == channels`` (depthwise stays depthwise)."""
    if scale <= 1:
        return list(layers)
    out: list[ConvLayer] = []
    prev_out: int | None = None
    for l in layers:
        cin = l.in_channels if prev_out is None else prev_out
        cout = max(1, l.out_channels // scale)
        if l.groups == l.in_channels and l.groups > 1:
            groups = cin                 # depthwise stays depthwise
        else:
            groups = math.gcd(l.groups, cin)   # must still divide cin
        if groups > 1:
            cout = -(-cout // groups) * groups  # round up to a multiple
        out.append(ConvLayer(name=l.name, ifmap=l.ifmap, in_channels=cin,
                             out_channels=cout, kernel=l.kernel,
                             stride=l.stride, padding=l.padding,
                             groups=groups))
        prev_out = cout
    return out


class PoolInferenceError(ValueError):
    """Spatial dims at a chain boundary cannot be explained by a
    plausible max pool — only a strided/dilated conv join (or, for
    ``reason="upsample"``, an explicit upsampling node) could produce
    them.  Subclasses ``ValueError`` so existing chainability handling
    keeps working; carries the boundary as structured fields so callers
    (and the unet wiring this was found on) can report *which* edge is
    miswired instead of silently planning a different network."""

    #: largest pool stride / window-overhang infer_pools will accept as a
    #: genuine pool rather than a disguised strided join.  Every real
    #: topology boundary in the zoo is within (VGG 2x2/s2, AlexNet
    #: 3x3/s2, sub-2x 3x3/s1, ResNet/U-Net 2x2/s2).
    MAX_STRIDE = 4
    MAX_OVERHANG = 2

    def __init__(self, msg: str, *, producer: str, consumer: str,
                 out_size: int, in_size: int, reason: str,
                 stride: int | None = None, window: int | None = None):
        super().__init__(msg)
        self.producer = producer
        self.consumer = consumer
        self.out_size = out_size
        self.in_size = in_size
        self.reason = reason
        self.stride = stride
        self.window = window


def pool_between(layer: ConvLayer, nxt: ConvLayer) -> tuple[int, int]:
    """Pooling ``(stride, window)`` between two consecutive conv layers,
    inferred from the topology's spatial dims: ``stride = out // next_in``
    and ``window = out - stride * (next_in - 1)`` — this recovers VGG's
    2x2/s2 and AlexNet's overlapping 3x3/s2 max pooling exactly.
    ``(1, 1)`` means no pooling at this boundary; a sub-2x boundary
    (e.g. 5 -> 3) resolves to a genuine stride-1 overlapping pool.

    Raises :class:`PoolInferenceError` when the dims admit no plausible
    pool: a growing boundary (``out < in`` — only an upsampling join
    explains it) or one whose inferred stride/window exceed the
    :attr:`PoolInferenceError.MAX_STRIDE` /
    ``stride + MAX_OVERHANG`` plausibility caps (only a strided or
    dilated join explains it).  Any ``o >= i`` pair *can* be written as
    ``(s, w) = (o // i, o - s*(i-1))``, so without the caps a miswired
    edge would silently plan a wildly subsampling "pool" that the
    topology never contained."""
    o, i = layer.out_size, nxt.ifmap
    if o == i:
        return 1, 1
    if o < i:
        raise PoolInferenceError(
            f"layer {layer.name} ofmap {o} smaller than {nxt.name} "
            f"ifmap {i}: not a chainable topology (only an upsampling "
            f"join can explain these dims — add an explicit 'upsample' "
            f"GraphNode)",
            producer=layer.name, consumer=nxt.name, out_size=o, in_size=i,
            reason="upsample")
    s = o // i
    w = o - s * (i - 1)
    if s > PoolInferenceError.MAX_STRIDE \
            or w > s + PoolInferenceError.MAX_OVERHANG:
        raise PoolInferenceError(
            f"boundary {layer.name}({o}) -> {nxt.name}({i}) implies a "
            f"{w}x{w}/s{s} pool — beyond the plausibility caps "
            f"(stride <= {PoolInferenceError.MAX_STRIDE}, window <= "
            f"stride + {PoolInferenceError.MAX_OVERHANG}); only a "
            f"strided or dilated conv join can explain these dims",
            producer=layer.name, consumer=nxt.name, out_size=o, in_size=i,
            reason="strided-join", stride=s, window=w)
    assert pooled_out_size(o, s, w) == i, (o, i, s, w)
    return s, w


def infer_pools(layers) -> list[tuple[int, int]]:
    """Per-layer pooling ``(stride, window)`` list (last layer: (1, 1))."""
    out = [pool_between(a, b) for a, b in zip(layers, layers[1:])]
    return out + [(1, 1)]


def pooled_out_size(h_out: int, stride: int, window: int) -> int:
    """Spatial size after the (stride, window) max pool — the single
    place the pooled-size rule lives (LayerStep.out_size and the
    residency decision in NetworkPlan.build both read it).  ``(1, 1)``
    is the no-pool identity; ``(1, window > 1)`` is a genuine stride-1
    overlapping pool (a sub-2x boundary like 5 -> 3 via 3x3/s1)."""
    if stride == 1 and window == 1:
        return h_out
    return (h_out - window) // stride + 1


def layer_kernel_problem(layer: ConvLayer, *, n: int = 1):
    """The conv problem ``ops.conv2d`` actually executes for one
    topology layer: ``(x_shape, pad, w_shape, padding)`` with
    ``x_shape`` the kernel-seen input (the ``padding`` mode's pre-pad
    folded in), ``pad`` the residual symmetric padding (0) and
    ``padding`` the ``ops.conv2d`` argument (``"same"`` for
    ``layer.padding > 0``, else ``"valid"``).

    This is the single place the layer -> executed-problem mapping
    lives: ``autotune.tune_network`` keys its records over these shapes,
    ``NetworkPlan(use_autotune_cache=True)`` looks them up over the same
    shapes, and ``models/layers.py cnn_*_from_layers`` run the same
    ``padding`` mode — so records can never be written under one key and
    read under another.

    Raises ``ValueError`` when the layer's symmetric paper padding is
    not reproduced by that mode (executed output size would differ from
    ``layer.out_size``) — the execution engine supports
    'same'-equivalent or zero padding, and anything else must fail
    loudly instead of silently running a different network.
    """
    from repro.kernels.ops import kernel_input_shape
    padding = "same" if layer.padding else "valid"
    x_shape, pad = kernel_input_shape(
        (n, layer.ifmap, layer.ifmap, layer.in_channels), layer.kernel,
        layer.stride, padding)
    out = (x_shape[1] + 2 * pad - layer.kernel) // layer.stride + 1
    if out != layer.out_size:
        raise ValueError(
            f"layer {layer.name}: padding={layer.padding} is not "
            f"{padding!r}-equivalent (executed output {out} != planned "
            f"{layer.out_size}); the execution engine runs 'same' or "
            f"zero padding only")
    w_shape = (layer.kernel, layer.kernel,
               layer.in_channels // layer.groups, layer.out_channels)
    return x_shape, pad, w_shape, padding


# ---------------------------------------------------------------------------
# DAG topology helpers
# ---------------------------------------------------------------------------

def linear_graph_nodes(network) -> list[GraphNode]:
    """A linear topology (name or ``list[ConvLayer]``) as graph nodes:
    one conv node per layer, chained in order, with the inter-layer max
    pools folded onto each conv as its epilogue — exactly the view
    :class:`NetworkPlan` takes, so ``NetworkGraph.build`` on these nodes
    reduces to the chain plan (tested as a hypothesis invariant)."""
    layers = network_layers(network)
    pools = infer_pools(layers)
    nodes: list[GraphNode] = []
    prev: str | None = None
    for l, (ps, pw) in zip(layers, pools):
        nodes.append(GraphNode(l.name, "conv", (prev,) if prev else (),
                               l, pool=ps, pool_window=pw))
        prev = l.name
    return nodes


def graph_nodes(graph) -> list[GraphNode]:
    """Resolve a DAG topology: a name from :data:`GRAPHS` ("resnet18",
    "unet"), a name from :data:`NETWORKS` or an explicit
    ``list[ConvLayer]`` (converted by :func:`linear_graph_nodes`), or an
    explicit ``list[GraphNode]`` passed through unchanged."""
    if isinstance(graph, str):
        if graph in GRAPHS:
            return GRAPHS[graph]()
        if graph in NETWORKS:
            return linear_graph_nodes(graph)
        raise ValueError(f"unknown network {graph!r}; have "
                         f"{sorted(GRAPHS) + sorted(NETWORKS)}")
    nodes = list(graph)
    if nodes and isinstance(nodes[0], ConvLayer):
        return linear_graph_nodes(nodes)
    return nodes


def scale_graph(graph, scale: int) -> list[GraphNode]:
    """Channel-shrink a DAG topology by ``scale`` (spatial dims and
    kernels unchanged) — the graph analogue of :func:`scale_layers`.
    Channels are recomputed in topological order (concat sums its
    inputs, joins pass through), so add/concat joins stay consistent
    after scaling."""
    nodes = graph_nodes(graph)
    if scale <= 1:
        return nodes
    ch: dict[str, int] = {}
    out: list[GraphNode] = []
    for nd in nodes:
        if nd.op == "conv":
            l = nd.layer
            cin = ch[nd.inputs[0]] if nd.inputs else l.in_channels
            cout = max(1, l.out_channels // scale)
            if l.groups == l.in_channels and l.groups > 1:
                groups = cin                 # depthwise stays depthwise
            else:
                groups = math.gcd(l.groups, cin)
            if groups > 1:
                cout = -(-cout // groups) * groups
            out.append(_dc_replace(nd, layer=_dc_replace(
                l, in_channels=cin, out_channels=cout, groups=groups)))
            ch[nd.name] = cout
        else:
            out.append(nd)
            if nd.op == "concat":
                ch[nd.name] = sum(ch[s] for s in nd.inputs)
            else:
                ch[nd.name] = ch[nd.inputs[0]]
    return out


# ---------------------------------------------------------------------------
# One chained layer
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class LayerStep:
    """One conv layer of a :class:`NetworkPlan`.

    ``plan`` is the single-layer :class:`ConvPlan` (or
    :class:`ShardedConvPlan`); the step adds the inter-layer decisions:

    * ``resident_in`` — the ifmap arrives from the previous layer's
      on-chip residency: its HBM input bytes (including any
      ``mode="trim"`` halo re-fetch) are not billed.
    * ``resident_out`` — the (pooled) ofmap stays on-chip as the next
      layer's ifmap: its HBM output bytes are not billed.
    * ``pool`` / ``pool_window`` — max-pooling folded into the epilogue;
      with ``fold_pooling`` the output bytes billed are the *pooled*
      activation (the elements the network actually keeps), else the
      full ofmap the plan writes.
    """

    index: int
    name: str
    layer: ConvLayer
    plan: ConvPlan
    pool: int = 1
    pool_window: int = 1
    resident_in: bool = False
    resident_out: bool = False
    fold_pooling: bool = True

    @property
    def out_size(self) -> int:
        """Spatial size of the (pooled) activation this step hands on."""
        return pooled_out_size(self.plan.h_out, self.pool,
                               self.pool_window)

    @property
    def out_elements(self) -> int:
        return self.plan.n * self.out_size ** 2 * self.plan.cout

    @property
    def out_bytes(self) -> int:
        """HBM bytes of the activation this step writes (0 if resident)."""
        if self.resident_out:
            return 0
        if self.fold_pooling:
            return self.out_elements * self.plan.dtype_bytes
        return self.plan.hbm_bytes()["output"]

    @property
    def macs(self) -> int:
        return self.plan.macs

    @property
    def ops(self) -> int:
        return 2 * self.macs

    @property
    def halo_bytes(self) -> int:
        """Cross-device halo-exchange bytes (sharded plans only) — wire
        traffic, kept out of the HBM Ops/MAcc denominator."""
        if isinstance(self.plan, ShardedConvPlan):
            return self.plan.halo_bytes_oneway
        return 0

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """This step's HBM byte terms under the network's residency and
        pooling decisions.  ``mode`` follows :meth:`ConvPlan.hbm_bytes`
        (``None`` accounts the plan's own dataflow)."""
        t = self.plan.hbm_bytes(mode)
        inp = 0 if self.resident_in else t["input"]
        out = self.out_bytes
        return dict(input=inp, weights=t["weights"], output=out,
                    halo=self.halo_bytes,
                    total=inp + t["weights"] + out)

    def accesses(self, mode: str | None = None) -> int:
        """Paper-metric memory accesses: ifmap + weight reads, in
        elements (DESIGN.md §1/§7 — output writes and psums excluded)."""
        t = self.hbm_bytes(mode)
        return (t["input"] + t["weights"]) // self.plan.dtype_bytes

    def ops_per_macc(self, mode: str | None = None) -> float:
        """Operations per memory access of this layer (paper metric)."""
        return self.ops / max(self.accesses(mode), 1)


# ---------------------------------------------------------------------------
# The chained network
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class NetworkPlan:
    """Per-layer ConvPlans chained across a full CNN topology.

    Build with :meth:`build`; every aggregate below is a pure function
    of the per-layer plans plus the residency/pooling decisions, so the
    network numbers printed by ``benchmarks/paper_eval.py`` are exactly
    the sums of the plans the kernels execute.

    Example (doctested by the README quickstart)::

        plan = NetworkPlan.build("vgg16")
        plan.ops_per_macc("3dtrim") / plan.ops_per_macc("trim")  # > 1
    """

    name: str
    steps: tuple
    residency: str = "auto"

    @classmethod
    def build(cls, network="vgg16", *, n: int = 1,
              dtype_bytes: int | None = None,
              dataflow: str = "carry", residency: str = "auto",
              residency_budget: int = RESIDENCY_BUDGET,
              fold_pooling: bool = True,
              batch_shards: int = 1, spatial_shards: int = 1,
              use_autotune_cache: bool = False, dtype: str = "float32",
              backend: str | None = None) -> "NetworkPlan":
        """Plan a whole topology.

        ``network`` is a name ("vgg16" | "alexnet" | "mobilenet") or an
        explicit ``list[ConvLayer]``.  ``residency`` decides inter-layer
        on-chip chaining: ``"auto"`` keeps an ofmap resident iff its
        pooled activation fits ``residency_budget``; ``"never"`` spills
        every boundary (whole-network traffic then reduces exactly to
        the sum of the per-layer plans when ``fold_pooling=False``);
        ``"always"`` forces every interior boundary resident.  With
        ``batch_shards``/``spatial_shards`` every layer is planned as a
        :class:`ShardedConvPlan` and the cross-device halo bytes ride
        along as a separate wire-traffic term.  With
        ``use_autotune_cache=True`` each layer's tile/dataflow knobs are
        filled from the persisted autotune records
        (:func:`repro.core.autotune.tune_network` writes them).
        """
        if residency not in ("auto", "never", "always"):
            raise ValueError(f"residency={residency!r} must be "
                             "'auto', 'never' or 'always'")
        if dtype_bytes is None:
            dtype_bytes = roofline.dtype_width(dtype)
        layers = network_layers(network)
        if not layers:
            raise ValueError("empty topology")
        for a, b in zip(layers, layers[1:]):
            if a.out_channels != b.in_channels:
                raise ValueError(
                    f"layer {a.name} ofmap channels {a.out_channels} != "
                    f"{b.name} ifmap channels {b.in_channels}")
        pools = infer_pools(layers)
        plans = [_plan_layer(layer, n=n, dtype_bytes=dtype_bytes,
                             dataflow=dataflow,
                             use_autotune_cache=use_autotune_cache,
                             dtype=dtype, backend=backend,
                             batch_shards=batch_shards,
                             spatial_shards=spatial_shards)
                 for layer in layers]

        steps = []
        last = len(layers) - 1
        for i, (layer, plan, (ps, pw)) in enumerate(
                zip(layers, plans, pools)):
            pooled_bytes = (n * pooled_out_size(plan.h_out, ps, pw) ** 2
                            * plan.cout * dtype_bytes)
            if i == last:
                keep = False            # the result leaves the accelerator
            elif residency == "never":
                keep = False
            elif residency == "always":
                keep = True
            else:
                keep = pooled_bytes <= residency_budget
            steps.append(LayerStep(
                index=i, name=layer.name, layer=layer, plan=plan,
                pool=ps, pool_window=pw,
                resident_in=bool(steps) and steps[-1].resident_out,
                resident_out=keep, fold_pooling=fold_pooling))
        nm = network if isinstance(network, str) else "custom"
        return cls(name=nm, steps=tuple(steps), residency=residency)

    # -- aggregates --------------------------------------------------------

    @property
    def n_layers(self) -> int:
        return len(self.steps)

    @property
    def macs(self) -> int:
        return sum(s.macs for s in self.steps)

    @property
    def ops(self) -> int:
        return 2 * self.macs

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Whole-network HBM byte terms (input / weights / output /
        total) plus the cross-device ``halo`` wire term, under the
        plan's residency and pooling decisions.  With
        ``residency="never"`` and ``fold_pooling=False`` the totals
        reduce exactly to the sum of the per-layer
        ``ConvPlan.hbm_bytes()`` (tested)."""
        tot = dict(input=0, weights=0, output=0, halo=0, total=0)
        for s in self.steps:
            t = s.hbm_bytes(mode)
            for k in tot:
                tot[k] += t.get(k, 0)
        return tot

    def accesses(self, mode: str | None = None) -> int:
        """Whole-network paper-metric accesses (ifmap + weight reads)."""
        return sum(s.accesses(mode) for s in self.steps)

    def ops_per_macc(self, mode: str | None = None) -> float:
        """The paper's network-level Ops/MAcc (arXiv:2502.18983 SV):
        total operations over total external reads."""
        return self.ops / max(self.accesses(mode), 1)

    def compare(self) -> dict:
        """The trim-vs-3dtrim comparison this subsystem exists for:
        per-layer and whole-network Ops/MAcc in both accounting modes
        with the 3dtrim/trim improvement ratio."""
        rows = []
        for s in self.steps:
            a3, at = s.ops_per_macc("3dtrim"), s.ops_per_macc("trim")
            rows.append(dict(
                layer=s.name, label=s.layer.label(), macs=s.macs,
                g_tiles=s.plan.g_tiles, dataflow=s.plan.dataflow,
                resident_in=s.resident_in, resident_out=s.resident_out,
                pool=s.pool,
                ops_per_macc_3dtrim=a3, ops_per_macc_trim=at,
                improvement=a3 / max(at, 1e-12)))
        n3, nt = self.ops_per_macc("3dtrim"), self.ops_per_macc("trim")
        return dict(
            network=self.name, residency=self.residency,
            layers=rows, macs=self.macs, ops=self.ops,
            ops_per_macc_3dtrim=n3, ops_per_macc_trim=nt,
            improvement=n3 / max(nt, 1e-12))

    def arch_compare(self, hw_a=None, hw_b=None) -> dict:
        """The paper's own §V network comparison: whole-network Ops/MAcc
        of the 3D-TrIM ASIC configuration vs the TrIM configuration,
        using the Fig. 6 architectural access model
        (:func:`repro.core.model.layer_accesses` — shadow registers,
        filter passes, kernel tiling and slice counts included).  This
        is the accounting that reproduces the claimed "up to 3.37x"
        per-layer improvements; :meth:`compare` is the TPU execution
        engine's strip-level image of the same tradeoff."""
        return arch_compare_steps(self.name, self.steps, hw_a, hw_b)

    def as_rows(self, mode: str | None = None) -> list[dict]:
        """Flat per-layer dict rows (the ``--json`` artifact shape)."""
        rows = []
        for s in self.steps:
            t = s.hbm_bytes(mode)
            rows.append(dict(
                layer=s.name, label=s.layer.label(),
                mode=mode or s.plan.traffic_mode,
                dataflow=s.plan.dataflow, macs=s.macs,
                hbm_input=t["input"], hbm_weights=t["weights"],
                hbm_output=t["output"], halo=t["halo"],
                hbm_total=t["total"],
                accesses=s.accesses(mode),
                ops_per_macc=s.ops_per_macc(mode),
                resident_in=s.resident_in,
                resident_out=s.resident_out, pool=s.pool))
        return rows


def arch_compare_steps(name: str, steps, hw_a=None, hw_b=None) -> dict:
    """The paper's §V architectural network comparison over any iterable
    of conv steps (``LayerStep``-shaped: ``.name`` + ``.layer``) — shared
    by :meth:`NetworkPlan.arch_compare` (linear chains) and
    :meth:`NetworkGraph.arch_compare` (DAGs, conv nodes only: joins do
    no MACs and the Fig. 6 access model has no term for them)."""
    from repro.core.model import TRIM, TRIM_3D, layer_accesses
    hw_a = TRIM_3D if hw_a is None else hw_a
    hw_b = TRIM if hw_b is None else hw_b
    steps = tuple(steps)
    rows, tot = [], {hw_a.name: 0, hw_b.name: 0}
    for s in steps:
        a = layer_accesses(s.layer, hw_a)
        b = layer_accesses(s.layer, hw_b)
        tot[hw_a.name] += a.total
        tot[hw_b.name] += b.total
        rows.append(dict(
            layer=s.name, label=s.layer.label(), ops=s.layer.ops,
            accesses={hw_a.name: a.total, hw_b.name: b.total},
            ops_per_macc={hw_a.name: a.ops_per_access,
                          hw_b.name: b.ops_per_access},
            ops_per_macc_per_slice={
                hw_a.name: a.ops_per_access_per_slice,
                hw_b.name: b.ops_per_access_per_slice},
            improvement=a.ops_per_access_per_slice
            / b.ops_per_access_per_slice))
    ops = sum(s.layer.ops for s in steps)
    net_a = ops / max(tot[hw_a.name], 1)
    net_b = ops / max(tot[hw_b.name], 1)
    return dict(
        network=name, layers=rows, ops=ops, accesses=tot,
        ops_per_macc={hw_a.name: net_a, hw_b.name: net_b},
        ops_per_macc_per_slice={hw_a.name: net_a / hw_a.slices,
                                hw_b.name: net_b / hw_b.slices},
        improvement=(net_a / hw_a.slices) / (net_b / hw_b.slices))


def _plan_layer(layer: ConvLayer, *, n: int, dtype_bytes: int,
                dataflow: str, use_autotune_cache: bool, dtype: str,
                backend: str | None, batch_shards: int = 1,
                spatial_shards: int = 1):
    """The single-layer plan for one topology layer — the one place
    :meth:`NetworkPlan.build` and :meth:`NetworkGraph.build` construct
    plans, so a graph's conv nodes are planned exactly like the chain's
    layers (the linear-reduction invariant depends on this)."""
    knobs = dict(tile_h=None, tile_cout=None, dataflow=dataflow)
    if use_autotune_cache:
        rec = _cached_knobs(layer, n=n, dtype=dtype, backend=backend,
                            batch_shards=batch_shards,
                            spatial_shards=spatial_shards)
        if rec is not None:
            knobs = dict(tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
                         dataflow=rec["dataflow"])
    x_shape = (n, layer.ifmap, layer.ifmap, layer.in_channels)
    w_shape = (layer.kernel, layer.kernel,
               layer.in_channels // layer.groups, layer.out_channels)
    build_kw = dict(stride=layer.stride, pad=layer.padding,
                    groups=layer.groups, dtype_bytes=dtype_bytes, **knobs)
    if batch_shards > 1 or spatial_shards > 1:
        return ShardedConvPlan.build(x_shape, w_shape,
                                     batch_shards=batch_shards,
                                     spatial_shards=spatial_shards,
                                     **build_kw)
    return ConvPlan.build(x_shape, w_shape, **build_kw)


# ---------------------------------------------------------------------------
# DAG network plans
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class EdgeState:
    """One producer -> consumer edge of a :class:`NetworkGraph` with its
    residency verdict.

    ``bytes`` is the (pooled) activation the edge carries — the quantity
    the residency pass charges against the VMEM budget and the HBM bytes
    a join consumer re-reads when the edge is not resident (a *conv*
    consumer's re-fetch is billed through its own plan, which adds the
    ``mode="trim"`` halo re-reads on top).  ``boundaries`` is the
    half-open interval of topological boundaries ``[producer_pos,
    consumer_pos)`` the tensor occupies while resident — a skip edge
    spans many boundaries, which is exactly how residual liveness turns
    the per-boundary budget check into an interval-overlap problem."""

    producer: str
    consumer: str
    bytes: int
    resident: bool
    boundaries: tuple[int, int]

    @property
    def state(self) -> str:
        return "resident" if self.resident else "refetch"

    @property
    def span(self) -> int:
        return self.boundaries[1] - self.boundaries[0]

    @property
    def refetch_bytes(self) -> int:
        return 0 if self.resident else self.bytes


@dataclass(frozen=True)
class JoinStep:
    """One non-conv node (pool / add / concat / upsample) of a
    :class:`NetworkGraph`.  Joins perform no MACs — their whole cost is
    activation traffic: the in-edges they must re-read from HBM plus the
    output they spill.  The interface mirrors :class:`LayerStep` where
    the network aggregates need it (``macs`` / ``ops`` / ``hbm_bytes`` /
    ``accesses`` / ``halo_bytes``); ``plan`` is ``None`` so the roofline
    treats joins as memory-only work."""

    index: int
    name: str
    op: str
    n: int
    out_size: int
    channels: int
    dtype_bytes: int
    in_bytes: tuple
    resident_ins: tuple
    resident_out: bool

    plan = None          # no ConvPlan: memory-only node

    @property
    def resident_in(self) -> bool:
        """True iff every in-edge arrives from VMEM residency."""
        return all(self.resident_ins)

    @property
    def out_elements(self) -> int:
        return self.n * self.out_size ** 2 * self.channels

    @property
    def out_bytes(self) -> int:
        if self.resident_out:
            return 0
        return self.out_elements * self.dtype_bytes

    @property
    def macs(self) -> int:
        return 0

    @property
    def ops(self) -> int:
        return 0

    @property
    def halo_bytes(self) -> int:
        return 0

    def hbm_bytes(self, mode: str | None = None) -> dict:
        inp = sum(b for b, r in zip(self.in_bytes, self.resident_ins)
                  if not r)
        out = self.out_bytes
        return dict(input=inp, weights=0, output=out, halo=0,
                    total=inp + out)

    def accesses(self, mode: str | None = None) -> int:
        """Activation re-reads in elements — joins add to the Ops/MAcc
        denominator (a re-fetched skip ifmap is an ifmap read) without
        adding MACs, which is the honest cost of a spilled skip edge."""
        return self.hbm_bytes(mode)["input"] // self.dtype_bytes

    def ops_per_macc(self, mode: str | None = None) -> float:
        return 0.0

    def label(self) -> str:
        return f"[{self.op} {self.out_size}x{self.out_size}" \
               f"x{self.channels}]"


@dataclass(frozen=True)
class NetworkGraph:
    """A DAG topology planned for residency — the generalization of
    :class:`NetworkPlan` from chains to graphs (ResNet residual blocks,
    U-Net encoder-decoders).

    The residency pass decides **per edge** whether a producer's
    activation stays VMEM-resident until that consumer or is re-fetched
    from HBM.  A tensor with a resident edge to consumer position ``j``
    occupies every topological boundary in ``[producer, j)``, so skip
    edges extend liveness intervals and the half-VMEM budget check
    becomes interval overlap: at every boundary the resident tensors'
    bytes must sum within ``residency_budget``.  ``"auto"`` admits edges
    greedily in consumer order; ``"never"`` / ``"always"`` override.  A
    tensor is *spilled* (written to HBM) iff any of its consumer edges
    is non-resident or it is a network output.

    On a linear chain every edge spans exactly one boundary, each
    boundary holds one tensor, and the pass reduces exactly to
    :class:`NetworkPlan`'s per-boundary ``pooled_bytes <= budget`` rule
    (hypothesis-tested invariant).

    Aggregation reuses the chain machinery: conv nodes become
    :class:`LayerStep`s (same plans, built by the same helper), joins
    become :class:`JoinStep`s, and ``compare()`` / ``arch_compare()``
    report whole-network HBM bytes and Ops/MAcc in both accounting
    modes."""

    name: str
    nodes: tuple
    steps: tuple
    edges: tuple
    residency: str
    residency_budget: int

    @classmethod
    def build(cls, graph="resnet18", *, n: int = 1,
              dtype_bytes: int | None = None, dataflow: str = "carry",
              residency: str = "auto",
              residency_budget: int = RESIDENCY_BUDGET,
              fold_pooling: bool = True,
              use_autotune_cache: bool = False, dtype: str = "float32",
              backend: str | None = None) -> "NetworkGraph":
        """Plan a DAG topology.  ``graph`` is a name from
        :data:`GRAPHS` ("resnet18" | "unet"), a linear name from
        :data:`NETWORKS`, an explicit ``list[GraphNode]`` in topological
        order, or a ``list[ConvLayer]`` (converted to a chain graph).
        Graphs are planned single-device; shard grids stay on
        :class:`NetworkPlan`."""
        if residency not in ("auto", "never", "always"):
            raise ValueError(f"residency={residency!r} must be "
                             "'auto', 'never' or 'always'")
        if dtype_bytes is None:
            dtype_bytes = roofline.dtype_width(dtype)
        nodes = graph_nodes(graph)
        if not nodes:
            raise ValueError("empty topology")

        # -- validate topology, compute per-node (size, channels) ------
        pos: dict[str, int] = {}
        out_size: dict[str, int] = {}
        channels: dict[str, int] = {}
        sources = 0
        for i, nd in enumerate(nodes):
            if nd.name in pos:
                raise ValueError(f"duplicate node name {nd.name!r}")
            for src in nd.inputs:
                if src not in pos:
                    raise ValueError(
                        f"node {nd.name}: input {src!r} is not an "
                        f"earlier node — nodes must be topological")
            if nd.op == "conv":
                l = nd.layer
                if len(nd.inputs) > 1:
                    raise ValueError(
                        f"conv node {nd.name}: exactly one input")
                if nd.inputs:
                    src = nd.inputs[0]
                    if (out_size[src] != l.ifmap
                            or channels[src] != l.in_channels):
                        raise ValueError(
                            f"node {nd.name}: expects {l.ifmap}^2"
                            f"x{l.in_channels}, producer {src} hands "
                            f"{out_size[src]}^2x{channels[src]}")
                else:
                    sources += 1
                sz = pooled_out_size(l.out_size, nd.pool, nd.pool_window)
                chn = l.out_channels
            elif nd.op == "pool":
                (src,) = nd.inputs
                if nd.pool_window > out_size[src]:
                    raise ValueError(
                        f"pool {nd.name}: window {nd.pool_window} > "
                        f"input size {out_size[src]}")
                sz = pooled_out_size(out_size[src], nd.pool,
                                     nd.pool_window)
                chn = channels[src]
            elif nd.op == "upsample":
                (src,) = nd.inputs
                sz = out_size[src] * nd.scale
                chn = channels[src]
            else:                        # add / concat
                if len(nd.inputs) < 2:
                    raise ValueError(
                        f"{nd.op} node {nd.name}: needs >= 2 inputs")
                sizes = {out_size[s] for s in nd.inputs}
                if len(sizes) != 1:
                    raise ValueError(
                        f"node {nd.name}: mismatched spatial dims "
                        f"{sorted(sizes)}")
                sz = sizes.pop()
                chs = [channels[s] for s in nd.inputs]
                if nd.op == "add" and len(set(chs)) != 1:
                    raise ValueError(
                        f"add node {nd.name}: mismatched channels {chs}")
                chn = chs[0] if nd.op == "add" else sum(chs)
            pos[nd.name] = i
            out_size[nd.name] = sz
            channels[nd.name] = chn
        if sources != 1:
            raise ValueError(
                f"graph needs exactly one source conv node "
                f"(empty inputs), got {sources}")

        # -- per-conv plans (same helper the chain build uses) ---------
        plans = {nd.name: _plan_layer(nd.layer, n=n,
                                      dtype_bytes=dtype_bytes,
                                      dataflow=dataflow,
                                      use_autotune_cache=use_autotune_cache,
                                      dtype=dtype, backend=backend)
                 for nd in nodes if nd.op == "conv"}
        tensor_bytes = {nm: n * out_size[nm] ** 2 * channels[nm]
                        * dtype_bytes for nm in pos}

        # -- residency: greedy interval packing over boundaries --------
        edge_list: list[tuple[str, str]] = []
        seen = set()
        for nd in nodes:
            for src in nd.inputs:
                if (src, nd.name) not in seen:
                    seen.add((src, nd.name))
                    edge_list.append((src, nd.name))
        occ = [0] * max(len(nodes) - 1, 0)
        upto: dict[str, int] = {}
        res: dict[tuple[str, str], bool] = {}
        for prod, cons in sorted(edge_list,
                                 key=lambda e: (pos[e[1]], pos[e[0]])):
            b = tensor_bytes[prod]
            start = upto.get(prod, pos[prod])
            span = range(start, pos[cons])
            if residency == "never":
                keep = False
            elif residency == "always":
                keep = True
            else:
                keep = all(occ[k] + b <= residency_budget for k in span)
            if keep:
                if residency != "always":
                    for k in span:
                        occ[k] += b
                upto[prod] = max(start, pos[cons])
            res[(prod, cons)] = keep

        # -- steps ------------------------------------------------------
        consumers: dict[str, list[str]] = {nm: [] for nm in pos}
        for prod, cons in edge_list:
            consumers[prod].append(cons)
        steps: list = []
        for i, nd in enumerate(nodes):
            outs = consumers[nd.name]
            spilled = (not outs) or any(not res[(nd.name, c)]
                                        for c in outs)
            if nd.op == "conv":
                r_in = bool(nd.inputs) and res[(nd.inputs[0], nd.name)]
                steps.append(LayerStep(
                    index=i, name=nd.name, layer=nd.layer,
                    plan=plans[nd.name], pool=nd.pool,
                    pool_window=nd.pool_window, resident_in=r_in,
                    resident_out=not spilled, fold_pooling=fold_pooling))
            else:
                steps.append(JoinStep(
                    index=i, name=nd.name, op=nd.op, n=n,
                    out_size=out_size[nd.name],
                    channels=channels[nd.name], dtype_bytes=dtype_bytes,
                    in_bytes=tuple(tensor_bytes[s] for s in nd.inputs),
                    resident_ins=tuple(res[(s, nd.name)]
                                       for s in nd.inputs),
                    resident_out=not spilled))
        edges = tuple(EdgeState(
            producer=prod, consumer=cons, bytes=tensor_bytes[prod],
            resident=res[(prod, cons)],
            boundaries=(pos[prod], pos[cons]))
            for prod, cons in edge_list)
        nm = graph if isinstance(graph, str) else "custom"
        return cls(name=nm, nodes=tuple(nodes), steps=tuple(steps),
                   edges=edges, residency=residency,
                   residency_budget=residency_budget)

    # -- aggregates --------------------------------------------------------

    @property
    def n_nodes(self) -> int:
        return len(self.nodes)

    @property
    def conv_steps(self) -> tuple:
        return tuple(s for s in self.steps if isinstance(s, LayerStep))

    @property
    def macs(self) -> int:
        return sum(s.macs for s in self.steps)

    @property
    def ops(self) -> int:
        return 2 * self.macs

    @property
    def spilled_edge_bytes(self) -> int:
        """HBM bytes of the edges that re-fetch (reporting; the billed
        traffic rides inside the consumer steps)."""
        return sum(e.refetch_bytes for e in self.edges)

    def boundary_occupancy(self) -> list[int]:
        """Resident bytes held across each topological boundary — the
        liveness-interval view of the residency decisions (every entry
        is <= ``residency_budget`` under ``"auto"``; tested)."""
        occ = [0] * max(len(self.nodes) - 1, 0)
        pos = {nd.name: i for i, nd in enumerate(self.nodes)}
        upto: dict[str, int] = {}
        for e in sorted(self.edges,
                        key=lambda e: (pos[e.consumer], pos[e.producer])):
            if not e.resident:
                continue
            start = upto.get(e.producer, e.boundaries[0])
            for k in range(start, e.boundaries[1]):
                occ[k] += e.bytes
            upto[e.producer] = max(start, e.boundaries[1])
        return occ

    def hbm_bytes(self, mode: str | None = None) -> dict:
        """Whole-network HBM byte terms under the graph's residency
        decisions.  With ``residency="never"`` and
        ``fold_pooling=False`` the conv terms reduce exactly to the sum
        of per-layer ``ConvPlan.hbm_bytes()`` plus the joins' activation
        traffic (tested)."""
        tot = dict(input=0, weights=0, output=0, halo=0, total=0)
        for s in self.steps:
            t = s.hbm_bytes(mode)
            for k in tot:
                tot[k] += t.get(k, 0)
        return tot

    def accesses(self, mode: str | None = None) -> int:
        """Whole-network paper-metric accesses: ifmap + weight reads,
        including join re-reads of spilled activations."""
        return sum(s.accesses(mode) for s in self.steps)

    def ops_per_macc(self, mode: str | None = None) -> float:
        return self.ops / max(self.accesses(mode), 1)

    def compare(self) -> dict:
        """trim-vs-3dtrim Ops/MAcc over the whole DAG: per-conv rows
        plus the network totals (join traffic in the denominator) and
        the edge-residency summary."""
        rows = []
        for s in self.conv_steps:
            a3, at = s.ops_per_macc("3dtrim"), s.ops_per_macc("trim")
            rows.append(dict(
                layer=s.name, label=s.layer.label(), macs=s.macs,
                g_tiles=s.plan.g_tiles, dataflow=s.plan.dataflow,
                resident_in=s.resident_in, resident_out=s.resident_out,
                pool=s.pool,
                ops_per_macc_3dtrim=a3, ops_per_macc_trim=at,
                improvement=a3 / max(at, 1e-12)))
        n3, nt = self.ops_per_macc("3dtrim"), self.ops_per_macc("trim")
        n_res = sum(1 for e in self.edges if e.resident)
        return dict(
            network=self.name, residency=self.residency,
            layers=rows, macs=self.macs, ops=self.ops,
            n_edges=len(self.edges), n_resident_edges=n_res,
            spilled_edge_bytes=self.spilled_edge_bytes,
            ops_per_macc_3dtrim=n3, ops_per_macc_trim=nt,
            improvement=n3 / max(nt, 1e-12))

    def arch_compare(self, hw_a=None, hw_b=None) -> dict:
        """The paper's §V architectural comparison over the graph's conv
        nodes (joins carry no MACs and no Fig. 6 term)."""
        return arch_compare_steps(self.name, self.conv_steps, hw_a, hw_b)

    def as_rows(self, mode: str | None = None) -> list[dict]:
        """Flat per-node dict rows (the ``--json`` artifact shape);
        join nodes report their op label and pure activation traffic."""
        rows = []
        for s in self.steps:
            t = s.hbm_bytes(mode)
            conv = isinstance(s, LayerStep)
            rows.append(dict(
                layer=s.name,
                label=s.layer.label() if conv else s.label(),
                mode=(mode or s.plan.traffic_mode) if conv else "-",
                dataflow=s.plan.dataflow if conv else "-",
                macs=s.macs,
                hbm_input=t["input"], hbm_weights=t["weights"],
                hbm_output=t["output"], halo=t["halo"],
                hbm_total=t["total"],
                accesses=s.accesses(mode),
                ops_per_macc=s.ops_per_macc(mode),
                resident_in=s.resident_in,
                resident_out=s.resident_out,
                pool=s.pool if conv else 1))
        return rows

    def edge_rows(self) -> list[dict]:
        """Per-edge residency rows (the ``--json`` "edge" kind)."""
        return [dict(producer=e.producer, consumer=e.consumer,
                     bytes=e.bytes, state=e.state, span=e.span,
                     boundaries=list(e.boundaries)) for e in self.edges]


def _cached_knobs(layer: ConvLayer, *, n: int, dtype: str,
                  backend: str | None, batch_shards: int,
                  spatial_shards: int) -> dict | None:
    """The autotune record for one topology layer, looked up under the
    same kernel-seen key ``ops.conv2d`` uses — derived by
    :func:`layer_kernel_problem`, the shared mapping ``tune_network``
    writes records with (the sharded namespace when a shard grid is
    given)."""
    from repro.core import autotune
    from repro.kernels.ops import MAX_NATIVE_K
    if layer.kernel > MAX_NATIVE_K:
        return None                      # kernel-tiled path: no cache
    try:
        x_shape, pad, w_shape, _ = layer_kernel_problem(layer, n=n)
    except ValueError:
        return None          # not executable as planned: nothing cached
    if batch_shards > 1 or spatial_shards > 1:
        return autotune.sharded_knobs_for(
            x_shape, w_shape, batch_shards=batch_shards,
            spatial_shards=spatial_shards, stride=layer.stride, pad=pad,
            groups=layer.groups, dtype=dtype, backend=backend)
    return autotune.knobs_for(x_shape, w_shape, stride=layer.stride,
                              pad=pad, groups=layer.groups, dtype=dtype,
                              backend=backend)
