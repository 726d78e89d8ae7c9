"""Shared transformer layers: norms, RoPE, GQA attention, MLP, MoE.

Every ``*_params`` function returns a tree of ``Param`` declarations with
logical sharding axes; every ``*_apply`` function is pure and consumes the
materialized (or abstract) tree.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import guard
from repro.kernels import ops
from repro.models.base import Param, shard_activation
from repro.models.config import ModelConfig


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_params(cfg: ModelConfig, d: int | None = None) -> dict:
    d = d or cfg.d_model
    p = {"scale": Param((d,), ("act_embed",), init="ones",
                        dtype=jnp.float32)}
    if cfg.norm == "layernorm":
        p["bias"] = Param((d,), ("act_embed",), init="zeros",
                          dtype=jnp.float32)
    return p


def norm_apply(p: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]
    else:
        var = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, L, H, D); positions: (B, L) or (L,)."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (B, L, D/2)
    if angles.ndim == 2:
        angles = angles[None]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (self- or cross-), with optional KV cache
# ---------------------------------------------------------------------------

def attention_params(cfg: ModelConfig) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": Param((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Param((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Param((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Param((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = Param((h, hd), ("heads", "head_dim"), init="zeros")
        p["bk"] = Param((kv, hd), ("kv_heads", "head_dim"), init="zeros")
        p["bv"] = Param((kv, hd), ("kv_heads", "head_dim"), init="zeros")
    return p


def attention_apply(p: dict, x: jax.Array, cfg: ModelConfig, rules: dict, *,
                    positions: jax.Array | None = None,
                    kv_cache: tuple | None = None,
                    cache_len=None,
                    causal: bool = True,
                    window: int | None = None,
                    encoder_out: jax.Array | None = None,
                    is_cross: bool = False,
                    use_rope: bool = True):
    """Returns (y, new_kv_cache).

    Modes:
      * train / prefill:  kv_cache is None -> attends within ``x`` (or to
        ``encoder_out`` for cross-attention); returns fresh (k, v).
      * decode:           kv_cache=(k, v).  Self-attention appends the new
        token at ``cache_len - 1``; cross-attention reads the static cache.
    """
    b, lq, _ = x.shape
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    if is_cross and kv_cache is not None:
        k = v = None                   # static encoder K/V: nothing to project
    else:
        kv_src = encoder_out if encoder_out is not None else x
        k = jnp.einsum("bld,dhk->blhk", kv_src, p["wk"])
        v = jnp.einsum("bld,dhk->blhk", kv_src, p["wv"])
        if cfg.qkv_bias:
            k, v = k + p["bk"], v + p["bv"]
    if use_rope and not is_cross:
        if positions is None:
            positions = jnp.arange(lq)[None]
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shard_activation(q, ("batch", None, "heads", None), rules)

    if kv_cache is not None:
        kc, vc = kv_cache
        if not is_cross:              # self-attention decode: append token
            idx = jnp.max(cache_len) - 1
            kc = jax.lax.dynamic_update_slice_in_dim(kc, k, idx, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(vc, v, idx, axis=1)
            o = ops.decode_attention(q, kc, vc, cache_len,
                                     soft_cap=cfg.logits_soft_cap,
                                     window=window)
        else:                          # cross-attention decode: static cache
            o = ops.attention(q, kc, vc, causal=False,
                              soft_cap=cfg.logits_soft_cap, impl="ref")
        new_cache = (kc, vc)
    else:
        o = ops.attention(q, k, v, causal=causal and encoder_out is None,
                          soft_cap=cfg.logits_soft_cap, window=window,
                          impl=cfg.attn_impl, chunk=cfg.attn_chunk)
        # train/prefill: do not thread caches through the stack (a scanned
        # stack would materialize all-layer K/V; production prefill writes
        # the cache seq-sharded instead — see EXPERIMENTS.md §Dry-run)
        new_cache = None
    y = jnp.einsum("blhk,hkd->bld", o, p["wo"])
    return shard_activation(y, ("batch", "seq", "act_embed"), rules), new_cache


# ---------------------------------------------------------------------------
# Convolution layers (3D-TrIM kernel path; CNN frontends / vision towers)
# ---------------------------------------------------------------------------

def conv2d_params(k: int, cin: int, cout: int, *, groups: int = 1,
                  bias: bool = True) -> dict:
    """Declarations for one (grouped) conv layer on the trim_conv2d path."""
    # init_params scales by 1/sqrt(shape[-2]) == 1/sqrt(cin/groups); the
    # extra 1/k recovers He-style 1/sqrt(K^2 * cin/groups) for conv taps
    p = {"w": Param((k, k, cin // groups, cout), (None, None, None, None),
                    scale=1.0 / k)}
    if bias:
        p["b"] = Param((cout,), (None,), init="zeros")
    return p


def conv2d_apply(p: dict, x: jax.Array, *, stride: int = 1,
                 padding: str = "same", groups: int = 1,
                 activation: str | None = "relu",
                 impl: str = "pallas",
                 mesh=None, rules: dict | None = None,
                 layer: str | None = None) -> jax.Array:
    """One conv layer with the bias + activation epilogue fused into the
    Pallas kernel (single HBM round-trip for the output).  Accepts either
    raw params (``{"w", "b"}``) or a tree packed by
    :func:`conv2d_pack_params` (``{"packed"}``) — the packed form skips
    the per-call weight pad/reshape.  ``mesh``/``rules`` select the
    sharded halo-exchange path (DESIGN.md §6; raw params only — packed
    weights freeze a single-device layout).  ``layer`` names this layer
    in guard demotion events (DESIGN.md §9)."""
    if "packed" in p:
        return ops.conv2d(x, p["packed"], stride=stride, padding=padding,
                          impl=impl, activation=activation,
                          mesh=mesh, rules=rules, layer=layer)
    return ops.conv2d(x, p["w"], stride=stride, padding=padding, impl=impl,
                      feature_group_count=groups, bias=p.get("b"),
                      activation=activation, mesh=mesh, rules=rules,
                      layer=layer)


def conv2d_pack_params(p: dict, *, groups: int = 1,
                       tile_cout: int | None = None,
                       tile_h: int | None = None,
                       dataflow: str | None = None,
                       x_shape=None, stride: int = 1,
                       padding: str = "same") -> dict:
    """Pack one conv layer's materialized params at load time.

    Performs the pad/reshape to the kernel's ``padded_weight_shape`` (and
    the padded bias row) exactly once; the returned tree is consumed
    transparently by :func:`conv2d_apply`.  With ``x_shape`` given, the
    autotune cache fills any unset tile/dataflow knob so the forward pass
    runs entirely on cached plans.
    """
    return {"packed": ops.pack_conv2d_weights(
        p["w"], p.get("b"), groups=groups, tile_cout=tile_cout,
        tile_h=tile_h, dataflow=dataflow, x_shape=x_shape, stride=stride,
        padding=padding)}


def calibrate_conv2d(p: dict, x_batch: jax.Array, *, groups: int = 1,
                     stride: int = 1, padding: str = "same",
                     tile_cout: int | None = None,
                     tile_h: int | None = None,
                     dataflow: str | None = None) -> dict:
    """Post-training int8 calibration of one conv layer (DESIGN.md §11).

    Observes the sample batch's activation range for the per-tensor
    affine calibration — ``scale = (max - min) / 255`` over the
    ``[-128, 127]`` grid with the range widened to contain 0.0 so the
    zero point (the quantized image of 0.0, which also pads 'same'
    borders) is representable — quantizes the weights per-out-channel
    symmetric (``ref.weight_scales_int8``) and packs everything into a
    quantized :class:`~repro.kernels.ops.PackedConv2dWeights`.  The
    returned ``{"packed": ...}`` tree replaces ``{"w", "b"}`` and is
    consumed transparently by :func:`conv2d_apply`, which then runs the
    int8 tier chain of ``ops.conv2d``.
    """
    xf = x_batch.astype(jnp.float32)
    lo = jnp.minimum(jnp.min(xf), 0.0)
    hi = jnp.maximum(jnp.max(xf), 0.0)
    scale = jnp.maximum(hi - lo, 1e-12) / 255.0
    zp = jnp.clip(jnp.round(-128.0 - lo / scale),
                  -128, 127).astype(jnp.int32)
    return {"packed": ops.quantize_conv2d_weights(
        p["w"], p.get("b"), x_scale=scale, x_zero_point=zp, groups=groups,
        tile_cout=tile_cout, tile_h=tile_h, dataflow=dataflow,
        x_shape=x_batch.shape, stride=stride, padding=padding)}


def depthwise_separable_params(k: int, cin: int, cout: int,
                               *, bias: bool = True) -> dict:
    """MobileNet-style depthwise 3x3 + pointwise 1x1 block."""
    return {"dw": conv2d_params(k, cin, cin, groups=cin, bias=bias),
            "pw": conv2d_params(1, cin, cout, bias=bias)}


def depthwise_separable_pack_params(p: dict, *, x_shape=None,
                                    stride: int = 1) -> dict:
    """Load-time packing of a depthwise-separable block (both convs)."""
    cin = p["dw"]["w"].shape[3]
    dw_shape = pw_shape = x_shape
    if x_shape is not None and stride != 1:
        n, h, w, _ = x_shape
        pw_shape = (n, -(-h // stride), -(-w // stride), cin)
    return {"dw": conv2d_pack_params(p["dw"], groups=cin, x_shape=dw_shape,
                                     stride=stride),
            "pw": conv2d_pack_params(p["pw"], x_shape=pw_shape)}


def depthwise_separable_apply(p: dict, x: jax.Array, *, stride: int = 1,
                              activation: str | None = "relu",
                              impl: str = "pallas",
                              mesh=None,
                              rules: dict | None = None) -> jax.Array:
    h = conv2d_apply(p["dw"], x, stride=stride, groups=x.shape[-1],
                     activation=activation, impl=impl, mesh=mesh,
                     rules=rules)
    return conv2d_apply(p["pw"], h, activation=activation, impl=impl,
                        mesh=mesh, rules=rules)


def _pooled_head(p: dict, x: jax.Array) -> jax.Array:
    """Global mean pool + linear classifier: ``(N, H, W, C) -> (N,
    classes)``.  One image at a time (``lax.map``), so every row runs
    the same compiled arithmetic whatever the batch: a served row
    equals the single-request forward bit for bit, where a batched
    reduction or matmul may order its sums by batch size.  The
    projection is an elementwise product summed over channels, full f32
    on every backend (a TPU matmul at default precision rounds f32
    operands to bf16)."""
    def one(xi):
        return (xi.mean(axis=(0, 1))[:, None] * p["w"]).sum(axis=0) \
            + p["b"]
    return jax.lax.map(one, x)


def simple_cnn_params(*, cin: int = 3, channels=(8, 16), n_classes: int = 10,
                      k: int = 3, depthwise_stage: bool = True) -> dict:
    """A small CIFAR-shaped classifier running entirely on trim kernels.

    Per stage: a stride-1 conv (fused ReLU) followed by a stride-2 conv
    for downsampling — pooling as strided convolution keeps every op on
    the differentiable Pallas path.  ``depthwise_stage`` inserts a
    depthwise 3x3 before the last downsample so training exercises the
    grouped backward kernels too.  The head is global mean pooling + a
    dense projection.
    """
    p, prev = {}, cin
    for i, c in enumerate(channels):
        p[f"conv{i}"] = conv2d_params(k, prev, c)
        p[f"down{i}"] = conv2d_params(k, c, c)
        prev = c
    if depthwise_stage:
        p["dw"] = conv2d_params(k, prev, prev, groups=prev)
    p["head"] = {"w": Param((prev, n_classes), (None, None)),
                 "b": Param((n_classes,), (None,), init="zeros")}
    return p


def simple_cnn_apply(p: dict, x: jax.Array, *, impl: str = "pallas",
                     mesh=None, rules: dict | None = None) -> jax.Array:
    """Forward pass of :func:`simple_cnn_params`.  x: (N, H, W, Cin);
    returns (N, n_classes) logits.  The depthwise stage is applied iff
    the params carry one (inferred from the tree, like the stage
    count).  With ``mesh``/``rules`` every conv runs the sharded
    halo-exchange path (data + spatial parallelism, DESIGN.md §6)."""
    n_stages = sum(1 for k in p if k.startswith("conv"))
    for i in range(n_stages):
        x = conv2d_apply(p[f"conv{i}"], x, activation="relu", impl=impl,
                         mesh=mesh, rules=rules)
        if "dw" in p and i == n_stages - 1:
            x = conv2d_apply(p["dw"], x, groups=x.shape[-1],
                             activation="relu", impl=impl, mesh=mesh,
                             rules=rules)
        x = conv2d_apply(p[f"down{i}"], x, stride=2, activation="relu",
                         impl=impl, mesh=mesh, rules=rules)
    return _pooled_head(p["head"], x)


def cnn_params_from_layers(layers_list, *, n_classes: int | None = None,
                           bias: bool = True) -> dict:
    """Parameter declarations for a whole conv topology (DESIGN.md §7).

    ``layers_list`` is a ``list[core.model.ConvLayer]`` — e.g.
    ``core.netplan.network_layers("vgg16")`` or a
    ``core.netplan.scale_layers`` reduction of it.  One ``conv{i}``
    entry per layer; ``n_classes`` adds a global-mean-pool linear head.
    Consumed by :func:`cnn_apply_from_layers` (and packable layer-by-
    layer with :func:`cnn_pack_params`).
    """
    p = {}
    for i, l in enumerate(layers_list):
        p[f"conv{i}"] = conv2d_params(l.kernel, l.in_channels,
                                      l.out_channels, groups=l.groups,
                                      bias=bias)
    if n_classes is not None:
        d = layers_list[-1].out_channels
        p["head"] = {"w": Param((d, n_classes), (None, None)),
                     "b": Param((n_classes,), (None,), init="zeros")}
    return p


def cnn_pack_params(p: dict, layers_list, *, n: int = 1) -> dict:
    """Load-time packing of a whole topology's conv weights.

    Threads the activation shape through the layers (pooling included)
    so each ``conv2d_pack_params`` call keys the autotune cache with the
    exact shape ``ops.conv2d`` will see — after an
    ``autotune.tune_network`` sweep the packed forward pass runs
    entirely on cached plans."""
    from repro.core.netplan import layer_kernel_problem
    packed = dict(p)
    for i, l in enumerate(layers_list):
        if l.kernel > ops.MAX_NATIVE_K:
            continue    # kernel-tiled path re-slices raw weights (§4)
        # the shared layer -> executed-problem mapping (validates that
        # the layer's padding is reproducible by the execution path)
        _, _, _, padding = layer_kernel_problem(l, n=n)
        packed[f"conv{i}"] = conv2d_pack_params(
            p[f"conv{i}"], groups=l.groups,
            x_shape=(n, l.ifmap, l.ifmap, l.in_channels),
            stride=l.stride, padding=padding)
    return packed


def _maxpool(x: jax.Array, stride: int, window: int) -> jax.Array:
    """Max pooling (VGG 2x2/s2, AlexNet overlapping 3x3/s2)."""
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, window, window, 1),
        (1, stride, stride, 1), "VALID")


def _cnn_apply_layer_range(p: dict, layers_list, pools, x: jax.Array,
                           lo: int, hi: int, *, activation, impl, mesh,
                           rules) -> jax.Array:
    """Per-layer execution of layers ``[lo, hi)`` — one ``conv2d`` call
    (plus the inferred max-pool) per layer.  Shared by the plain forward
    pass and by the fused path's depth-1 groups."""
    from repro.core.netplan import layer_kernel_problem
    for i in range(lo, hi):
        l, (ps, pw) = layers_list[i], pools[i]
        # derive (and validate) the padding mode through the shared
        # layer -> executed-problem mapping: a topology whose paper
        # padding this path cannot reproduce fails loudly here instead
        # of silently running a different network than NetworkPlan bills
        _, _, _, padding = layer_kernel_problem(l, n=x.shape[0])
        x = conv2d_apply(p[f"conv{i}"], x, stride=l.stride,
                         padding=padding, groups=l.groups,
                         activation=activation, impl=impl, mesh=mesh,
                         rules=rules, layer=l.name)
        if ps > 1 or pw > 1:      # (1, w>1): stride-1 overlapping pool
            x = _maxpool(x, ps, pw)
    return x


def cnn_apply_from_layers(p: dict, layers_list, x: jax.Array, *,
                          activation: str | None = "relu",
                          impl: str = "pallas", mesh=None,
                          rules: dict | None = None,
                          fused: bool = False,
                          fuse_plan=None) -> jax.Array:
    """Forward pass of a conv topology built by
    :func:`cnn_params_from_layers`: each conv runs on the trim kernel
    path (bias + activation fused; packed params and cached plans when
    the tree was packed/tuned), with the topology's max-pooling inferred
    from the spatial dims between consecutive layers
    (``core.netplan.infer_pools``).  Returns class logits when the tree
    has a head, else the final feature map.

    ``fused=True`` executes each residency group of a
    :class:`~repro.core.fuse_plan.FusedGroupPlan` as one megakernel
    (conv→[pool]→conv chains with interior activations VMEM-resident,
    DESIGN.md §8) instead of one ``pallas_call`` per layer; depth-1
    groups fall back to the per-layer path, so outputs are bit-identical
    either way.  Pass ``fuse_plan`` to reuse a prebuilt (e.g. autotuned)
    plan; otherwise one is built for ``x``'s batch.  The fused path
    needs raw (unpacked) conv params and is single-device —
    ``mesh``/``rules`` select the sharded per-layer engine instead.
    """
    from repro.core.netplan import infer_pools
    pools = list(infer_pools(layers_list))
    if fused or fuse_plan is not None:
        if mesh is not None or rules is not None:
            raise ValueError(
                "fused execution is single-device; drop mesh/rules or "
                "run the per-layer sharded path (fused=False)")
        from repro.core.fuse_plan import FusedGroupPlan
        from repro.kernels.trim_conv2d_fused import fused_group_apply
        if fuse_plan is None:
            fuse_plan = FusedGroupPlan.build(list(layers_list),
                                             n=x.shape[0])
        for g in fuse_plan.groups:
            lo, hi = g.start, g.start + g.depth
            if not g.fused:
                x = _cnn_apply_layer_range(
                    p, layers_list, pools, x, lo, hi,
                    activation=activation, impl=impl, mesh=None,
                    rules=None)
                continue
            weights, biases = [], []
            for i in range(lo, hi):
                lp = p[f"conv{i}"]
                if "packed" in lp:
                    raise ValueError(
                        f"conv{i}: fused execution needs raw conv "
                        "params ({'w', 'b'}); packed trees freeze the "
                        "per-layer kernel layout — skip cnn_pack_params "
                        "on the fused path")
                weights.append(lp["w"])
                biases.append(lp.get("b"))
            # guarded megakernel (DESIGN.md §9): a lowering/runtime
            # failure of the whole-group kernel demotes this group to
            # per-layer execution, which itself demotes conv-by-conv
            label = f"{layers_list[lo].name}..{layers_list[hi - 1].name}"

            def _fused_tier(x=x, weights=weights, biases=biases, g=g):
                return fused_group_apply(x, weights, biases, group=g,
                                         activation=activation)

            def _per_layer_tier(x=x, lo=lo, hi=hi):
                return _cnn_apply_layer_range(
                    p, layers_list, pools, x, lo, hi,
                    activation=activation, impl=impl, mesh=None,
                    rules=None)

            key = f"fused:d{g.depth}:n{g.n}:{g.signature}:{x.dtype}"
            x = guard.run_chain(key, [("fused", _fused_tier),
                                      ("pallas", _per_layer_tier)],
                                layer=label)
    else:
        x = _cnn_apply_layer_range(p, layers_list, pools, x, 0,
                                   len(layers_list),
                                   activation=activation, impl=impl,
                                   mesh=mesh, rules=rules)
    if "head" not in p:
        return x
    return _pooled_head(p["head"], x)


def cnn_params_from_graph(graph, *, n_classes: int | None = None,
                          bias: bool = True) -> dict:
    """Parameter declarations for a DAG topology (DESIGN.md §12).

    ``graph`` is anything ``core.netplan.graph_nodes`` resolves — a name
    ("resnet18" | "unet"), a ``list[GraphNode]`` or a linear topology.
    One entry per conv node, keyed by the NODE name (graphs have no
    layer order to index by); joins carry no params.  ``n_classes``
    adds a global-mean-pool linear head over the terminal node's
    channels.  Consumed by :func:`cnn_apply_from_graph`."""
    from repro.core.netplan import graph_nodes
    nodes = graph_nodes(graph)
    p, ch = {}, {}
    for nd in nodes:
        if nd.name == "head":
            raise ValueError(
                'node name "head" is reserved for the linear classifier '
                "head — rename the graph node")
        if nd.op == "conv":
            l = nd.layer
            p[nd.name] = conv2d_params(l.kernel, l.in_channels,
                                       l.out_channels, groups=l.groups,
                                       bias=bias)
            ch[nd.name] = l.out_channels
        elif nd.op == "concat":
            ch[nd.name] = sum(ch[s] for s in nd.inputs)
        else:
            ch[nd.name] = ch[nd.inputs[0]]
    if n_classes is not None:
        d = ch[nodes[-1].name]
        p["head"] = {"w": Param((d, n_classes), (None, None)),
                     "b": Param((n_classes,), (None,), init="zeros")}
    return p


def cnn_pack_params_from_graph(p: dict, graph, *, n: int = 1) -> dict:
    """Load-time packing of a DAG topology's conv weights — the graph
    analogue of :func:`cnn_pack_params`: each conv node's kernel-seen
    shape keys the autotune cache, so a ``tune_graph`` sweep makes the
    packed forward pass run entirely on cached plans."""
    from repro.core.netplan import graph_nodes, layer_kernel_problem
    packed = dict(p)
    for nd in graph_nodes(graph):
        if nd.op != "conv" or nd.layer.kernel > ops.MAX_NATIVE_K:
            continue
        l = nd.layer
        _, _, _, padding = layer_kernel_problem(l, n=n)
        packed[nd.name] = conv2d_pack_params(
            p[nd.name], groups=l.groups,
            x_shape=(n, l.ifmap, l.ifmap, l.in_channels),
            stride=l.stride, padding=padding)
    return packed


def _upsample_nearest(x: jax.Array, scale: int) -> jax.Array:
    """Nearest-neighbour spatial upsampling (U-Net decoder)."""
    return jnp.repeat(jnp.repeat(x, scale, axis=1), scale, axis=2)


def _graph_conv_node(p: dict, nd, x: jax.Array, *, activation, impl,
                     mesh, rules) -> jax.Array:
    """One graph conv node: the trim conv (padding validated through the
    shared layer -> executed-problem mapping) plus its epilogue pool."""
    from repro.core.netplan import layer_kernel_problem
    l = nd.layer
    _, _, _, padding = layer_kernel_problem(l, n=x.shape[0])
    y = conv2d_apply(p[nd.name], x, stride=l.stride, padding=padding,
                     groups=l.groups, activation=activation, impl=impl,
                     mesh=mesh, rules=rules, layer=l.name)
    if nd.pool > 1 or nd.pool_window > 1:
        y = _maxpool(y, nd.pool, nd.pool_window)
    return y


def cnn_apply_from_graph(p: dict, graph, x: jax.Array, *,
                         activation: str | None = "relu",
                         impl: str = "pallas", mesh=None,
                         rules: dict | None = None,
                         fused: bool = False,
                         fuse_plan=None) -> jax.Array:
    """Forward pass of a DAG topology built by
    :func:`cnn_params_from_graph`: nodes execute in topological order —
    conv nodes on the trim kernel path (tuned / packed / guarded, same
    engine as the chains), joins as their jnp epilogues (elementwise
    add, channel concat, max pool, nearest upsample).  Returns the
    terminal node's activation, or class logits when the tree has a
    head.

    ``fused=True`` partitions the graph into fusable linear segments
    between joins (``core.fuse_plan.graph_segments``) and executes each
    multi-conv segment exactly like today's chains —
    :func:`cnn_apply_from_layers` with a per-segment
    :class:`~repro.core.fuse_plan.FusedGroupPlan` — so fused and
    per-node execution are bit-identical (tested).  Pass ``fuse_plan``
    (a prebuilt :class:`~repro.core.fuse_plan.GraphFusePlan`) to reuse
    tuned segment plans.  The fused path needs raw conv params and is
    single-device."""
    from repro.core.netplan import graph_nodes
    nodes = graph_nodes(graph)
    by = {nd.name: nd for nd in nodes}
    seg_of: dict[str, tuple] = {}
    if fused or fuse_plan is not None:
        if mesh is not None or rules is not None:
            raise ValueError(
                "fused execution is single-device; drop mesh/rules or "
                "run the per-node path (fused=False)")
        if fuse_plan is not None:
            segs = list(fuse_plan.segments)
        else:
            from repro.core.fuse_plan import graph_segments
            segs = [(names, None) for names, _ in graph_segments(nodes)]
        for names, plan in segs:
            seg_of[names[0]] = (names, plan)

    outs: dict[str, jax.Array] = {}
    executed: set[str] = set()
    last = None
    for nd in nodes:
        if nd.name in executed:
            continue
        if nd.name in seg_of and len(seg_of[nd.name][0]) > 1:
            names, plan = seg_of[nd.name]
            seg_nodes = [by[nm] for nm in names]
            conv_nodes = [sn for sn in seg_nodes if sn.op == "conv"]
            first, tail = seg_nodes[0], seg_nodes[-1]
            xin = outs[first.inputs[0]] if first.inputs else x
            p_sub = {f"conv{i}": p[sn.name]
                     for i, sn in enumerate(conv_nodes)}
            y = cnn_apply_from_layers(
                p_sub, [sn.layer for sn in conv_nodes], xin,
                activation=activation, impl=impl, fused=True,
                fuse_plan=plan)
            if tail.pool > 1 or tail.pool_window > 1:
                y = _maxpool(y, tail.pool, tail.pool_window)
            executed.update(names)
            outs[tail.name] = y
            last = tail.name
            continue
        if nd.op == "conv":
            xin = outs[nd.inputs[0]] if nd.inputs else x
            y = _graph_conv_node(p, nd, xin, activation=activation,
                                 impl=impl, mesh=mesh, rules=rules)
        elif nd.op == "pool":
            y = _maxpool(outs[nd.inputs[0]], nd.pool, nd.pool_window)
        elif nd.op == "add":
            y = outs[nd.inputs[0]]
            for s in nd.inputs[1:]:
                y = y + outs[s]
        elif nd.op == "concat":
            y = jnp.concatenate([outs[s] for s in nd.inputs], axis=-1)
        else:                                     # upsample
            y = _upsample_nearest(outs[nd.inputs[0]], nd.scale)
        outs[nd.name] = y
        executed.add(nd.name)
        last = nd.name
    y = outs[last]
    if "head" not in p:
        return y
    return _pooled_head(p["head"], y)


# ---------------------------------------------------------------------------
# Dense MLPs
# ---------------------------------------------------------------------------

def mlp_params(cfg: ModelConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp in ("swiglu", "geglu"):
        return {"w_gate": Param((d, f), ("embed", "mlp")),
                "w_up": Param((d, f), ("embed", "mlp")),
                "w_down": Param((f, d), ("mlp", "embed"))}
    return {"w_up": Param((d, f), ("embed", "mlp")),
            "b_up": Param((f,), ("mlp",), init="zeros"),
            "w_down": Param((f, d), ("mlp", "embed")),
            "b_down": Param((d,), ("act_embed",), init="zeros")}


def mlp_apply(p: dict, x: jax.Array, cfg: ModelConfig, rules: dict):
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"]) * (x @ p["w_up"])
    else:
        h = jax.nn.gelu(x @ p["w_up"] + p["b_up"])
    h = shard_activation(h, ("batch", None, "mlp"), rules)
    y = h @ p["w_down"]
    if "b_down" in p:
        y = y + p["b_down"]
    return shard_activation(y, ("batch", "seq", "act_embed"), rules)


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity-grouped matmul)
# ---------------------------------------------------------------------------

def moe_params(cfg: ModelConfig) -> dict:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_dff
    p = {
        "router": Param((d, e), ("embed", "experts"), scale=0.1),
        "w_gate": Param((e, d, f), ("experts", "embed", "mlp")),
        "w_up": Param((e, d, f), ("experts", "embed", "mlp")),
        "w_down": Param((e, f, d), ("experts", "mlp", "embed")),
    }
    if cfg.shared_expert_dff:
        p["shared"] = {
            "w_gate": Param((d, cfg.shared_expert_dff), ("embed", "mlp")),
            "w_up": Param((d, cfg.shared_expert_dff), ("embed", "mlp")),
            "w_down": Param((cfg.shared_expert_dff, d), ("mlp", "embed")),
        }
    return p


def moe_apply(p: dict, x: jax.Array, cfg: ModelConfig, rules: dict):
    """GShard-style token-choice top-k with *grouped* capacity dispatch.

    Tokens are grouped by sequence (the group dim is batch-sharded), so
    every gather/scatter in the dispatch is a *batched* op over a sharded
    leading dim — SPMD shards it instead of all-gathering the operands.
    The expert einsum is (g, e, c, d) x (e, d, f) with g on the data axis
    and e on the model axis (expert parallelism); the data->expert
    boundary at the capacity buffer is the MoE all-to-all.  HLO flops
    reflect the useful expert compute: T*k*cf * 3*D*F.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    # decode (s == 1): a single group over the whole batch keeps the
    # capacity waste bounded (cap ~ B*k/E instead of 1 per sequence).
    xg = x.reshape(1, b, d) if s == 1 else x
    g, tg, _ = xg.shape
    xg = shard_activation(xg, ("batch", None, None), rules)

    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)              # (g, tg, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                     1e-9)                     # renormalize

    cap = max(int(math.ceil(tg * k / e * cfg.capacity_factor)), 1)

    def _dispatch_one(xg1, idx1, val1):
        """One group: sort tokens by expert, scatter into capacity slots.

        vmapped over groups so every gather/scatter carries an explicit
        batch dim that the SPMD partitioner shards (a flat multi-dim
        scatter would be replicated on every device).
        """
        flat_e = idx1.reshape(tg * k)
        flat_t = jnp.repeat(jnp.arange(tg), k)
        flat_g = val1.reshape(tg * k).astype(x.dtype)
        order = jnp.argsort(flat_e)                            # stable
        seg, tok, gts = flat_e[order], flat_t[order], flat_g[order]
        counts = jnp.bincount(flat_e, length=e)
        starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(tg * k) - starts[seg]
        keep = rank < cap
        slot = jnp.where(keep, seg * cap + rank, e * cap)      # overflow
        rows = xg1[tok] * keep[:, None].astype(x.dtype)
        buf1 = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].set(rows)
        return buf1[:-1], slot, tok, gts, keep, counts

    buf, slot, tok, gts, keep, counts = jax.vmap(_dispatch_one)(
        xg, gate_idx, gate_vals)
    buf = buf.reshape(g, e, cap, d)
    # the data->expert all-to-all boundary (expert parallelism)
    buf = shard_activation(buf, ("batch", "experts", None, None), rules)

    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])) \
        * jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = shard_activation(h, ("batch", "experts", None, "mlp"), rules)
    yexp = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    yexp = shard_activation(yexp, ("batch", "experts", None, None), rules)

    def _combine_one(yexp1, slot1, tok1, gts1, keep1):
        back = yexp1.reshape(e * cap, d)[jnp.clip(slot1, 0, e * cap - 1)]
        contrib = jnp.where(keep1[:, None], back, 0.0) * gts1[:, None]
        return jnp.zeros((tg, d), x.dtype).at[tok1].add(contrib)

    y = jax.vmap(_combine_one)(yexp, slot, tok, gts, keep)
    y = shard_activation(y, ("batch", None, None), rules)

    if "shared" in p:
        y = y + mlp_apply(p["shared"], xg, cfg, rules)

    # load-balancing auxiliary loss (Switch-style), averaged over groups
    me = probs.mean(axis=1)                                    # (g, e)
    ce = counts.astype(jnp.float32) / (tg * k)                 # (g, e)
    aux = e * jnp.mean(jnp.sum(me * ce, axis=-1))
    return (shard_activation(y.reshape(b, s, d),
                             ("batch", "seq", "act_embed"), rules), aux)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------

def embedding_params(cfg: ModelConfig) -> dict:
    p = {"embed": Param((cfg.vocab, cfg.d_model), ("vocab", "embed"),
                        scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = Param((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    return p


def embed_apply(p: dict, tokens: jax.Array, cfg: ModelConfig, rules: dict):
    x = jnp.take(p["embed"], tokens, axis=0)
    return shard_activation(x, ("batch", "seq", "act_embed"), rules)


def head_apply(p: dict, x: jax.Array, cfg: ModelConfig, rules: dict):
    w = p["embed"].T if cfg.tie_embeddings else p["head"]
    logits = x @ w
    return shard_activation(logits, ("batch", None, "vocab"), rules)
