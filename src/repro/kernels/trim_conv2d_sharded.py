"""Sharded TrIM convolution execution under ``shard_map`` (DESIGN.md §6).

The multi-device image of the paper's shadow-register overlap: each
device owns an H-slab of the (pre-padded) ifmap and a strip of the
output rows; before the local Pallas kernel runs, the K-1 boundary rows
move between neighbors as an explicit ``ppermute`` halo exchange — the
on-chip carry traffic of ``ConvPlan`` made into real inter-chip bytes,
which :class:`~repro.core.conv_shard.ShardedConvPlan` bills as a
first-class roofline term.

Per-shard schedule (geometry owned by the plan):

1. **Slab split.**  The globally padded input is padded/cropped to
   exactly ``spatial_shards * slab_rows`` rows plus a K-1 row tail; the
   slabs shard over ``spatial_axis``, the tail stays with the batch.
2. **Halo exchange.**  Shard ``d`` receives the first K-1 slab rows of
   shard ``d+1`` (*down*; the last shard's down-halo is the local
   tail).  Slabs are stride-aligned by construction, so this single
   direction assembles every owned output row's full receptive field —
   nothing is recomputed.
3. **Local kernel.**  The assembled ``local_in_rows`` window runs
   through the ordinary carry/halo Pallas kernel (``local_conv``; the
   differentiable custom_vjp core when called via ``ops.conv2d``) as a
   valid stride-``s`` conv, emitting exactly the owned ``h_out_local``
   rows per shard.

Because the whole function is ordinary traced jax, the backward pass
falls out of transposition: the input-grad halo exchange is the
transpose of the forward ``ppermute`` shuffle (boundary cotangent rows
flow back to the neighbor that owns them), and the weight/bias
cotangents of the replicated operands finish with a ``psum`` over the
mesh.  The per-shard cotangent kernels are the custom_vjp backward
kernels of the local conv — the single-device machinery, per shard.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.conv_shard import ShardedConvPlan


def make_sharded_plan(x_shape, w_shape, mesh, *, rules: dict | None = None,
                      **kw) -> ShardedConvPlan:
    """The exact plan :func:`sharded_conv2d` executes for these
    arguments on this mesh (shard grid resolved from the conv rules)."""
    return ShardedConvPlan.from_mesh(x_shape, w_shape, mesh, rules=rules,
                                     **kw)


def sharded_conv2d(x: jax.Array, w: jax.Array,
                   bias: jax.Array | None = None, *,
                   plan: ShardedConvPlan, mesh,
                   local_conv=None,
                   interpret: bool | None = None) -> jax.Array:
    """Run one sharded conv according to ``plan`` under ``shard_map``.

    x: (N, H, W, Cin) **already pre-padded** (``plan.pad == 0`` — the
    caller folds 'same' padding globally, exactly like the single-device
    path); w: (K, K, Cin/groups, Cout) logical weights (replicated);
    bias: (Cout,) or None (replicated).

    ``local_conv(window, w, bias)`` executes one shard's valid
    stride-``plan.stride`` convolution; it defaults to the raw
    ``trim_conv2d`` kernel with the plan's knobs — ``ops.conv2d`` passes
    its differentiable custom_vjp core instead so gradients run on the
    Pallas backward kernels per shard.
    Returns the global (N, H_out, W_out, Cout).
    """
    if plan.pad != 0:
        raise ValueError("sharded_conv2d expects pre-padded input "
                         f"(plan.pad == 0), got pad={plan.pad}")
    assert x.shape == (plan.n, plan.h, plan.w, plan.cin), \
        (x.shape, plan)
    s, kh, ss = plan.stride, plan.kh, plan.spatial_shards
    slab = plan.slab_rows
    total, tail = ss * slab, kh - 1
    ba, sa = plan.batch_axis, plan.spatial_axis

    if local_conv is None:
        from repro.kernels.trim_conv2d import trim_conv2d
        local_conv = functools.partial(
            trim_conv2d, stride=s, pad=0, tile_h=plan.tile_h,
            tile_cout=plan.tile_cout, groups=plan.groups,
            dataflow=plan.dataflow, interpret=interpret)

    # slab split: exactly ss * slab_rows rows shard over the spatial
    # axis; the K-1 tail (real rows beyond the slabs, or zero padding)
    # rides replicated along it so the last shard's down-halo is local
    grow = total + tail - x.shape[1]
    xr = jnp.pad(x, ((0, 0), (0, max(grow, 0)), (0, 0), (0, 0)))
    xr = xr[:, :total + tail]
    x_main, x_tail = xr[:, :total], xr[:, total:]

    hops = -(-tail // slab) if tail else 0   # neighbor hops per exchange

    def _down_halo(xm, xt):
        """The K-1 rows below the slab: global rows [(d+1)*slab,
        (d+1)*slab + K-1).  Usually one ppermute from the next shard;
        when slabs are shorter than K-1 (over-sharded tail shards) the
        window spans several neighbors — hop ``j`` fetches shard
        ``d+j``'s slab prefix, and sources past the last slab read the
        replicated global tail instead."""
        if ss == 1:
            return xt
        idx = jax.lax.axis_index(sa)
        xtp = jnp.pad(xt, ((0, 0), (0, hops * slab - tail), (0, 0),
                           (0, 0)))
        parts, got = [], 0
        for j in range(1, hops + 1):
            take = min(slab, tail - got)
            src = xm[:, :take]
            perm = [(i + j, i) for i in range(ss - j)]
            hop = jax.lax.ppermute(src, sa, perm) if perm \
                else jnp.zeros_like(src)
            from_tail = jax.lax.dynamic_slice_in_dim(
                xtp, jnp.clip(idx + j - ss, 0, j - 1) * slab, take,
                axis=1)
            parts.append(jnp.where(idx + j >= ss, from_tail, hop))
            got += take
        return parts[0] if hops == 1 else jnp.concatenate(parts, axis=1)

    def _local(xm, xt, wl, bl):
        window = xm if not tail \
            else jnp.concatenate([xm, _down_halo(xm, xt)], axis=1)
        return local_conv(window, wl, bl)

    in_specs = [P(ba, sa, None, None), P(ba, None, None, None), P()]
    args = [x_main, x_tail, w]
    if bias is None:
        fn = lambda xm, xt, wl: _local(xm, xt, wl, None)  # noqa: E731
    else:
        fn = _local
        in_specs.append(P())
        args.append(bias)

    out = jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                        out_specs=P(ba, sa, None, None),
                        check_vma=False)(*args)
    assert out.shape[1] == ss * plan.h_out_local, (out.shape, plan)
    return out[:, :plan.h_out]
