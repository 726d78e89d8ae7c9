"""Gradient compression for the cross-pod all-reduce.

``compressed_psum``: int8-quantized gradient reduction via shard_map —
each device quantizes its local partial gradient to int8 (per-tensor
scale), all-gathers the int8 payload (1 byte/элемент on the wire instead
of 4), and reduces locally in fp32.  Ring wire cost: S*(g-1)/g bytes vs
2*S*4*(g-1)/g for an fp32 all-reduce — an ~8x collective-bytes saving,
visible in the dry-run HLO as an s8 all-gather.

``ef_quantize``: error-feedback quantization (residual carried in the
optimizer state) for when compression is applied at the optimizer level.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize_int8(x: jax.Array):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_allreduce(x: jax.Array, axis_name):
    """Inside shard_map: all-reduce with int8 wire format.

    Quantize -> all-gather int8 (1 B/elt on the wire) -> fp32 local reduce.
    """
    q, scale = _quantize_int8(x.astype(jnp.float32))
    qg = jax.lax.all_gather(q, axis_name)           # int8 on the wire
    sg = jax.lax.all_gather(scale, axis_name)
    out = jnp.tensordot(sg, qg.astype(jnp.float32), axes=((0,), (0,)))
    return out.astype(x.dtype)


def compressed_psum(x: jax.Array, axis_name: str, mesh):
    """All-reduce a replicated-per-shard partial ``x`` over one mesh axis
    with int8 wire format (shard_map wrapper for manual-DP train steps)."""
    fn = functools.partial(int8_allreduce, axis_name=axis_name)
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(x)


def ef_quantize(grad: jax.Array, residual: jax.Array, bits: int = 8):
    """Error-feedback quantization: returns (q_grad, new_residual)."""
    levels = 2 ** (bits - 1) - 1
    x = grad.astype(jnp.float32) + residual.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x)) / levels + 1e-12
    q = jnp.clip(jnp.round(x / scale), -levels, levels) * scale
    return q.astype(grad.dtype), (x - q).astype(residual.dtype)
