"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These are the ground truth for the allclose sweeps in tests/ and the
fallback implementation on platforms without Pallas support.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def epilogue(y: jax.Array, bias: jax.Array | None = None,
             activation: str | None = None) -> jax.Array:
    """Bias + activation epilogue oracle (fused into trim_conv2d)."""
    if bias is not None:
        y = y + bias
    if activation is None:
        return y
    fn = {"relu": jax.nn.relu, "gelu": jax.nn.gelu,
          "silu": jax.nn.silu}[activation]
    return fn(y)


def lax_padding(padding):
    """The ``lax.conv_general_dilated`` padding of a conv ``padding``:
    ``"same"`` / ``"valid"``, or an int ``p``, the symmetric ``(p, p)``
    on both spatial axes."""
    if isinstance(padding, str):
        return padding.upper()
    return [(padding, padding), (padding, padding)]


def conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
           padding: str | int = "same", feature_group_count: int = 1,
           bias: jax.Array | None = None,
           activation: str | None = None) -> jax.Array:
    """2D (grouped) convolution oracle.

    x: (N, H, W, Cin); w: (K, K, Cin/groups, Cout); bias: (Cout,) or None;
    ``padding`` as :func:`lax_padding` takes it.
    """
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=lax_padding(padding),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count)
    return epilogue(y, bias, activation)


def _same_pads(size: int, k: int, s: int) -> tuple[int, int]:
    """XLA 'SAME' padding: out = ceil(size/s), possibly asymmetric."""
    out = -(-size // s)
    total = max((out - 1) * s + k - size, 0)
    return total // 2, total - total // 2


def quantize_int8(x: jax.Array, scale, zero_point=0) -> jax.Array:
    """Affine int8 quantization ``q = clip(round(x/scale) + zp, -128, 127)``.

    ``scale``/``zero_point`` may be scalars (per-tensor activations) or
    broadcastable arrays (per-channel weights with ``zero_point=0``).
    """
    q = jnp.round(x / jnp.asarray(scale, jnp.float32))
    q = q + jnp.asarray(zero_point, jnp.float32)
    return jnp.clip(q, -128, 127).astype(jnp.int8)


def weight_scales_int8(w: jax.Array) -> jax.Array:
    """Per-out-channel symmetric weight scales: ``max|w| / 127``.

    w: (K, K, Cin/g, Cout) -> (Cout,) f32.  Symmetric (zero_point = 0),
    so the int8 matmul needs no weight zero-point correction.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=(0, 1, 2))
    return jnp.maximum(amax, 1e-12) / 127.0


def dequant_params(w_q: jax.Array, w_scale: jax.Array, x_scale,
                   x_zero_point, bias: jax.Array | None = None) -> tuple:
    """The epilogue ``y = (acc_i32 + bias_q) * scale`` of an int8 conv.

    ``scale = x_scale * w_scale`` per out channel, and ``bias_q`` is the
    *requantized int32 bias*:

        bias_q = -z_x * colsum(w_q) + round(bias / scale)

    Because the ifmap is padded with the activation *zero point* (not
    zero), every output position sees a full kernel window of quantized
    values, so the zero-point correction ``-z_x * colsum`` is
    position-independent — and exactly integer.  The real bias is
    rounded onto the ``scale`` grid (the standard fixed-point bias
    treatment), which keeps the whole epilogue an exact int32 add
    followed by ONE correctly-rounded f32 multiply: no mul+add pair
    exists for a backend to contract into an FMA, so the kernel and the
    oracle agree bit-for-bit on every backend.

    Works on logical ``(K, K, Cin/g, Cout)`` weights with ``(Cout,)``
    scales and on the kernel's padded layout with ``(1, G*CoutP)`` rows
    (pad ``w_scale`` with ones so the bias requantization never divides
    by zero) — the kernel and the oracle MUST both price their epilogue
    through this one helper for the bit-exactness contract of
    ``tests/test_quant.py`` to hold.
    """
    colsum = w_q.astype(jnp.int32).sum(axis=(0, 1, 2))
    scale = jnp.asarray(x_scale, jnp.float32) * w_scale.astype(jnp.float32)
    bias_q = -jnp.asarray(x_zero_point, jnp.int32) * colsum
    if bias is not None:
        bias_q = bias_q + jnp.round(
            bias.astype(jnp.float32) / scale).astype(jnp.int32)
    return scale, bias_q


def conv2d_quantized(x_q: jax.Array, w_q: jax.Array, *, x_scale,
                     x_zero_point, w_scale: jax.Array,
                     bias: jax.Array | None = None, stride: int = 1,
                     padding: str | int = "same",
                     feature_group_count: int = 1,
                     activation: str | None = None) -> jax.Array:
    """Int8 quantized conv oracle: int32 accumulation, f32 dequant epilogue.

    x_q: int8 (N, H, W, Cin); w_q: int8 (K, K, Cin/g, Cout); w_scale:
    (Cout,) per-out-channel symmetric scales; ``x_scale``/``x_zero_point``
    the per-tensor affine activation quantization.  'same' padding pads
    with the activation zero point (the quantized image of 0.0), so the
    result dequantizes to the f32 'same' conv.  Returns f32.
    """
    if padding == "same":
        kh, kw = w_q.shape[0], w_q.shape[1]
        ph = _same_pads(x_q.shape[1], kh, stride)
        pw = _same_pads(x_q.shape[2], kw, stride)
    elif isinstance(padding, int) and padding >= 0:
        ph = pw = (padding, padding)
    elif padding == "valid":
        ph = pw = (0, 0)
    else:
        raise ValueError(f"padding={padding!r} must be 'same', 'valid' "
                         "or a symmetric pad >= 0")
    zp = jnp.asarray(x_zero_point, x_q.dtype)
    x_q = jax.lax.pad(x_q, zp, ((0, 0, 0), (*ph, 0), (*pw, 0), (0, 0, 0)))
    acc = jax.lax.conv_general_dilated(
        x_q.astype(jnp.int32), w_q.astype(jnp.int32),
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=feature_group_count)
    scale, bias_q = dequant_params(w_q, w_scale, x_scale, x_zero_point,
                                   bias)
    y = (acc + bias_q).astype(jnp.float32) * scale
    return epilogue(y, None, activation)


def conv2d_grads(x: jax.Array, w: jax.Array, gy: jax.Array, *,
                 stride: int = 1, padding: str | int = "same",
                 feature_group_count: int = 1) -> tuple:
    """Canonical (dx, dw) oracle: ``jax.vjp`` on the XLA convolution.

    Every kernel gradient test compares against this single source —
    the same ``lax.conv_general_dilated`` the forward oracle wraps.
    """
    def f(x, w):
        return jax.lax.conv_general_dilated(
            x, w, window_strides=(stride, stride),
            padding=lax_padding(padding),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=feature_group_count)
    _, vjp = jax.vjp(f, x, w)
    return vjp(gy)


def conv2d_input_grad(x: jax.Array, w: jax.Array, gy: jax.Array, *,
                      stride: int = 1, padding: str | int = "same",
                      feature_group_count: int = 1) -> jax.Array:
    """Input cotangent of the conv2d oracle."""
    return conv2d_grads(x, w, gy, stride=stride, padding=padding,
                        feature_group_count=feature_group_count)[0]


def conv2d_weight_grad(x: jax.Array, w: jax.Array, gy: jax.Array, *,
                       stride: int = 1, padding: str | int = "same",
                       feature_group_count: int = 1) -> jax.Array:
    """Weight cotangent of the conv2d oracle."""
    return conv2d_grads(x, w, gy, stride=stride, padding=padding,
                        feature_group_count=feature_group_count)[1]


def depthwise_conv1d(x: jax.Array, w: jax.Array) -> jax.Array:
    """Causal depthwise conv1d oracle (Mamba / RG-LRU temporal conv).

    x: (B, L, D); w: (K, D).  y[b, t, d] = sum_k x[b, t-K+1+k, d] * w[k, d].
    """
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(k))


def depthwise_conv1d_step(state: jax.Array, x_t: jax.Array, w: jax.Array):
    """Single decode step.  state: (B, K-1, D) trailing inputs; x_t: (B, D).

    Returns (new_state, y_t).  The state is the decode-time image of the
    shadow registers: the K-1 values carried across step boundaries.
    """
    window = jnp.concatenate([state, x_t[:, None, :]], axis=1)  # (B, K, D)
    y_t = jnp.einsum("bkd,kd->bd", window, w)
    return window[:, 1:, :], y_t


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, logits_soft_cap: float | None = None,
              window: int | None = None) -> jax.Array:
    """Dense attention oracle with GQA.

    q: (B, Lq, Hq, D); k/v: (B, Lk, Hkv, D); Hq % Hkv == 0.
    ``window``: optional local-attention span (RecurrentGemma).
    """
    b, lq, hq, d = q.shape
    _, lk, hkv, _ = k.shape
    group = hq // hkv
    qg = q.reshape(b, lq, hkv, group, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / jnp.sqrt(d).astype(q.dtype)
    if logits_soft_cap is not None:
        logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
    q_pos = jnp.arange(lq)[:, None] + (lk - lq)
    k_pos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
    logits = jnp.where(mask, logits.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, lq, hq, d)


def matmul(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.dot(x, y, preferred_element_type=jnp.float32).astype(x.dtype)
