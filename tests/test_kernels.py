"""Per-kernel allclose sweeps against the ref.py oracles (interpret mode),
including hypothesis property sweeps over shapes/dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.trim_conv2d import hbm_traffic_model

RNG = np.random.default_rng(7)


def _allclose(a, b, tol=2e-3):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(b))) + 1e-6
    assert float(jnp.max(jnp.abs(a - b))) / scale < tol


# ---------------------------------------------------------------------------
# trim_conv2d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dataflow", ["carry", "halo"])
@pytest.mark.parametrize("h,w,cin,cout,k,s,padding", [
    (8, 8, 4, 8, 3, 1, "same"),
    (14, 14, 16, 32, 3, 1, "same"),
    (17, 13, 3, 5, 3, 1, "valid"),
    (27, 27, 6, 8, 5, 1, "same"),
    (32, 32, 3, 4, 3, 2, "same"),
    (56, 56, 3, 4, 11, 4, "valid"),     # AlexNet conv1 (kernel tiling)
    (16, 16, 4, 4, 1, 1, "valid"),
    (12, 20, 5, 7, 7, 3, "valid"),
])
def test_conv2d_vs_oracle(h, w, cin, cout, k, s, padding, dataflow):
    x = jnp.asarray(RNG.standard_normal((2, h, w, cin)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((k, k, cin, cout)) * 0.2,
                     jnp.float32)
    got = ops.conv2d(x, wt, stride=s, padding=padding, impl="pallas",
                     dataflow=dataflow)
    want = ref.conv2d(x, wt, stride=s, padding=padding)
    assert got.shape == want.shape
    _allclose(got, want)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-3),
                                       (jnp.bfloat16, 3e-2)])
def test_conv2d_dtypes(dtype, tol):
    x = jnp.asarray(RNG.standard_normal((1, 12, 12, 8)), dtype)
    wt = jnp.asarray(RNG.standard_normal((3, 3, 8, 16)) * 0.2, dtype)
    _allclose(ops.conv2d(x, wt, impl="pallas"),
              ref.conv2d(x.astype(jnp.float32), wt.astype(jnp.float32)),
              tol)


@settings(max_examples=12, deadline=None)
@given(h=st.integers(6, 24), w=st.integers(6, 24), cin=st.integers(1, 8),
       cout=st.integers(1, 8), k=st.sampled_from([1, 3, 5]),
       s=st.sampled_from([1, 2]))
def test_conv2d_property(h, w, cin, cout, k, s):
    if h < k or w < k:
        return
    x = jnp.asarray(np.random.default_rng(0).standard_normal((1, h, w, cin)),
                    jnp.float32)
    wt = jnp.asarray(np.random.default_rng(1).standard_normal(
        (k, k, cin, cout)) * 0.3, jnp.float32)
    _allclose(ops.conv2d(x, wt, stride=s, padding="valid", impl="pallas"),
              ref.conv2d(x, wt, stride=s, padding="valid"))


def test_conv2d_tile_boundaries():
    """Strips + carry (or halo over-fetch) must agree with the oracle at
    every tile_h."""
    from repro.kernels.trim_conv2d import trim_conv2d
    x = jnp.asarray(RNG.standard_normal((1, 16, 10, 4)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((3, 3, 4, 8)) * 0.3, jnp.float32)
    want = ref.conv2d(x, wt, padding="valid")
    for th in (1, 2, 4, 8, 16):
        for df in ("carry", "halo"):
            _allclose(trim_conv2d(x, wt, tile_h=th, dataflow=df), want)


def test_layer_name_changes_nothing_computed():
    """The layer name only names the kernel: the same rows, and no
    guard event or memoized demotion."""
    from repro.core import guard
    x = jnp.asarray(RNG.standard_normal((2, 8, 8, 4)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((3, 3, 4, 8)) * 0.3, jnp.float32)
    plain = ops.conv2d(x, wt, activation="relu")
    named = ops.conv2d(x, wt, activation="relu", layer="conv7")
    assert np.array_equal(np.asarray(plain), np.asarray(named))
    assert guard.events() == [] and guard.demotions() == {}


# ---------------------------------------------------------------------------
# packed weights (load-time pad/reshape) vs the per-call path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("groups,cout,tile_cout,activation", [
    (1, 12, None, None),
    (1, 10, 4, "relu"),      # ragged cout tile: padded channels sliced off
    (4, 8, None, "gelu"),    # grouped
    (8, 16, 2, None),        # depthwise-ish with tiny tiles
])
def test_packed_weights_match_unpacked(groups, cout, tile_cout, activation):
    cin = 8
    x = jnp.asarray(RNG.standard_normal((2, 12, 11, cin)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, cin // groups, cout)) * .3,
                    jnp.float32)
    b = jnp.asarray(RNG.standard_normal((cout,)), jnp.float32)
    want = ops.conv2d(x, w, bias=b, activation=activation,
                      feature_group_count=groups)
    pk = ops.pack_conv2d_weights(w, b, groups=groups, tile_cout=tile_cout)
    got = ops.conv2d(x, pk, activation=activation)
    _allclose(got, want, tol=1e-6)
    for df in ("carry", "halo"):
        _allclose(ops.conv2d(x, pk, activation=activation, dataflow=df),
                  want, tol=1e-6)


def test_packed_weights_is_jit_transparent_pytree():
    """Packed params must survive jit boundaries: arrays are leaves, the
    tile knobs static."""
    x = jnp.asarray(RNG.standard_normal((1, 10, 10, 4)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 4, 6)) * .3, jnp.float32)
    pk = ops.pack_conv2d_weights(w, tile_cout=2)

    @jax.jit
    def fwd(x, pk):
        return ops.conv2d(x, pk, padding="valid")

    _allclose(fwd(x, pk), ref.conv2d(x, w, padding="valid"))
    leaves = jax.tree_util.tree_leaves(pk)
    assert all(hasattr(l, "shape") for l in leaves)


def test_pack_rejects_kernel_tiled_k():
    w = jnp.zeros((11, 11, 3, 4), jnp.float32)
    with pytest.raises(ValueError):
        ops.pack_conv2d_weights(w)


def test_hbm_traffic_model_shadow_vs_halo():
    """The kernel's traffic model mirrors the paper: 'trim' mode re-fetches
    K-1 halo rows per strip; '3dtrim' (carry) has zero overhead."""
    a = hbm_traffic_model(1, 224, 224, 64, 64, 3, tile_h=8, mode="3dtrim")
    b = hbm_traffic_model(1, 224, 224, 64, 64, 3, tile_h=8, mode="trim")
    assert a["overhead_pct"] == 0.0
    assert b["overhead_pct"] > 0
    assert b["input"] > a["input"]


# ---------------------------------------------------------------------------
# trim_conv1d
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,l,d,k", [(2, 16, 8, 4), (1, 100, 24, 4),
                                     (3, 7, 5, 2), (2, 33, 16, 3)])
def test_conv1d_vs_oracle(b, l, d, k):
    x = jnp.asarray(RNG.standard_normal((b, l, d)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((k, d)), jnp.float32)
    _allclose(ops.depthwise_conv1d(x, wt, impl="pallas"),
              ref.depthwise_conv1d(x, wt))


def test_conv1d_decode_step_equals_full():
    """The decode-time carry is the shadow-register state: stepping one
    token at a time reproduces the full convolution."""
    x = jnp.asarray(RNG.standard_normal((2, 10, 8)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((4, 8)), jnp.float32)
    full = ref.depthwise_conv1d(x, wt)
    state = jnp.zeros((2, 3, 8))
    for t in range(10):
        state, y = ops.depthwise_conv1d_step(state, x[:, t], wt)
        _allclose(y, full[:, t])


# ---------------------------------------------------------------------------
# attention (pallas flash + chunked jnp) vs dense oracle
# ---------------------------------------------------------------------------

CASES = [
    (2, 32, 32, 4, 2, 16, True, None, None),
    (1, 64, 64, 8, 8, 32, True, 30.0, None),
    (2, 17, 47, 4, 1, 16, True, None, None),
    (2, 32, 32, 4, 2, 16, False, None, None),
    (1, 64, 64, 4, 2, 16, True, None, 16),
    (2, 1, 40, 8, 2, 32, True, None, None),
]


@pytest.mark.parametrize("impl", ["pallas", "chunked", "chunked_unroll"])
@pytest.mark.parametrize("case", CASES)
def test_attention_vs_oracle(impl, case):
    b, lq, lk, hq, hkv, d, causal, cap, win = case
    q = jnp.asarray(RNG.standard_normal((b, lq, hq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, lk, hkv, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, lk, hkv, d)), jnp.float32)
    want = ref.attention(q, k, v, causal=causal, logits_soft_cap=cap,
                         window=win)
    got = ops.attention(q, k, v, causal=causal, soft_cap=cap, window=win,
                        impl=impl, chunk=16)
    _allclose(got, want)


def test_decode_attention_vs_oracle():
    b, lmax, hq, hkv, d = 2, 24, 4, 2, 16
    q = jnp.asarray(RNG.standard_normal((b, 1, hq, d)), jnp.float32)
    kc = jnp.asarray(RNG.standard_normal((b, lmax, hkv, d)), jnp.float32)
    vc = jnp.asarray(RNG.standard_normal((b, lmax, hkv, d)), jnp.float32)
    clen = 17
    want = ref.attention(q, kc[:, :clen], vc[:, :clen], causal=True)
    _allclose(ops.decode_attention(q, kc, vc, jnp.full((b,), clen)), want)


@settings(max_examples=10, deadline=None)
@given(lq=st.integers(1, 40), lk_extra=st.integers(0, 40),
       hkv=st.sampled_from([1, 2, 4]), group=st.sampled_from([1, 2, 3]),
       causal=st.booleans())
def test_attention_property(lq, lk_extra, hkv, group, causal):
    lk = lq + lk_extra
    b, d = 1, 8
    hq = hkv * group
    rng = np.random.default_rng(lq * 100 + lk)
    q = jnp.asarray(rng.standard_normal((b, lq, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, lk, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, lk, hkv, d)), jnp.float32)
    want = ref.attention(q, k, v, causal=causal)
    _allclose(ops.attention(q, k, v, causal=causal, impl="chunked",
                            chunk=8), want)
