"""Conv autotuner tests: cache round-trip determinism, model-guided and
measured search, the ops.conv2d consultation path, and the packed-params
layer wiring (DESIGN.md §4).

The autouse conftest fixture points the cache at a per-test temp file, so
everything here is hermetic.
"""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune
from repro.core.conv_plan import KERNEL_VMEM_BUDGET
from repro.kernels import ops, ref
from repro.models import layers
from repro.models.base import init_params

RNG = np.random.default_rng(5)

X_SHAPE = (1, 16, 16, 8)
W_SHAPE = (3, 3, 8, 12)


def _allclose(a, b, tol=2e-3):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = float(np.abs(b).max()) + 1e-6
    assert float(np.abs(a - b).max()) / scale < tol


# ---------------------------------------------------------------------------
# Cache round trip + determinism
# ---------------------------------------------------------------------------

def test_tune_round_trip_is_deterministic():
    rec1 = autotune.tune(X_SHAPE, W_SHAPE, stride=1, pad=0)
    rec2 = autotune.tune(X_SHAPE, W_SHAPE, stride=1, pad=0)
    assert rec1 == rec2                       # same inputs, same winner
    key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0)
    assert autotune.lookup(key) == rec1
    # survives dropping the in-process memo: read back from the JSON file
    autotune.reset_memory_cache()
    assert autotune.lookup(key) == rec1
    # and the on-disk schema is what DESIGN.md documents
    with open(autotune.cache_path()) as f:
        data = json.load(f)
    assert data["version"] == 1
    assert data["entries"][key]["tile_h"] == rec1["tile_h"]
    assert rec1["dataflow"] in autotune.DATAFLOWS
    assert rec1["source"] == "model"


def test_store_overwrites_and_persists_atomically():
    key = "conv2d:test"
    autotune.store(key, dict(tile_h=4, tile_cout=8, dataflow="carry"))
    autotune.store(key, dict(tile_h=8, tile_cout=8, dataflow="halo"))
    autotune.reset_memory_cache()
    assert autotune.lookup(key)["tile_h"] == 8
    assert not os.path.exists(autotune.cache_path() + ".tmp")


def test_lookup_missing_cache_returns_none():
    assert autotune.lookup("conv2d:absent") is None
    assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None


def test_knobs_for_validates_records_and_env_kill_switch(monkeypatch):
    key = autotune.make_key(X_SHAPE, W_SHAPE, stride=2, pad=0)
    # invalid: tile_h not a stride multiple -> rejected, not crashed
    autotune.store(key, dict(tile_h=3, tile_cout=8, dataflow="carry"))
    assert autotune.knobs_for(X_SHAPE, W_SHAPE, stride=2) is None
    autotune.store(key, dict(tile_h=4, tile_cout=8, dataflow="halo"))
    assert autotune.knobs_for(X_SHAPE, W_SHAPE, stride=2)["tile_h"] == 4
    monkeypatch.setenv(autotune.AUTOTUNE_ENV, "0")
    assert autotune.knobs_for(X_SHAPE, W_SHAPE, stride=2) is None


# ---------------------------------------------------------------------------
# Robustness (DESIGN.md §9): concurrent stores, quarantine, validation
# ---------------------------------------------------------------------------

_STRESS_WORKER = r"""
import sys
from repro.core import autotune
path, wid, n = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
for i in range(n):
    autotune.store(f"conv2d:w{wid}:e{i}",
                   dict(tile_h=4, tile_cout=8, dataflow="carry",
                        worker=wid, i=i), path)
print("done", wid)
"""


def test_concurrent_store_loses_no_entries(tmp_path):
    """ISSUE 7 acceptance: N>=4 processes hammering one cache path
    concurrently retain 100% of their entries — the .lock sidecar +
    read-merge-replace store closes the lost-update race."""
    import subprocess
    import sys
    n_proc, n_entries = 4, 30
    path = str(tmp_path / "convtune.json")
    env = dict(os.environ, PYTHONPATH="src")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _STRESS_WORKER, path, str(w),
         str(n_entries)],
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for w in range(n_proc)]
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, err
    autotune.reset_memory_cache()
    with open(path) as f:
        entries = json.load(f)["entries"]
    want = {f"conv2d:w{w}:e{i}" for w in range(n_proc)
            for i in range(n_entries)}
    missing = want - set(entries)
    assert not missing, f"lost {len(missing)}/{len(want)}: " \
                        f"{sorted(missing)[:5]}..."
    # and each record survived byte-for-byte (merge never mangles)
    assert entries["conv2d:w0:e0"]["worker"] == 0


@pytest.mark.parametrize("mode", ["truncate", "garbage", "wrong_version",
                                  "empty"])
def test_corrupt_cache_is_quarantined_not_reset(tmp_path, mode):
    """An unreadable (or unknown-schema) cache is renamed to
    convtune.json.corrupt-<pid> with a warning — preserved for
    inspection, never silently discarded — and reads as empty."""
    from repro.testing import faults
    path = str(tmp_path / "convtune.json")
    autotune.store("conv2d:x", dict(tile_h=4, tile_cout=8,
                                    dataflow="carry"), path)
    faults.corrupt_cache(path, mode)
    autotune.reset_memory_cache()
    with pytest.warns(RuntimeWarning, match="quarantined"):
        assert autotune.lookup("conv2d:x", path) is None
    quarantined = [f.name for f in tmp_path.iterdir()
                   if ".corrupt-" in f.name]
    assert len(quarantined) == 1
    assert not os.path.exists(path)
    # the cache restarts cleanly after quarantine
    autotune.reset_memory_cache()
    autotune.store("conv2d:y", dict(tile_h=2, tile_cout=4,
                                    dataflow="halo"), path)
    autotune.reset_memory_cache()
    assert autotune.lookup("conv2d:y", path)["tile_h"] == 2


def test_wrong_version_quarantine_names_the_version(tmp_path):
    """A future schema version is quarantined with the version in the
    warning (migrate-or-quarantine, never silent discard)."""
    path = str(tmp_path / "convtune.json")
    with open(path, "w") as f:
        json.dump({"version": 999, "entries": {"k": {}}}, f)
    with pytest.warns(RuntimeWarning, match="999"):
        assert autotune.lookup("k", path) is None
    # the quarantined file still holds the original document
    (q,) = [f for f in tmp_path.iterdir() if ".corrupt-" in f.name]
    with open(q) as f:
        assert json.load(f)["version"] == 999


def test_missing_cache_file_is_not_quarantine(tmp_path, recwarn):
    """A cache that never existed is an empty cache — no warning, no
    .corrupt file (quarantine is for corruption, not first run)."""
    path = str(tmp_path / "nonexistent.json")
    assert autotune.lookup("k", path) is None
    assert not [w for w in recwarn.list
                if "quarantined" in str(w.message)]
    assert not list(tmp_path.iterdir())


def test_malformed_record_warns_once_and_misses():
    """A truncated/hand-edited record is a miss + ONE warning, not a
    KeyError in the dispatch path and not a warning per conv call."""
    key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0)
    autotune.store(key, dict(tile_cout=8, dataflow="carry"))  # no tile_h
    with pytest.warns(RuntimeWarning, match="malformed"):
        assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None
    # warn-once: subsequent lookups are silent misses
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None
    # conv2d dispatch degrades to the default plan instead of crashing
    x = jnp.asarray(RNG.standard_normal((1, 14, 14, 8)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(W_SHAPE) * .3, jnp.float32)
    _allclose(ops.conv2d(x, w), ref.conv2d(x, w))


def test_geometry_insane_record_is_rejected():
    """Structurally valid knobs that cannot build a ConvPlan for the
    problem (e.g. tile_cout way past the per-group C_out after a shape
    edit) are a miss + warning, not a crash inside the kernel."""
    key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0)
    autotune.store(key, dict(tile_h=4, tile_cout=10 ** 6,
                             dataflow="carry"))
    with pytest.warns(RuntimeWarning, match="infeasible"):
        assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------

def test_candidates_cover_both_dataflows_and_fit_vmem():
    plans = autotune.candidate_knobs(X_SHAPE, W_SHAPE)
    assert {p.dataflow for p in plans} == set(autotune.DATAFLOWS)
    assert all(p.vmem_resident_bytes <= KERNEL_VMEM_BUDGET for p in plans)
    # the full-height strip (one grid step along H) is always a candidate
    assert any(p.g_tiles == 1 for p in plans)


def test_measured_tune_records_wall_clock():
    rec = autotune.tune((1, 8, 8, 4), (3, 3, 4, 4), measure=True,
                        measure_top_k=2, write=False)
    assert rec["source"] == "measured"
    assert rec["measured_us"] > 0


# ---------------------------------------------------------------------------
# ops.conv2d consults the cache
# ---------------------------------------------------------------------------

def test_conv2d_uses_cached_knobs(monkeypatch):
    x = jnp.asarray(RNG.standard_normal((1, 14, 14, 8)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(W_SHAPE) * .3, jnp.float32)
    # 'same' K=3 s=1 pre-pads to 16x16; that's the key conv2d looks up
    key = autotune.make_key((1, 16, 16, 8), W_SHAPE, stride=1, pad=0)
    autotune.store(key, dict(tile_h=6, tile_cout=4, dataflow="halo",
                             source="model"))

    seen = {}
    real = ops.trim_conv2d

    def spy(*args, **kw):
        seen.update(kw)
        return real(*args, **kw)

    monkeypatch.setattr(ops, "trim_conv2d", spy)
    got = ops.conv2d(x, w)
    assert (seen["tile_h"], seen["tile_cout"], seen["dataflow"]) \
        == (6, 4, "halo")
    _allclose(got, ref.conv2d(x, w))
    # explicit knobs win over the cache
    seen.clear()
    ops.conv2d(x, w, tile_h=8, dataflow="carry")
    assert (seen["tile_h"], seen["tile_cout"], seen["dataflow"]) \
        == (8, 4, "carry")
    # kill switch restores the plan defaults
    seen.clear()
    ops.conv2d(x, w, use_autotune_cache=False)
    assert (seen["tile_h"], seen["dataflow"]) == (None, "carry")


@pytest.mark.parametrize("lname", ["pw1", "dw2"])
def test_hillclimb_write_cache_feeds_conv2d(lname):
    """The sweep->cache->conv2d loop: benchmarks/hillclimb.py --conv
    --write-cache stores a record under the exact key ops.conv2d looks
    up — including the stride-2 'same' case where the kernel-seen
    pre-pad is asymmetric (dw2: 112 -> 113 rows, not the layer's
    symmetric 114)."""
    import importlib
    hillclimb = importlib.import_module("benchmarks.hillclimb")
    res = hillclimb.conv_hillclimb(f"mobilenet:{lname}",
                                   ("carry", "halo"), write_cache=True)
    assert res["best"] is not None
    rec = autotune.lookup(res["cache_key"])
    assert rec["tile_h"] == res["best"]["tile_h"]
    # the stored key is found through the exact lookup ops.conv2d does
    from repro.core import mobilenet_layers
    layer = [l for l in mobilenet_layers() if l.name == lname][0]
    w_shape = (layer.kernel, layer.kernel,
               layer.in_channels // layer.groups, layer.out_channels)
    x_shape, pad = ops.kernel_input_shape(
        (1, layer.ifmap, layer.ifmap, layer.in_channels), layer.kernel,
        layer.stride, "same" if layer.padding else "valid")
    got = autotune.knobs_for(x_shape, w_shape, stride=layer.stride,
                             pad=pad, groups=layer.groups)
    assert got == rec


# ---------------------------------------------------------------------------
# Packed layer params (models/layers.py wiring)
# ---------------------------------------------------------------------------

def test_conv2d_pack_params_matches_unpacked():
    import jax
    p = init_params(layers.conv2d_params(3, 8, 12),
                    jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.standard_normal((1, 12, 12, 8)), jnp.float32)
    want = layers.conv2d_apply(p, x, activation="relu")
    packed = layers.conv2d_pack_params(p, x_shape=x.shape)
    got = layers.conv2d_apply(packed, x, activation="relu")
    _allclose(got, want, tol=1e-6)


def test_depthwise_separable_pack_matches_unpacked():
    import jax
    p = init_params(layers.depthwise_separable_params(3, 8, 16),
                    jax.random.PRNGKey(1))
    x = jnp.asarray(RNG.standard_normal((1, 10, 10, 8)), jnp.float32)
    want = layers.depthwise_separable_apply(p, x, stride=2)
    packed = layers.depthwise_separable_pack_params(p, x_shape=x.shape,
                                                    stride=2)
    got = layers.depthwise_separable_apply(packed, x, stride=2)
    _allclose(got, want, tol=1e-6)


# ---------------------------------------------------------------------------
# Backward shapes (DESIGN.md §5): keys, tuning, and the bwd consultation
# ---------------------------------------------------------------------------

def test_backward_keys_never_collide_with_forward():
    """The weight-grad record is op-namespaced, and the input-grad conv's
    key is the transformed problem's own conv2d key — even a forward
    problem with the *identical* raw shape tuple gets a different key
    than the wgrad record, and writing one never shadows the other."""
    fwd_key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0)
    wgrad_key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0,
                                  op="conv2d_wgrad")
    assert fwd_key != wgrad_key
    assert fwd_key.startswith("conv2d:")
    assert wgrad_key.startswith("conv2d_wgrad:")
    autotune.store(fwd_key, dict(tile_h=8, tile_cout=4, dataflow="carry"))
    autotune.store(wgrad_key, dict(tile_go=2, tile_cout=3))
    assert autotune.lookup(fwd_key)["tile_h"] == 8
    assert autotune.lookup(wgrad_key)["tile_go"] == 2
    # the input-grad conv of this problem keys a *different* conv2d shape
    from repro.core.conv_plan import input_grad_geometry
    geo = input_grad_geometry(X_SHAPE, W_SHAPE, stride=1, pad=0)
    ig_key = autotune.make_key(geo["g_padded_shape"], geo["wt_shape"],
                               stride=1, pad=0)
    assert ig_key != fwd_key


def test_tune_backward_round_trip():
    """tune_backward persists both records into the hermetic per-test
    cache and they read back through the validated lookups."""
    recs = autotune.tune_backward(X_SHAPE, W_SHAPE, stride=1, pad=0)
    assert set(recs) == {"input_grad", "weight_grad"}
    assert recs["weight_grad"]["tile_go"] >= 1
    wrec = autotune.weight_grad_knobs_for(X_SHAPE, W_SHAPE, stride=1,
                                          pad=0)
    assert wrec == recs["weight_grad"]
    from repro.core.conv_plan import input_grad_geometry
    geo = input_grad_geometry(X_SHAPE, W_SHAPE, stride=1, pad=0)
    irec = autotune.knobs_for(geo["g_padded_shape"], geo["wt_shape"],
                              stride=1, pad=0)
    assert irec == recs["input_grad"]
    # survives dropping the in-process memo (on-disk round trip)
    autotune.reset_memory_cache()
    assert autotune.weight_grad_knobs_for(X_SHAPE, W_SHAPE) == wrec
    # malformed wgrad records are rejected, not trusted
    autotune.store(autotune.make_key(X_SHAPE, W_SHAPE,
                                     op="conv2d_wgrad"),
                   dict(tile_go="bad", tile_cout=1))
    assert autotune.weight_grad_knobs_for(X_SHAPE, W_SHAPE) is None


# ---------------------------------------------------------------------------
# Sharded keys (DESIGN.md §6): conv2d_shard:<ndev> namespacing
# ---------------------------------------------------------------------------

def test_sharded_keys_never_alias_single_device():
    """Sharded records are namespaced by the full shard grid: the same
    raw shape tuple under different (batch x spatial) splits — even
    splits with the same device count — and the single-device path are
    all distinct keys, and writing any one never shadows the others."""
    # batch 8 so every split below is geometry-feasible (the consult-site
    # validation rejects records whose shard grid cannot divide the
    # problem — see test_geometry_insane_record_is_rejected)
    xb = (8, 16, 16, 8)
    fwd_key = autotune.make_key(xb, W_SHAPE, stride=1, pad=0)
    splits = [(1, 1), (1, 4), (4, 1), (1, 8), (8, 1), (2, 4)]
    keys = {grid: autotune.make_key(xb, W_SHAPE, stride=1, pad=0,
                                    op=autotune.sharded_key_op(*grid))
            for grid in splits}
    assert len({fwd_key, *keys.values()}) == len(splits) + 1
    for (bs, ss), key in keys.items():
        assert key.startswith(f"conv2d_shard:{bs * ss}:")
    autotune.store(fwd_key, dict(tile_h=8, tile_cout=4, dataflow="carry"))
    for i, ((bs, ss), key) in enumerate(keys.items()):
        autotune.store(key, dict(tile_h=i + 1, tile_cout=2,
                                 dataflow="halo"))
    # each lookup sees only its own record — in particular the two
    # 8-device splits (8x1 data-parallel vs 1x8 spatial) never alias
    assert autotune.knobs_for(xb, W_SHAPE)["tile_h"] == 8
    for i, (bs, ss) in enumerate(splits):
        got = autotune.sharded_knobs_for(xb, W_SHAPE,
                                         batch_shards=bs,
                                         spatial_shards=ss)
        assert (got["tile_h"], got["dataflow"]) == (i + 1, "halo")
    assert autotune.sharded_knobs_for(xb, W_SHAPE,
                                      spatial_shards=2) is None
    # malformed sharded records are rejected, not trusted
    autotune.store(keys[(1, 4)], dict(tile_h="bad", tile_cout=2,
                                      dataflow="halo"))
    assert autotune.sharded_knobs_for(xb, W_SHAPE,
                                      spatial_shards=4) is None


def test_tune_sharded_round_trip():
    """tune_sharded persists under the shard-grid key and reads back
    through the validated lookup (surviving the in-process memo)."""
    rec = autotune.tune_sharded(X_SHAPE, W_SHAPE, spatial_shards=4)
    assert rec["dataflow"] in autotune.DATAFLOWS
    assert rec["tile_h"] >= 1 and rec["tile_cout"] >= 1
    got = autotune.sharded_knobs_for(X_SHAPE, W_SHAPE, spatial_shards=4)
    assert got == rec
    autotune.reset_memory_cache()
    assert autotune.sharded_knobs_for(X_SHAPE, W_SHAPE,
                                      spatial_shards=4) == rec
    # a different mesh size — or a different split of the same size —
    # is a different problem
    assert autotune.sharded_knobs_for(X_SHAPE, W_SHAPE,
                                      spatial_shards=8) is None
    assert autotune.sharded_knobs_for(X_SHAPE, W_SHAPE,
                                      batch_shards=4) is None
    rec2 = autotune.tune_sharded(X_SHAPE, W_SHAPE, batch_shards=1,
                                 spatial_shards=1)
    assert autotune.sharded_knobs_for(X_SHAPE, W_SHAPE) == rec2
    # ... and never pollutes the single-device lookup
    assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None


def test_conv2d_sharded_consults_namespaced_cache(monkeypatch):
    """ops.conv2d(..., mesh=) fills unset knobs from the
    conv2d_shard:<ndev> record of the global kernel-seen shape — and
    ignores the single-device record for the same shape."""
    import jax
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    x = jnp.asarray(RNG.standard_normal((1, 14, 14, 8)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(W_SHAPE) * .3, jnp.float32)
    # 'same' K=3 s=1 pre-pads to 16x16; the 1x1 grid on this tiny mesh
    autotune.store(autotune.make_key((1, 16, 16, 8), W_SHAPE, stride=1,
                                     pad=0,
                                     op=autotune.sharded_key_op(1, 1)),
                   dict(tile_h=6, tile_cout=4, dataflow="halo",
                        source="model"))
    autotune.store(autotune.make_key((1, 16, 16, 8), W_SHAPE, stride=1,
                                     pad=0),
                   dict(tile_h=2, tile_cout=12, dataflow="carry",
                        source="model"))

    seen = {}
    real = ops.trim_conv2d

    def spy(*args, **kw):
        seen.update(kw)
        return real(*args, **kw)

    monkeypatch.setattr(ops, "trim_conv2d", spy)
    got = ops.conv2d(x, w, mesh=mesh)
    assert (seen["tile_h"], seen["tile_cout"], seen["dataflow"]) \
        == (6, 4, "halo")
    _allclose(got, ref.conv2d(x, w))


def test_weight_grad_candidates_fit_vmem():
    plans = autotune.candidate_weight_grad_knobs(X_SHAPE, W_SHAPE)
    assert plans
    assert all(p.vmem_resident_bytes <= KERNEL_VMEM_BUDGET for p in plans)
    # the full-height cotangent strip (one sweep step per image) is
    # always a candidate
    assert any(p.go_tiles == 1 for p in plans)


def test_backward_pass_uses_cached_knobs(monkeypatch):
    """The conv backward consults both caches: the weight-grad kernel
    under its conv2d_wgrad key, the input-grad conv under the conv2d
    key of its transformed shapes."""
    import jax
    from repro.kernels import trim_conv2d as tc
    x = jnp.asarray(RNG.standard_normal(X_SHAPE), jnp.float32)
    w = jnp.asarray(RNG.standard_normal(W_SHAPE) * .3, jnp.float32)
    autotune.store(autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0,
                                     op="conv2d_wgrad"),
                   dict(tile_go=3, tile_cout=6))
    from repro.core.conv_plan import input_grad_geometry
    geo = input_grad_geometry(X_SHAPE, W_SHAPE, stride=1, pad=0)
    autotune.store(autotune.make_key(geo["g_padded_shape"],
                                     geo["wt_shape"], stride=1, pad=0),
                   dict(tile_h=5, tile_cout=4, dataflow="halo",
                        source="model"))

    seen = {}
    real_ig, real_wg = ops.trim_conv2d_input_grad, \
        ops.trim_conv2d_weight_grad

    def spy_ig(*a, **kw):
        seen["ig"] = kw
        return real_ig(*a, **kw)

    def spy_wg(*a, **kw):
        seen["wg"] = kw
        return real_wg(*a, **kw)

    monkeypatch.setattr(ops, "trim_conv2d_input_grad", spy_ig)
    monkeypatch.setattr(ops, "trim_conv2d_weight_grad", spy_wg)
    gx, gw = jax.grad(
        lambda x, w: (ops.conv2d(x, w, padding="valid") ** 2).sum(),
        argnums=(0, 1))(x, w)
    assert (seen["ig"]["tile_h"], seen["ig"]["tile_cout"],
            seen["ig"]["dataflow"]) == (5, 4, "halo")
    assert (seen["wg"]["tile_go"], seen["wg"]["tile_cout"]) == (3, 6)
    dx_ref, dw_ref = ref.conv2d_grads(
        x, w, 2 * ref.conv2d(x, w, padding="valid"), stride=1,
        padding="valid")
    _allclose(gx, dx_ref, tol=1e-5)
    _allclose(gw, dw_ref, tol=1e-5)


def test_packed_params_pick_up_cached_plan():
    """Pack-time cache consultation: a tuned record fixes the packed
    tile_cout and rides along as tile_h/dataflow hints."""
    key = autotune.make_key((1, 14, 14, 8), (3, 3, 8, 12),
                            stride=1, pad=0)
    autotune.store(key, dict(tile_h=4, tile_cout=6, dataflow="halo",
                             source="model"))
    w = jnp.asarray(RNG.standard_normal(W_SHAPE) * .3, jnp.float32)
    pk = ops.pack_conv2d_weights(w, x_shape=(1, 12, 12, 8))
    assert (pk.tile_cout, pk.tile_h, pk.dataflow) == (6, 4, "halo")
    x = jnp.asarray(RNG.standard_normal((1, 12, 12, 8)), jnp.float32)
    _allclose(ops.conv2d(x, pk), ref.conv2d(x, w))


# ---------------------------------------------------------------------------
# Fused-group keys (DESIGN.md §8): conv2d_fused:d<depth> namespacing
# ---------------------------------------------------------------------------

def test_fused_keys_never_alias_other_namespaces():
    """A fused-group record lives under conv2d_fused:d<depth>:... — it
    can never collide with the per-layer conv2d:/conv2d_wgrad:/
    conv2d_shard: keys of its own stages, and groups that share a
    leading stage stay distinct (depth + signature chain in the key)."""
    from repro.core.fuse_plan import build_group
    from repro.core.netplan import network_layers
    layers = network_layers("alexnet")[1:]        # conv2..conv5 (K<=5)
    g2 = build_group(layers[:2], 0)
    g4 = build_group(layers, 0)
    k2 = autotune.fused_key(g2.signature)
    k4 = autotune.fused_key(g4.signature)
    assert k2.startswith("conv2d_fused:d2:")
    assert k4.startswith("conv2d_fused:d4:")
    per_layer = {autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0, op=op)
                 for op in ("conv2d", "conv2d_wgrad",
                            autotune.sharded_key_op(1, 4))}
    assert len({k2, k4, *per_layer}) == 2 + len(per_layer)
    # batch and dtype are part of the problem
    assert autotune.fused_key(g2.signature, n=4) != k2
    assert autotune.fused_key(g2.signature, dtype="bfloat16") != k2
    # writing a fused record never shadows the others
    autotune.store(k2, dict(strip_rows=3, depth=2))
    autotune.store(k4, dict(strip_rows=7, depth=4))
    assert autotune.fused_knobs_for(g2.signature)["strip_rows"] == 3
    assert autotune.fused_knobs_for(g4.signature)["strip_rows"] == 7
    assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None
    # malformed fused records are rejected, not trusted
    autotune.store(k2, dict(strip_rows="bad"))
    assert autotune.fused_knobs_for(g2.signature) is None
    autotune.store(k2, dict(strip_rows=0))
    assert autotune.fused_knobs_for(g2.signature) is None


def test_tune_fused_round_trip():
    """tune_fused persists a VMEM-feasible strip height under the fused
    key; FusedGroupPlan.build(use_autotune_cache=True) then runs on the
    cached group knob (surviving the in-process memo)."""
    from repro.core.fuse_plan import FUSED_VMEM_BUDGET, FusedGroupPlan, \
        build_group
    from repro.core.netplan import infer_pools, network_layers
    layers = network_layers("alexnet")
    pools = list(infer_pools(layers))
    sub = layers[1:]                              # the fusable chain
    rec = autotune.tune_fused(sub, pools=pools[1:])
    assert rec["strip_rows"] >= 1 and rec["depth"] == len(sub)
    assert rec["source"] == "model"
    g = build_group(sub, 0, strip_rows=rec["strip_rows"], pools=pools[1:])
    assert g.vmem_resident_bytes <= FUSED_VMEM_BUDGET
    got = autotune.fused_knobs_for(g.signature)
    assert got == rec
    autotune.reset_memory_cache()
    assert autotune.fused_knobs_for(g.signature) == rec
    # the plan-level consumer: cached strip heights drive the partition
    plan = FusedGroupPlan.build("alexnet", use_autotune_cache=True)
    fused = [gg for gg in plan.groups if gg.fused]
    assert fused and fused[0].strip_rows == rec["strip_rows"]
    # REPRO_CONV_AUTOTUNE=0 disables the lookup
    os.environ[autotune.AUTOTUNE_ENV] = "0"
    try:
        assert autotune.fused_knobs_for(g.signature) is None
    finally:
        del os.environ[autotune.AUTOTUNE_ENV]


def test_tune_fused_network_sweep():
    """One record per depth>=2 group of the partition, each under its
    own conv2d_fused key."""
    recs = autotune.tune_fused_network("vgg16")
    assert recs, "vgg16 partition produced no fused groups"
    from repro.core.fuse_plan import FusedGroupPlan
    plan = FusedGroupPlan.build("vgg16")
    assert len(recs) == sum(1 for g in plan.groups if g.fused)
    keys = {r["key"] for r in recs.values()}
    assert len(keys) == len(recs)
    for r in recs.values():
        assert r["key"].startswith("conv2d_fused:")
        assert autotune.lookup(r["key"])["strip_rows"] == r["strip_rows"]


# ---------------------------------------------------------------------------
# Quantized keys (DESIGN.md §11): conv2d_q8 namespacing + dtype in the key
# ---------------------------------------------------------------------------

def test_q8_keys_never_alias_other_namespaces():
    """An int8 record lives under conv2d_q8:...:int8:... — the same raw
    shape tuple can never collide with the conv2d:/conv2d_wgrad:/
    conv2d_shard:/conv2d_fused: records of its own geometry, and dtype
    is part of *every* namespace's key (an f32 and an int8 tune of the
    identical problem are distinct records in the same namespace)."""
    q8_key = autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0,
                               dtype="int8", op="conv2d_q8")
    assert q8_key.startswith("conv2d_q8:")
    assert ":int8:" in q8_key
    others = {autotune.make_key(X_SHAPE, W_SHAPE, stride=1, pad=0, op=op)
              for op in ("conv2d", "conv2d_wgrad",
                         autotune.sharded_key_op(1, 4))}
    assert len({q8_key, *others}) == 1 + len(others)
    # dtype distinguishes records inside a namespace, not just across
    for op in ("conv2d", "conv2d_q8", "conv2d_wgrad"):
        assert autotune.make_key(X_SHAPE, W_SHAPE, op=op, dtype="int8") \
            != autotune.make_key(X_SHAPE, W_SHAPE, op=op, dtype="float32")
    # writing the q8 record never shadows the plain conv2d consult
    autotune.store(q8_key, dict(tile_h=4, tile_cout=6, dataflow="halo"))
    assert autotune.knobs_for(X_SHAPE, W_SHAPE) is None
    assert autotune.knobs_for(X_SHAPE, W_SHAPE, dtype="int8",
                              op="conv2d_q8")["tile_cout"] == 6
    # ... and the f32 record never leaks into the q8 consult
    autotune.store(autotune.make_key(X_SHAPE, W_SHAPE),
                   dict(tile_h=8, tile_cout=12, dataflow="carry"))
    got = autotune.knobs_for(X_SHAPE, W_SHAPE, dtype="int8",
                             op="conv2d_q8")
    assert (got["tile_h"], got["dataflow"]) == (4, "halo")
    # malformed q8 records are rejected, not trusted
    autotune.store(q8_key, dict(tile_h="bad", tile_cout=6,
                                dataflow="halo"))
    assert autotune.knobs_for(X_SHAPE, W_SHAPE, dtype="int8",
                              op="conv2d_q8") is None


# ---------------------------------------------------------------------------
# Serving prewarm (DESIGN.md §10): no cold tunes after prewarm_buckets
# ---------------------------------------------------------------------------

def _serving_topo(scale=8):
    from repro.core import network_layers, scale_layers
    return scale_layers(network_layers("alexnet"), scale)


def test_prewarm_buckets_covers_every_grid_shape(monkeypatch):
    """After ``prewarm_buckets``, every (layer, bucket) problem of the
    grid resolves through ``knobs_for`` without a single call into the
    tuner — the serving definition of "zero cold tunes"."""
    from repro.core.netplan import layer_kernel_problem
    from repro.kernels.ops import MAX_NATIVE_K
    topo = _serving_topo()
    buckets = (1, 2, 4)
    recs = autotune.prewarm_buckets(topo, buckets)
    assert sorted(recs) == [1, 2, 4]

    def cold(*a, **kw):                    # any tune call is a cold tune
        raise AssertionError(f"cold tune after prewarm: {a} {kw}")

    monkeypatch.setattr(autotune, "tune", cold)
    for b in buckets:
        for layer in topo:
            if layer.kernel > MAX_NATIVE_K:
                assert "skipped" in recs[b]["layers"][layer.name]
                continue
            x_shape, pad, w_shape, _ = layer_kernel_problem(layer, n=b)
            knobs = autotune.knobs_for(x_shape, w_shape,
                                       stride=layer.stride, pad=pad,
                                       groups=layer.groups)
            assert knobs is not None, (layer.name, b)
            assert knobs == {k: v for k, v in
                             recs[b]["layers"][layer.name].items()
                             if k in knobs}


def test_prewarm_buckets_fused_seeds_group_records():
    """``fused=True`` additionally sweeps the conv2d_fused group records
    per bucket, so the megakernel path is warm too."""
    topo = _serving_topo()
    recs = autotune.prewarm_buckets(topo, (1, 2), fused=True)
    for b in (1, 2):
        fused = recs[b]["fused"]
        assert fused, f"no fused groups recorded at bucket {b}"
        for r in fused.values():
            assert r["key"].startswith("conv2d_fused:")
            assert f":n{b}:" in r["key"] or b == 1
            assert autotune.lookup(r["key"]) is not None


def test_prewarm_buckets_dedups_and_validates():
    topo = _serving_topo()
    with pytest.raises(ValueError):
        autotune.prewarm_buckets(topo, (0, 2))
    recs = autotune.prewarm_buckets(topo, (2, 1, 2, 1))
    assert sorted(recs) == [1, 2]


_PREWARM_WORKER = r"""
import sys
from repro.core import autotune, network_layers, scale_layers
path = sys.argv[1]
topo = scale_layers(network_layers("alexnet"), 8)
autotune.prewarm_buckets(topo, (1, 2), path=path)
print("done")
"""


def test_concurrent_prewarm_merges_cleanly(tmp_path):
    """ISSUE 8: 4 serving replicas prewarming the same cache path at
    once (the multi-replica startup race) lose nothing — every record a
    solo prewarm would write is present after the concurrent ones merge
    through the flock+merge store."""
    import subprocess
    import sys
    path = str(tmp_path / "convtune.json")
    env = dict(os.environ, PYTHONPATH="src")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PREWARM_WORKER, path],
        env=env, cwd=os.path.join(os.path.dirname(__file__), ".."),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for _ in range(4)]
    for p in procs:
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, err

    # the expected key set: what a single prewarm would persist
    topo = _serving_topo()
    want = set()
    for per in autotune.prewarm_buckets(topo, (1, 2),
                                        write=False).values():
        want |= {r["key"] for r in per["layers"].values()
                 if "key" in r}
    with open(path) as f:
        entries = json.load(f)["entries"]
    missing = want - set(entries)
    assert not missing, f"lost {len(missing)}/{len(want)}: " \
                        f"{sorted(missing)[:5]}"
