"""Chaos suite: injected faults through the guarded execution stack
(DESIGN.md §9).

Every test asserts two things about a fallback edge: the demoted result
still matches the ``ref`` oracle (1e-5), and ``guard.events()`` records
exactly the expected demotions — once per problem, never per call.

Run in CI with ``REPRO_CONV_GUARD=1`` (the chaos job step); the numerics
tests set the env themselves so the suite is self-contained.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import guard
from repro.kernels import ops, ref
from repro.testing import faults
from repro.testing.faults import InjectedFault

pytestmark = pytest.mark.chaos

RNG = np.random.default_rng(11)


def _conv_inputs(n=1, h=12, w=12, cin=8, cout=12, k=3):
    x = jnp.asarray(RNG.standard_normal((n, h, w, cin)), jnp.float32)
    wt = jnp.asarray(RNG.standard_normal((k, k, cin, cout)) * .3,
                     jnp.float32)
    return x, wt


def _allclose(a, b, tol=1e-5):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape, (a.shape, b.shape)
    scale = float(np.abs(b).max()) + 1e-6
    assert float(np.abs(a - b).max()) / scale < tol


# ---------------------------------------------------------------------------
# Single fallback edges
# ---------------------------------------------------------------------------

@pytest.mark.guard_events
def test_pallas_failure_demotes_to_ref():
    x, w = _conv_inputs()
    want = ref.conv2d(x, w, bias=jnp.ones(12), activation="relu")
    with faults.lowering_failure("pallas") as fault:
        got = ops.conv2d(x, w, bias=jnp.ones(12), activation="relu",
                         layer="conv_t")
    _allclose(got, want)
    assert fault.calls == 1
    (ev,) = guard.events()
    assert (ev["tier"], ev["to"], ev["kind"]) == ("pallas", "ref", "error")
    assert ev["layer"] == "conv_t"
    assert "InjectedFault" in ev["error"]


def test_demotion_is_memoized_once_per_problem():
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    with faults.lowering_failure("pallas") as fault:
        for _ in range(3):                  # same problem three times
            _allclose(ops.conv2d(x, w), want)
    # the broken tier was attempted exactly once; one event total
    assert fault.calls == 1
    assert len(guard.events()) == 1
    # even after the fault is gone, the memo keeps routing to ref
    # (a broken tier stays broken for the life of the process)
    _allclose(ops.conv2d(x, w), want)
    assert len(guard.events()) == 1
    # a *different* problem is its own key: re-attempted, new event
    x2, w2 = _conv_inputs(h=16, w=16)
    with faults.lowering_failure("pallas"):
        _allclose(ops.conv2d(x2, w2), ref.conv2d(x2, w2))
    assert len(guard.events()) == 2
    # reset() clears the memo: the (now healthy) tier runs again
    guard.reset()
    _allclose(ops.conv2d(x, w), want)
    assert guard.events() == []


@pytest.mark.guard_events
def test_packed_weights_failure_demotes_to_ref():
    x, w = _conv_inputs()
    pk = ops.pack_conv2d_weights(w, jnp.ones(12))
    want = ref.conv2d(x, w, bias=jnp.ones(12), activation="relu")
    with faults.lowering_failure("pallas") as fault:
        got = ops.conv2d(x, pk, activation="relu")
    _allclose(got, want)
    assert fault.calls == 1
    (ev,) = guard.events()
    assert ev["key"].startswith("conv2d_packed:")
    assert (ev["tier"], ev["to"]) == ("pallas", "ref")


@pytest.mark.guard_events
def test_sharded_failure_demotes_to_pallas():
    from repro.launch.mesh import make_conv_mesh
    mesh = make_conv_mesh(1, 1)
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    with faults.lowering_failure("sharded") as fault:
        got = ops.conv2d(x, w, mesh=mesh)
    _allclose(got, want)
    assert fault.calls == 1
    (ev,) = guard.events()
    assert (ev["tier"], ev["to"], ev["kind"]) \
        == ("sharded", "pallas", "error")


@pytest.mark.guard_events
def test_sharded_and_pallas_failures_demote_to_ref():
    from repro.launch.mesh import make_conv_mesh
    mesh = make_conv_mesh(1, 1)
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    with faults.lowering_failure("sharded"), \
            faults.lowering_failure("pallas"):
        got = ops.conv2d(x, w, mesh=mesh)
    _allclose(got, want)
    tiers = [(e["tier"], e["to"]) for e in guard.events()]
    assert tiers == [("sharded", "pallas"), ("pallas", "ref")]


@pytest.mark.guard_events
def test_depthwise_conv_failure_demotes_to_ref():
    x = jnp.asarray(RNG.standard_normal((1, 10, 10, 6)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((3, 3, 1, 6)) * .3, jnp.float32)
    want = ref.conv2d(x, w, feature_group_count=6)
    with faults.lowering_failure("pallas"):
        got = ops.depthwise_conv2d(x, w, layer="dw")
    _allclose(got, want)
    (ev,) = guard.events()
    assert ev["layer"] == "dw" and ":g6:" in ev["key"]


@pytest.mark.guard_events
def test_fused_group_failure_demotes_to_per_layer():
    """A fused-megakernel failure falls back to the per-layer path and
    stays bit-identical to the unfused forward."""
    from repro.core.model import ConvLayer
    from repro.models import layers as L
    from repro.models.base import init_params
    net = [ConvLayer("c0", 12, 3, 4, 3, 1, 1),
           ConvLayer("c1", 12, 4, 6, 3, 1, 1),      # pool 2/2 -> 6
           ConvLayer("c2", 6, 6, 8, 3, 1, 1)]
    p = init_params(L.cnn_params_from_layers(net),
                    jax.random.PRNGKey(0))
    x = jnp.asarray(RNG.standard_normal((2, 12, 12, 3)), jnp.float32)
    want = L.cnn_apply_from_layers(p, net, x)       # per-layer pallas
    with faults.lowering_failure("fused") as fault:
        got = L.cnn_apply_from_layers(p, net, x, fused=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    evs = [e for e in guard.events() if e["tier"] == "fused"]
    assert evs and fault.calls == len(evs)   # one attempt per group
    for ev in evs:
        assert ev["to"] == "pallas" and ev["key"].startswith("fused:")
        assert ".." in ev["layer"]           # "convA..convB" group label


# ---------------------------------------------------------------------------
# Acceptance: full VGG-16 forward under compound injected failures
# ---------------------------------------------------------------------------

@pytest.mark.guard_events
def test_vgg16_forward_survives_fused_and_pallas_failures():
    """ISSUE 7 acceptance: with BOTH the fused megakernels and the
    per-layer Pallas kernels broken, a full VGG-16 forward completes via
    demotion, matches the ref oracle at 1e-5, and every demotion appears
    exactly once in guard.events()."""
    from repro.core.fuse_plan import FusedGroupPlan
    from repro.core.netplan import network_layers
    from repro.models import layers as L
    from repro.models.base import init_params
    net = network_layers("vgg16")
    p = init_params(L.cnn_params_from_layers(net, n_classes=10),
                    jax.random.PRNGKey(1))
    x = jnp.asarray(RNG.standard_normal((1, 224, 224, 3)), jnp.float32)
    want = L.cnn_apply_from_layers(p, net, x, impl="ref")
    with faults.lowering_failure("fused"), faults.lowering_failure("pallas"):
        got = L.cnn_apply_from_layers(p, net, x, fused=True)
    _allclose(got, want, tol=1e-5)

    evs = guard.events()
    # every demotion appears exactly once: no duplicate (tier, key)
    pairs = [(e["tier"], e["key"]) for e in evs]
    assert len(pairs) == len(set(pairs))
    # fused demotions: one per fused (depth>=2) group of the plan
    plan = FusedGroupPlan.build(net, n=1)
    n_fused_groups = sum(1 for g in plan.groups if g.fused)
    assert sum(1 for e in evs if e["tier"] == "fused") == n_fused_groups
    # pallas demotions: one per distinct per-layer conv problem
    pallas_keys = {e["key"] for e in evs if e["tier"] == "pallas"}
    assert sum(1 for e in evs if e["tier"] == "pallas") == len(pallas_keys)
    assert all(e["to"] == "ref" for e in evs if e["tier"] == "pallas")
    # VGG-16 has 13 convs but repeated blocks share problems; every
    # distinct problem demoted at most once and at least one per stage
    assert 5 <= len(pallas_keys) <= 13


# ---------------------------------------------------------------------------
# Numerics guard (REPRO_CONV_GUARD=1)
# ---------------------------------------------------------------------------

@pytest.mark.guard_events
def test_nan_poison_demotes_with_numerics_guard(monkeypatch):
    monkeypatch.setenv(guard.GUARD_ENV, "1")
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    with faults.nan_poison("pallas") as fault:
        got = ops.conv2d(x, w, layer="poisoned_layer")
    assert fault.calls == 1
    _allclose(got, want)
    assert np.isfinite(np.asarray(got)).all()
    (ev,) = guard.events()
    assert (ev["tier"], ev["to"], ev["kind"]) \
        == ("pallas", "ref", "numerics")
    assert ev["layer"] == "poisoned_layer"
    assert "NaN" in ev["error"]


def test_nan_poison_passes_through_without_guard(monkeypatch):
    """Off by default: the numerics check costs a device sync per conv,
    so NaN propagates unless REPRO_CONV_GUARD=1 opted in."""
    monkeypatch.delenv(guard.GUARD_ENV, raising=False)
    x, w = _conv_inputs()
    with faults.nan_poison("pallas"):
        got = ops.conv2d(x, w)
    assert np.isnan(np.asarray(got)).any()
    assert guard.events() == []


def test_numerics_guard_inert_under_jit(monkeypatch):
    """Under a jit trace the tier output is a tracer — the finite check
    cannot run and must pass through, not crash on bool(tracer)."""
    monkeypatch.setenv(guard.GUARD_ENV, "1")
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    got = jax.jit(lambda x, w: ops.conv2d(x, w))(x, w)
    _allclose(got, want)
    assert guard.events() == []


@pytest.mark.guard_events
def test_lowering_failure_demotes_inside_jit_trace():
    """A tier that raises at trace time demotes within the jit trace —
    the compiled function is the fallback tier's."""
    x, w = _conv_inputs()
    want = ref.conv2d(x, w)
    with faults.lowering_failure("pallas") as fault:
        got = jax.jit(lambda x, w: ops.conv2d(x, w))(x, w)
    _allclose(got, want)
    assert fault.calls == 1
    (ev,) = guard.events()
    assert (ev["tier"], ev["to"]) == ("pallas", "ref")


# ---------------------------------------------------------------------------
# Strict mode + guard internals
# ---------------------------------------------------------------------------

def test_strict_mode_restores_crash_semantics(monkeypatch):
    monkeypatch.setenv(guard.STRICT_ENV, "1")
    x, w = _conv_inputs()
    with faults.lowering_failure("pallas"):
        with pytest.raises(InjectedFault):
            ops.conv2d(x, w)
    assert guard.events() == []


@pytest.mark.parametrize("exc", list(guard.BUG_ERRORS))
def test_bug_errors_propagate_without_demotion(exc):
    """An error of our own code (a renamed API, a wrong argument) is not
    a tier fault: it propagates from the fast tier, is not memoized, and
    the slower tier never runs in its place."""
    ran = []

    def bug():
        raise exc("bug in the fast tier")

    with pytest.raises(exc, match="bug in the fast tier"):
        guard.run_chain("k", [("pallas", bug),
                              ("ref", lambda: ran.append(1))])
    assert not ran
    assert guard.events() == [] and guard.demotions() == {}


@pytest.mark.guard_events
def test_final_tier_errors_propagate():
    """The last tier runs unguarded: a genuinely invalid problem still
    raises (from the simplest engine), never returns garbage."""
    def bad():
        raise ValueError("genuinely invalid problem")
    with pytest.raises(ValueError, match="genuinely invalid"):
        guard.run_chain("k", [("pallas", bad), ("ref", bad)])
    # the pallas attempt was recorded; the ref failure propagated
    (ev,) = guard.events()
    assert ev["tier"] == "pallas"


@pytest.mark.guard_events
def test_event_ring_is_bounded():
    for i in range(guard.RING_SIZE + 44):
        def boom(i=i):
            raise RuntimeError(f"fault {i}")
        guard.run_chain(f"key{i}", [("pallas", boom), ("ref", lambda: 0)])
    evs = guard.events()
    assert len(evs) == guard.RING_SIZE           # ring, not a leak
    assert evs[-1]["error"].endswith(f"fault {guard.RING_SIZE + 43}")
    # the demotion memo is complete even where the ring wrapped
    assert len(guard.demotions()) == guard.RING_SIZE + 44


def test_problem_key_is_structural_and_backend_free():
    k1 = guard.problem_key("conv2d", (1, 8, 8, 4), (3, 3, 4, 8))
    k2 = guard.problem_key("conv2d", (1, 8, 8, 4), (3, 3, 4, 8))
    k3 = guard.problem_key("conv2d", (2, 8, 8, 4), (3, 3, 4, 8))
    assert k1 == k2 and k1 != k3
    assert "jax" not in k1  # no backend/device leakage in the key


# ---------------------------------------------------------------------------
# Cache / checkpoint fault edges (the corrupt-file injectors)
# ---------------------------------------------------------------------------

def test_autotune_crash_before_publish_preserves_cache(tmp_path):
    from repro.core import autotune
    from repro.testing.faults import InjectedCrash
    path = str(tmp_path / "convtune.json")
    autotune.store("conv2d:a", dict(tile_h=4, tile_cout=8,
                                    dataflow="carry"), path)
    with faults.crash_before_publish("autotune"):
        with pytest.raises(InjectedCrash):
            autotune.store("conv2d:b", dict(tile_h=2, tile_cout=4,
                                            dataflow="halo"), path)
    # the published cache is intact and readable; no stray temp files
    autotune.reset_memory_cache()
    assert autotune.lookup("conv2d:a", path)["tile_h"] == 4
    stray = [f for f in tmp_path.iterdir() if ".tmp" in f.name]
    assert stray == []
    # the interrupted record was never published
    assert autotune.lookup("conv2d:b", path) is None


def test_guard_module_is_jax_free():
    """benchmarks/run.py --shard imports repro.core modules before
    choosing a device config; the guard must not initialize jax."""
    import subprocess
    import sys
    code = ("import repro.core.guard, sys; "
            "assert 'jax' not in sys.modules, 'guard imported jax'; "
            "print('ok')")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True,
                         env={**__import__('os').environ,
                              "PYTHONPATH": "src"},
                         cwd=__import__('os').path.join(
                             __import__('os').path.dirname(__file__), ".."))
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr
