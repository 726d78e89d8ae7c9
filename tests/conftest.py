import os
import sys
import types

import pytest

# smoke tests and benches see the single real CPU device (the dry-run sets
# its own 512-device flag in its own process)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Multi-device harness for the `multidevice`-marked differential tests
# (DESIGN.md §6): REPRO_MULTIDEVICE=1 forces 8 host CPU devices.  This
# must happen at conftest *import* time — XLA reads the flag at first
# jax initialization, long before any fixture runs.  The second tier-1
# CI job sets the env var; the default job leaves it unset and the
# marked tests skip (single device).
MULTIDEVICE_COUNT = 8
if os.environ.get("REPRO_MULTIDEVICE", "0") not in ("", "0"):
    from repro.launch.hostdevices import force_host_device_count
    force_host_device_count(MULTIDEVICE_COUNT)


@pytest.fixture(scope="session")
def multidevice_harness():
    """The forced multi-device CPU mesh backing the sharded differential
    tests; yields the device count (>= 2 or the test was skipped)."""
    import jax
    n = jax.device_count()
    assert n >= 2, "multidevice tests collected on a single-device run"
    yield n


def pytest_collection_modifyitems(config, items):
    if not any("multidevice" in item.keywords for item in items):
        return
    import jax
    if jax.device_count() >= 2:
        return
    skip = pytest.mark.skip(
        reason="needs the forced multi-device CPU harness "
               "(REPRO_MULTIDEVICE=1, 8 host devices)")
    for item in items:
        if "multidevice" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _isolated_convtune_cache(tmp_path, monkeypatch):
    """Point the conv autotune cache at a per-test temp file: tests must
    never read knobs from (or write records into) the developer's real
    ``~/.cache/repro/convtune.json``."""
    from repro.core import autotune
    monkeypatch.setenv(autotune.CACHE_ENV,
                       str(tmp_path / "convtune.json"))
    autotune.reset_memory_cache()
    yield
    autotune.reset_memory_cache()


@pytest.fixture
def pallas_calls():
    """``calls(fn, *args)``: ``[(name, operand shapes)]`` of every
    ``pallas_call`` in the traced program of ``fn(*args)``, nested
    jaxprs included, in trace order — which kernels the program runs,
    under which names, over which problems.  ``args`` may be arrays or
    ``jax.ShapeDtypeStruct``s."""
    import jax

    def calls(fn, *args):
        found = []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append((eqn.params["name"],
                                  [tuple(v.aval.shape)
                                   for v in eqn.invars]))
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return found
    return calls


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "guard_events: the test provokes conv-tier demotions "
                   "on purpose (fault injection); without it, any guard "
                   "event fails the test")


@pytest.fixture(autouse=True)
def _guard_reset(request):
    """Fresh guard state (events + memoized demotions) per test: a
    demotion memoized by one test must never silently reroute another
    test's conv dispatch.  A test that ends with a demotion it did not
    declare (``@pytest.mark.guard_events``) fails: a demoted fast path
    would otherwise pass by comparing a slower tier with the oracle."""
    from repro.core import guard
    guard.reset()
    yield
    events = guard.events()
    guard.reset()
    if events and request.node.get_closest_marker("guard_events") is None:
        pytest.fail("undeclared guard demotions: " + "; ".join(
            f"{e['tier']}->{e['to']} {e['key']}: {e['error'][:200]}"
            for e in events), pytrace=False)

try:                                    # pragma: no cover - env-dependent
    import hypothesis  # noqa: F401
except ImportError:
    # Minimal stand-in so the property tests still run (as deterministic
    # random sweeps) on a bare interpreter without the hypothesis package.
    import numpy as _np

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def _integers(min_value, max_value):
        return _Strategy(
            lambda rng: int(rng.integers(min_value, max_value + 1)))

    def _sampled_from(elements):
        elements = list(elements)
        return _Strategy(
            lambda rng: elements[int(rng.integers(len(elements)))])

    def _booleans():
        return _Strategy(lambda rng: bool(rng.integers(2)))

    def _floats(min_value=0.0, max_value=1.0, **_kw):
        return _Strategy(lambda rng: float(rng.uniform(min_value, max_value)))

    def _given(**strategies):
        def deco(fn):
            # zero-arg wrapper (no functools.wraps: pytest must not see the
            # wrapped signature, or it would treat the strategy parameters
            # as fixtures)
            def wrapper():
                n = getattr(wrapper, "_stub_max_examples", 10)
                rng = _np.random.default_rng(0)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    fn(**drawn)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            return wrapper
        return deco

    def _settings(max_examples=10, **_kw):
        def deco(fn):
            fn._stub_max_examples = max_examples
            return fn
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers, _st.sampled_from = _integers, _sampled_from
    _st.booleans, _st.floats = _booleans, _floats
    _hyp = types.ModuleType("hypothesis")
    _hyp.given, _hyp.settings, _hyp.strategies = _given, _settings, _st
    _hyp.HealthCheck = types.SimpleNamespace(too_slow=None)
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
