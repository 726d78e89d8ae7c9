"""Serving-engine tests (DESIGN.md §10): batching policy, padding
isolation, FIFO order, backpressure, deterministic replay, and the
differential gate — every served row bit-matches the single-request
tuned forward.  The chaos case demotes a replica mid-load and asserts
it keeps serving with the expected ``guard.events()`` surfaced in
``stats()``.

Tests that need real forwards use a deliberately small 3-layer topology
so the suite stays tier-1 fast; policy-only tests use fake replicas and
injected service times on the virtual timeline — no jax, no wall-clock.
"""

import numpy as np
import pytest

import jax

from repro.core import guard, serving
from repro.core.model import ConvLayer
from repro.core.serving import (BucketGrid, QueueFull, Replica,
                                ServingEngine, pow2_buckets, replay)
from repro.models import layers as mlayers
from repro.models.base import init_params
from repro.testing import faults
from repro.testing.load import (TraceRecorder, burst_arrivals,
                                poisson_arrivals, ramp_arrivals)

pytestmark = pytest.mark.serving

TOPO = [ConvLayer("t0", ifmap=12, in_channels=3, out_channels=8,
                  kernel=3, stride=1, padding=1),
        ConvLayer("t1", ifmap=12, in_channels=8, out_channels=8,
                  kernel=3, stride=2, padding=1),
        ConvLayer("t2", ifmap=6, in_channels=8, out_channels=16,
                  kernel=3, stride=1, padding=1)]
RNG = np.random.default_rng(8)


def _params():
    return init_params(
        mlayers.cnn_params_from_layers(TOPO, n_classes=10),
        jax.random.PRNGKey(0))


def _engine(**kw):
    kw.setdefault("buckets", (1, 2, 4))
    return ServingEngine.for_topology(TOPO, _params(), **kw)


def _echo_replica(name="echo"):
    """A fake replica whose output row encodes the input row — lets
    policy tests verify routing without any real forward."""
    return Replica(name=name, fn=lambda b: np.asarray(b).sum(
        axis=tuple(range(1, np.asarray(b).ndim))))


def _xs(n, shape=(12, 12, 3)):
    return RNG.standard_normal((n,) + shape).astype(np.float32)


# ---------------------------------------------------------------------------
# Bucket selection: exact and deterministic
# ---------------------------------------------------------------------------

def test_bucket_for_is_exact():
    g = BucketGrid.build((1, 2, 4, 8))
    assert [g.bucket_for(n) for n in range(1, 9)] == \
        [1, 2, 4, 4, 8, 8, 8, 8]
    assert [g.pad_rows(n) for n in range(1, 9)] == \
        [0, 0, 1, 0, 3, 2, 1, 0]


def test_bucket_for_bounds():
    g = BucketGrid.build((2, 4))
    assert g.bucket_for(1) == 2       # smallest bucket still fits
    with pytest.raises(ValueError):
        g.bucket_for(0)
    with pytest.raises(ValueError):
        g.bucket_for(5)               # beyond max_bucket: caller splits
    with pytest.raises(ValueError):
        BucketGrid.build(())
    with pytest.raises(ValueError):
        BucketGrid.build((0, 2))


def test_grid_sorts_and_dedups():
    g = BucketGrid.build((8, 1, 4, 4, 2))
    assert g.buckets == (1, 2, 4, 8)
    assert g.max_bucket == 8


def test_pow2_buckets():
    assert pow2_buckets(8) == (1, 2, 4, 8)
    assert pow2_buckets(6) == (1, 2, 4, 6)
    assert pow2_buckets(1) == (1,)
    with pytest.raises(ValueError):
        pow2_buckets(0)


# ---------------------------------------------------------------------------
# Differential gate: served rows bit-match the unbatched forward
# ---------------------------------------------------------------------------

def test_served_rows_bit_match_single_request_forward():
    eng = _engine()
    eng.prewarm()
    xs = _xs(7)
    trace = [(t, i, xs[i])
             for i, t in enumerate(poisson_arrivals(500.0, 7, seed=3))]
    results, rejected = replay(eng, trace)
    assert not rejected and len(results) == 7
    # batches actually formed at more than one bucket size
    assert len(eng.stats()["bucket_batches"]) >= 1
    for i in range(7):
        assert np.array_equal(results[i], eng.forward_one(xs[i])), i


def test_padding_rows_never_leak():
    """Serving the same requests under two engines whose padding fill
    differs wildly must produce identical rows — proof the padded rows
    cannot influence any real row."""
    xs = _xs(3)     # 3 requests -> bucket 4: one padding row
    outs = {}
    for fill in (0.0, 1e9):
        eng = _engine(pad_fill=fill)
        eng.prewarm()
        trace = [(0.0, i, xs[i]) for i in range(3)]
        results, _ = replay(eng, trace)
        outs[fill] = results
    assert eng.stats()["bucket_batches"] == {4: 1}
    for i in range(3):
        assert np.array_equal(outs[0.0][i], outs[1e9][i]), i


# ---------------------------------------------------------------------------
# Queue policy: FIFO, backpressure, determinism (fake replicas)
# ---------------------------------------------------------------------------

def test_fifo_within_bucket():
    eng = ServingEngine([_echo_replica()], buckets=(1, 2, 4),
                        input_shape=(2,))
    for rid in range(10):
        eng.submit(rid, np.full(2, rid, np.float32), now=float(rid))
    order = []
    t = 10.0
    while eng.pending():
        out, dt = eng.step(now=t, service_model=lambda b: 1.0)
        order.extend(rid for rid, _ in out)
        t += dt
    assert order == list(range(10))     # strict arrival order
    recs = eng.recorder.completed()
    assert [r.rid for r in recs] == list(range(10))


def test_backpressure_bounds_queue_depth():
    eng = ServingEngine([_echo_replica()], buckets=(1, 2, 4),
                        max_queue=4)
    for rid in range(4):
        eng.submit(rid, np.zeros(2), now=0.0)
    with pytest.raises(QueueFull):
        eng.submit(99, np.zeros(2), now=0.0)
    assert eng.recorder.max_queue_depth == 4
    assert eng.pending() == 4

    # replay sheds (records) instead of raising: open-loop load
    eng2 = ServingEngine([_echo_replica()], buckets=(1, 2, 4),
                         max_queue=4)
    trace = [(0.0, i, np.zeros(2)) for i in range(12)]
    results, rejected = replay(eng2, trace,
                               service_model=lambda b: 1.0)
    assert len(results) + len(rejected) == 12
    assert eng2.recorder.max_queue_depth <= 4
    assert eng2.stats()["rejected"] == len(rejected)


def test_max_queue_must_fit_a_batch():
    with pytest.raises(ValueError):
        ServingEngine([_echo_replica()], buckets=(1, 8), max_queue=4)


def test_replay_is_deterministic():
    def run():
        eng = ServingEngine([_echo_replica("a"), _echo_replica("b")],
                            buckets=(1, 2, 4))
        trace = [(t, i, np.full(2, i, np.float32)) for i, t in
                 enumerate(ramp_arrivals(5.0, 50.0, 20, seed=7))]
        results, rejected = replay(eng, trace,
                                   service_model=lambda b: 0.05 * b)
        timeline = [(r.rid, r.t_enqueue, r.t_execute, r.t_complete,
                     r.bucket, r.replica)
                    for r in eng.recorder.completed()]
        return results, rejected, timeline

    r1, rej1, tl1 = run()
    r2, rej2, tl2 = run()
    assert tl1 == tl2 and rej1 == rej2
    assert sorted(r1) == sorted(r2)
    assert all(np.array_equal(r1[k], r2[k]) for k in r1)


def test_continuous_batching_fills_buckets_under_burst():
    eng = ServingEngine([_echo_replica()], buckets=(1, 2, 4))
    # 8 simultaneous arrivals: two full max-bucket batches, FIFO
    trace = [(0.0, i, np.zeros(2)) for i in range(8)]
    replay(eng, trace, service_model=lambda b: 1.0)
    assert eng.stats()["bucket_batches"] == {4: 2}
    for r in eng.recorder.completed():
        assert r.bucket == 4 and r.batch_real == 4


def test_round_robin_spreads_load_over_replicas():
    eng = ServingEngine([_echo_replica("a"), _echo_replica("b")],
                        buckets=(1,))
    trace = [(float(i), i, np.zeros(2)) for i in range(6)]
    replay(eng, trace, service_model=lambda b: 0.1)
    served = eng.stats()["replicas"]
    assert served["a"]["served"] == 3 and served["b"]["served"] == 3


def test_recorder_lifecycle_and_latency():
    rec = TraceRecorder()
    eng = ServingEngine([_echo_replica()], buckets=(1, 2),
                        recorder=rec)
    eng.submit(0, np.zeros(2), now=1.0)
    eng.submit(1, np.zeros(2), now=1.5)
    out, dt = eng.step(now=2.0, service_model=lambda b: 0.5)
    assert {rid for rid, _ in out} == {0, 1} and dt == 0.5
    r0 = rec.records[0]
    assert (r0.t_enqueue, r0.t_execute, r0.t_complete) == (1.0, 2.0, 2.5)
    assert r0.latency == 1.5 and r0.queue_wait == 1.0
    assert r0.bucket == 2 and r0.batch_real == 2
    s = rec.summary()
    assert s["count"] == 2 and s["buckets"][2]["count"] == 2


def test_arrival_generators_are_seed_deterministic():
    assert poisson_arrivals(10.0, 5, seed=4) == \
        poisson_arrivals(10.0, 5, seed=4)
    assert poisson_arrivals(10.0, 5, seed=4) != \
        poisson_arrivals(10.0, 5, seed=5)
    bursts = burst_arrivals(3, 4, 1.0)
    assert bursts == [0.0] * 4 + [1.0] * 4 + [2.0] * 4
    ramp = ramp_arrivals(5.0, 50.0, 10, seed=1)
    assert ramp == sorted(ramp) and len(ramp) == 10


# ---------------------------------------------------------------------------
# Prewarm: no cold paths after it
# ---------------------------------------------------------------------------

def test_prewarm_eliminates_cold_tunes():
    eng = _engine()
    eng.prewarm()
    xs = _xs(5)
    trace = [(0.0, i, xs[i]) for i in range(5)]
    replay(eng, trace)
    st = eng.stats()
    assert st["cold_tunes"] == 0
    assert st["prewarmed_buckets"] == [1, 2, 4]


def test_unprewarmed_bucket_counts_as_cold_tune():
    eng = _engine()
    xs = _xs(2)
    eng.submit(0, xs[0], now=0.0)
    eng.submit(1, xs[1], now=0.0)
    eng.step(now=0.0)
    assert eng.stats()["cold_tunes"] == 1    # bucket 2, tuned on the spot
    eng.submit(2, xs[0], now=1.0)
    eng.submit(3, xs[1], now=1.0)
    eng.step(now=1.0)
    assert eng.stats()["cold_tunes"] == 1    # warm on the second hit


# ---------------------------------------------------------------------------
# Chaos: a demoted replica keeps serving, visibly
# ---------------------------------------------------------------------------

@pytest.mark.chaos
@pytest.mark.guard_events
def test_replica_demoted_mid_load_keeps_serving():
    # eager replicas: the guarded tier chain dispatches per call, so a
    # fault injected mid-load demotes on the very next batch
    eng = _engine(jit=False)
    eng.prewarm()
    xs = _xs(6)
    t = 0.0

    def serve(rid):
        nonlocal t
        eng.submit(rid, xs[rid], now=t)
        out, _ = eng.step(now=t, service_model=lambda b: 0.1)
        t += 0.1
        return dict(out)[rid]

    clean = [serve(rid) for rid in range(3)]
    assert not guard.events()
    before = [eng.forward_one(xs[i]) for i in range(6)]

    with faults.lowering_failure("pallas"):
        degraded = [serve(rid) for rid in range(3, 6)]

    # the engine kept serving every request...
    st = eng.stats()
    assert st["served"] == 6 and st["pending"] == 0
    # ...the demotions are attributed to the replica that hit them...
    rep = st["replicas"]["replica0"]
    assert rep["degraded"] and rep["served"] == 6
    evs = rep["guard_events"]
    assert evs and all(e["tier"] == "pallas" and e["to"] == "ref"
                       for e in evs)
    assert [dict(e) for e in guard.events()] == evs
    # ...and the demoted tier still matches the healthy forward (ref
    # numerics == pallas numerics within the stack's exactness contract)
    for rid, row in zip(range(3), clean):
        assert np.array_equal(row, before[rid])
    for rid, row in zip(range(3, 6), degraded):
        np.testing.assert_allclose(row, before[rid], rtol=2e-5,
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# Engine construction guards
# ---------------------------------------------------------------------------

def test_engine_needs_a_replica():
    with pytest.raises(ValueError):
        ServingEngine([], buckets=(1,))


def test_duplicate_rid_rejected():
    eng = ServingEngine([_echo_replica()], buckets=(1,))
    eng.submit(0, np.zeros(2), now=0.0)
    with pytest.raises(ValueError):
        eng.submit(0, np.zeros(2), now=0.1)


def test_serving_module_exports():
    for name in serving.__all__:
        assert getattr(serving, name) is not None
