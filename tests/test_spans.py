"""The serving path's host span log (``core.spans``): off by default,
nesting and step ids, the cap, and the spans one serving step leaves
through ``replay``, the asyncio front and a jitted replica."""

import asyncio

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.core.model import ConvLayer
from repro.core.serving import (Replica, ServingEngine, jit_replica_fn,
                                replay)
from repro.launch.serve_conv import AsyncConvServer
from repro.models import layers as mlayers
from repro.models.base import init_params

REPLICA_CHILDREN = ["replica.h2d", "replica.dispatch", "replica.wait",
                    "replica.d2h"]


@pytest.fixture
def log():
    spans.enable()
    yield spans
    spans.disable()


@pytest.fixture
def fake_clock(monkeypatch):
    """``perf_counter_ns`` that moves only when a test says so."""
    now = [0]
    monkeypatch.setattr(spans.time, "perf_counter_ns", lambda: now[0])

    def advance(ns):
        now[0] += ns
    return advance


def _echo_replica():
    return Replica("echo", lambda b: np.asarray(b).reshape(len(b), -1)
                   .sum(axis=1))


def test_off_by_default_records_nothing():
    n = len(spans.records())
    with spans.span("engine.step") as sp:
        sp.set_step(3)
        with spans.span("replica.forward"):
            pass
    assert len(spans.records()) == n


def test_nesting_gives_self_time(log, fake_clock):
    with spans.span("outer", step=7):
        fake_clock(100)
        with spans.span("inner"):
            fake_clock(250)
        fake_clock(50)
    (inner, i0, i1, istep), (outer, o0, o1, ostep) = spans.records()
    # a child ends, and is recorded, before its parent
    assert (inner, outer) == ("inner", "outer")
    assert o0 <= i0 <= i1 <= o1
    assert (o1 - o0) - (i1 - i0) == 150
    assert istep == ostep == 7


def test_step_id_reaches_children_and_is_restored(log):
    with spans.span("before"):
        pass
    with spans.span("engine.step") as sp:
        with spans.span("engine.batch"):
            sp.set_step(11)       # known only once the queue is popped
        with spans.span("replica.forward"):
            pass
    with spans.span("after"):
        pass
    steps = {name: step for name, _, _, step in spans.records()}
    assert steps == {"before": None, "engine.batch": 11,
                     "replica.forward": 11, "engine.step": 11,
                     "after": None}


def test_cap_counts_dropped(log, monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    for i in range(5):
        with spans.span(f"s{i}"):
            pass
    assert [r[0] for r in spans.records()] == ["s0", "s1", "s2"]
    assert spans.dropped() == 2
    spans.enable()                    # a fresh log
    assert spans.records() == [] and spans.dropped() == 0


def test_threads_lose_no_span(log, monkeypatch):
    """Many threads recording at once, switching often: every span is
    either kept or counted as dropped, and the log stops at the cap."""
    import sys
    import threading
    monkeypatch.setattr(spans, "CAP", 1000)
    n_threads, per_thread = 8, 400
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per_thread):
                with spans.span("s"):
                    pass
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert len(spans.records()) == 1000
    assert len(spans.records()) + spans.dropped() == n_threads * per_thread


def _by_step(records):
    out = {}
    for rec in records:
        out.setdefault(rec[3], []).append(rec)
    return out


def _within(child, parent):
    return parent[1] <= child[1] <= child[2] <= parent[2]


TOPO = [ConvLayer("t0", ifmap=8, in_channels=3, out_channels=8, kernel=3,
                  stride=1, padding=1),
        ConvLayer("t1", ifmap=8, in_channels=8, out_channels=8, kernel=3,
                  stride=1, padding=1)]


def test_replay_leaves_one_step_span_per_step(log):
    """A real jitted replica: every step is one ``engine.step`` holding
    ``engine.batch``, ``replica.forward`` (its four children in order,
    ``replica.h2d_wait`` inside ``replica.wait``) and ``engine.finish``,
    all under the step's id; each request's record names its step."""
    params = init_params(mlayers.cnn_params_from_layers(TOPO, n_classes=4),
                         jax.random.PRNGKey(0))
    eng = ServingEngine.for_topology(TOPO, params, buckets=(1, 2, 4))
    eng.prewarm()
    spans.enable()                 # the prewarm's forwards are not steps
    xs = np.random.default_rng(0).standard_normal((6, 8, 8, 3)) \
        .astype(np.float32)
    trace = [(0.0, 0, xs[0]), (0.0, 1, xs[1]), (0.0, 2, xs[2]),
             (0.0, 3, xs[3]), (0.0, 4, xs[4]), (5.0, 5, xs[5])]
    results, rejected = replay(eng, trace, service_model=lambda b: 1.0)
    assert len(results) == 6 and not rejected
    recs = spans.records()
    assert spans.dropped() == 0
    assert [r[3] for r in recs if r[0] == "engine.submit"] == [None] * 6
    steps = _by_step(r for r in recs if r[0] != "engine.submit")
    n_steps = sum(eng.stats()["bucket_batches"].values())
    assert sorted(steps) == list(range(n_steps)) == [0, 1, 2]
    assert eng.steps_taken == n_steps
    for sid, rs in steps.items():
        names = [r[0] for r in rs]
        assert names == ["engine.batch", "replica.h2d", "replica.dispatch",
                         "replica.h2d_wait", "replica.wait", "replica.d2h",
                         "replica.forward", "engine.finish",
                         "engine.step"], (sid, names)
        by = {r[0]: r for r in rs}
        assert _within(by["replica.h2d_wait"], by["replica.wait"])
        step = by["engine.step"]
        for child in ("engine.batch", "replica.forward", "engine.finish"):
            assert _within(by[child], step)
        assert by["engine.batch"][2] <= by["replica.forward"][1] \
            <= by["replica.forward"][2] <= by["engine.finish"][1]
        children = [by[n] for n in REPLICA_CHILDREN]
        for a, b in zip(children, children[1:]):
            assert a[2] <= b[1]
        assert all(_within(c, by["replica.forward"]) for c in children)
    assert {rid: rec.step for rid, rec in eng.recorder.records.items()} \
        == {0: 0, 1: 0, 2: 0, 3: 0, 4: 1, 5: 2}


def test_async_front_spans_carry_the_step(log):
    eng = ServingEngine([_echo_replica()], buckets=(1, 2, 4))

    async def go():
        async with AsyncConvServer(eng, max_wait_s=0.0) as srv:
            return await asyncio.gather(
                *[srv.submit(np.full((2,), float(i))) for i in range(5)])

    outs = asyncio.run(go())
    assert [float(o) for o in outs] == [0.0, 2.0, 4.0, 6.0, 8.0]
    recs = spans.records()
    steps = sorted({r[3] for r in recs if r[0] == "engine.step"})
    assert steps == list(range(eng.steps_taken))
    for name in ("server.step", "server.resolve"):
        assert sorted(r[3] for r in recs if r[0] == name) == steps
    by = {(r[0], r[3]): r for r in recs}
    for sid in steps:
        # the executor hop around the step: server.step holds it
        assert _within(by["engine.step", sid], by["server.step", sid])
        assert by["server.step", sid][2] <= by["server.resolve", sid][1]


def test_jit_replica_fn_puts_the_batch_on_its_device():
    dev = jax.devices()[-1]
    seen = []

    def call(p, x):
        seen.append(x.devices())
        return x * p

    fn = jit_replica_fn(call, 2.0, dev)
    out = fn(np.ones((2, 3), np.float32))
    assert isinstance(out, np.ndarray) and out.tolist() == [[2.0] * 3] * 2
    assert seen == [{dev}]
