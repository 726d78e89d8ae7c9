"""The chunked replica forward (DESIGN.md §15): the rule that splits a
large batch into chunks, the pipelined forward that returns the same
rows as the whole batch, prewarm's cover of the chunk shapes, the
``replica_chunks`` counter, and chunks placed on a distributed
replica's own device."""

import jax
import numpy as np
import pytest

from repro.core import autotune, serving, spans
from repro.core.model import ConvLayer
from repro.core.serving import ServingEngine, chunk_rows, jit_replica_fn
from repro.models import layers as mlayers
from repro.models.base import init_params

pytestmark = pytest.mark.serving

TOPO = [ConvLayer("c0", ifmap=12, in_channels=3, out_channels=8,
                  kernel=3, stride=1, padding=1),
        ConvLayer("c1", ifmap=12, in_channels=8, out_channels=8,
                  kernel=3, stride=2, padding=1)]
ROW_BYTES = 12 * 12 * 3 * 4          # one f32 image of TOPO's input


def _params(topo=TOPO):
    return init_params(mlayers.cnn_params_from_layers(topo, n_classes=10),
                       jax.random.PRNGKey(0))


def _xs(n):
    return np.random.default_rng(16).standard_normal(
        (n, 12, 12, 3)).astype(np.float32)


def _split_at(monkeypatch, rows):
    """Make the rule split TOPO's batches into chunks of ``rows``."""
    monkeypatch.setattr(serving, "CHUNK_BYTES", rows * ROW_BYTES)


@pytest.mark.parametrize("shape,chunks", [
    ((128, 224, 224, 3), 16),        # 77 MB: 16 chunks of 8 rows
    ((32, 224, 224, 3), 4),          # 19.3 MB: 4 chunks of 8 rows
    ((1, 224, 224, 3), 1),           # one row stays whole
    ((4, 12, 12, 3), 1),             # a tiny batch stays whole
])
def test_chunk_rows_at_the_default_target(shape, chunks):
    rows = chunk_rows(shape, 4)
    assert shape[0] % rows == 0
    assert rows & (rows - 1) == 0 or rows == shape[0]
    assert shape[0] // rows == chunks


@pytest.mark.parametrize("n,target_rows,rows", [
    (8, 2, 2),                       # a power of two that fits the target
    (8, 3, 2),                       # ... rounded down to one
    (6, 4, 2),                       # ... that divides the batch
    (8, 0.5, 1),                     # a row larger than the target
    (8, 8, 8),                       # a batch within the target
])
def test_chunk_rows_with_a_smaller_target(n, target_rows, rows):
    got = chunk_rows((n, 12, 12, 3), 4,
                     target_bytes=int(target_rows * ROW_BYTES))
    assert got == rows and n % got == 0


def test_chunks_run_in_order_on_the_device():
    """Each chunk reaches the program on ``device`` with its own rows,
    in order, and the rows come back joined in order."""
    dev = jax.devices()[-1]
    seen = []

    def call(p, x):
        seen.append((x.shape, x.devices()))
        return x * p

    fn = jit_replica_fn(call, 2.0, dev)
    x = np.arange(8 * 3, dtype=np.float32).reshape(8, 3)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(serving, "CHUNK_BYTES", 2 * 3 * 4)
        out = fn(x)
    assert seen == [((2, 3), {dev})] * 4
    np.testing.assert_array_equal(out, 2.0 * x)


def test_chunked_forward_equals_the_whole_batch(monkeypatch):
    """A batch of 8 split into 4 chunks returns the rows of the same
    forward unsplit, in order, and leaves the spans of a whole batch."""
    call = jax.jit(lambda p, x: mlayers.cnn_apply_from_layers(p, TOPO, x))
    fn = jit_replica_fn(call, _params())
    xs = _xs(8)
    whole = fn(xs)
    _split_at(monkeypatch, 2)
    spans.enable()
    try:
        chunked = fn(xs)
        names = [r[0] for r in spans.records()]
    finally:
        spans.disable()
    assert chunked.shape == whole.shape == (8, 10)
    np.testing.assert_allclose(chunked, whole, rtol=1e-6)
    assert names == ["replica.h2d", "replica.dispatch", "replica.h2d_wait",
                     "replica.wait", "replica.d2h"]


def test_prewarm_covers_the_chunk_shapes(monkeypatch):
    """After ``prewarm()`` every layer has a tune record at the chunk's
    rows, and a forward of every bucket compiles nothing new and tunes
    nothing cold."""
    _split_at(monkeypatch, 2)
    eng = ServingEngine.for_topology(TOPO, _params(), buckets=(1, 4, 8))
    assert eng.stats()["replica_chunks"] == {1: 1, 4: 2, 8: 4}
    records = eng.prewarm()
    assert sorted(records) == [1, 2, 4, 8]
    chunk = records[2]["layers"]
    assert sorted(chunk) == [layer.name for layer in TOPO]
    for rec in chunk.values():
        assert ":n2h" in rec["key"], rec["key"]
        assert autotune.lookup(rec["key"]) is not None

    compiles = []

    def listener(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    def cold(*a, **kw):
        raise AssertionError(f"cold tune after prewarm: {a} {kw}")

    monkeypatch.setattr(autotune, "tune", cold)
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        xs, rid = _xs(8), 0
        for b in eng.grid.buckets:
            for i in range(b):
                eng.submit(rid, xs[i], now=0.0)
                rid += 1
            out, _ = eng.step(now=0.0)
            assert len(out) == b
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []
    assert eng.stats()["cold_tunes"] == 0


def test_replica_chunks_counts_the_rule_at_full_size():
    """``stats()["replica_chunks"]``: 16 for a bucket-128 224x224
    engine, 4 at bucket 32, 1 at bucket 1 (nothing is compiled to read
    it)."""
    topo = [ConvLayer("s0", ifmap=224, in_channels=3, out_channels=8,
                      kernel=3, stride=2, padding=1)]
    eng = ServingEngine.for_topology(topo, _params(topo),
                                     buckets=(1, 32, 128))
    assert eng.stats()["replica_chunks"] == {1: 1, 32: 4, 128: 16}


def test_distributed_replica_puts_every_chunk_on_its_device(monkeypatch):
    """With ``distribute=True`` every chunk of replica i's batch is put
    on replica i's own device, and its rows equal the whole batch's."""
    eng = ServingEngine.for_topology(TOPO, _params(), buckets=(4,),
                                     n_replicas=2, distribute=True)
    devices = jax.devices()
    xs = _xs(4)
    whole = [np.asarray(r.fn(xs)) for r in eng.replicas]
    put = jax.device_put
    placed = []

    def spy(x, device=None, **kw):
        out = put(x, device, **kw)
        placed.extend((a.shape[0], a.devices()) for a in out)
        return out

    _split_at(monkeypatch, 2)
    monkeypatch.setattr(jax, "device_put", spy)
    for i, rep in enumerate(eng.replicas):
        placed.clear()
        out = rep.fn(xs)
        assert placed == [(2, {devices[i % len(devices)]})] * 2
        np.testing.assert_allclose(out, whole[i], rtol=1e-6)
