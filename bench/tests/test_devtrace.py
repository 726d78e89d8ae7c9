"""The trace reduction (``devtrace``) on a hand-built trace whose answer
is worked out below, and on a trace recorded on a TPU v5e."""

import os
from types import SimpleNamespace as NS

import pytest

import devtrace

MS = 1_000_000      # nanoseconds


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
              stats=[])


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


OFFSET = 7 * MS       # trace clock minus host clock
KERNEL = 'custom-call(...), custom_call_target="tpu_custom_call"'


def hand_trace():
    """A 100 ms window, on the trace's clock, after two marker runs.
    Host: a forward span 10-60 ms, a submit span 60-75 ms.  Device: two
    program runs, each a Pallas kernel and an XLA op, busy 10-30 and
    40-55 (kernel and op overlap 25-30) and 80-90."""
    markers = [-30, -20]
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev(f"jit_{devtrace.MARKER}(1)", m, 0.01)
                             for m in markers]
                + [ev("jit_fwd(2)", 10, 45), ev("jit_fwd(2)", 80, 10)],
                XLA_Ops=[ev("%marker = f32[8,128]{1,0} add", -30, 0.01),
                         ev("%fusion.1 = f32[8]{0} fusion", 10, 20),
                         ev("%k.1 = f32[8]{0} " + KERNEL, 25, 5),
                         ev("%k.2 = f32[8]{0} " + KERNEL, 40, 15),
                         ev("%k.3 = f32[8]{0} " + KERNEL, 80, 4),
                         ev("%copy.2 = f32[8]{0} copy", 84, 6),
                         ev("%late = f32[8]{0} add", 95, 20)])  # clipped
    host = {"window": [0 - OFFSET, 100 * MS - OFFSET],
            "markers": [m * MS - OFFSET for m in markers],
            "spans": [["bench.forward", 10 * MS - OFFSET, 60 * MS - OFFSET],
                      ["bench.submit", 60 * MS - OFFSET, 75 * MS - OFFSET]]}
    return NS(planes=[plane("/host:CPU"), dev]), host


def test_hand_trace():
    pd, host = hand_trace()
    r = devtrace.reduce_profile(pd, host, kernel_names=["c1", "c2"])
    assert r.window_s == pytest.approx(0.100)
    # busy: 10-30, 40-55, 80-90, 95-100
    assert r.busy_s == pytest.approx(0.050)
    assert r.idle_share == pytest.approx(0.5)
    assert r.op_s == pytest.approx((20 + 5 + 15 + 4 + 6 + 5) / 1e3)
    assert r.kernel_s == pytest.approx(0.024)
    assert r.nonkernel_s == pytest.approx(0.031)
    assert r.n_modules == 2
    # the first run has two kernels, as many as there are convs: named
    # by position; the second has one and keeps its own name
    top = dict(r.top_ops)
    assert top["c2:%k.2 = f32[8]"] == pytest.approx(0.015)
    assert top["c1:%k.1 = f32[8]"] == pytest.approx(0.005)
    assert top["%k.3 = f32[8]"] == pytest.approx(0.004)
    assert [v for _, v in r.top_ops] == sorted(
        (v for _, v in r.top_ops), reverse=True)
    # gaps: 0-10 (none), 30-40 (forward), 55-80 (forward 55-60, submit
    # 60-75, none 75-80), 90-95 (none)
    gaps = dict(r.idle_gaps)
    assert gaps["bench.forward"] == pytest.approx(0.015)
    assert gaps["bench.submit"] == pytest.approx(0.015)
    assert gaps[devtrace.NO_SPAN] == pytest.approx(0.020)


def test_nothing_to_read():
    """No device plane, or no marker to align the clocks by: nothing."""
    pd, host = hand_trace()
    assert devtrace.reduce_profile(NS(planes=pd.planes[:1]), host) is None
    assert devtrace.reduce_profile(pd, dict(host, markers=[1])) is None


def test_union():
    assert devtrace._union([(5, 6), (0, 2), (1, 3), (3, 4)]) == \
        [[0, 4], [5, 6]]


DATA = os.path.join(os.path.dirname(__file__), "data")


def test_recorded_v5e_trace():
    """A short window of ``vgg16-f32.single`` traced on one TPU v5e: one
    program run per request, 13 Pallas conv kernels in each."""
    r = devtrace.reduce_dir(DATA, kernel_names=[f"conv{i}"
                                                for i in range(1, 14)])
    assert r is not None and r.n_devices == 1
    assert 0.2 < r.window_s < 1.0
    assert 0 < r.busy_s < r.window_s
    assert 0 < r.kernel_s < r.op_s
    assert r.n_modules > 5
    named = [n for n, _ in r.top_ops if n.startswith("conv")]
    assert len(named) >= 5, r.top_ops
    assert "bench.forward" in dict(r.idle_gaps)
