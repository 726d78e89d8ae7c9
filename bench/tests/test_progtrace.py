"""The trace read by the program's own names (``progtrace``) on a
hand-built trace whose answer is worked out below, the readers of the
metrics that use it, and the span run (``spanrun.py``) at rehearsal
size."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

import cellspec
import progtrace
from test_devtrace import KERNEL, MS, OFFSET, ev, plane

import devtrace


def hand_trace():
    """A 100 ms window after two marker runs.  Device: conv1 split over
    two kernels (10-20, 20-25), conv2 one kernel (40-55), an XLA op
    (80-90).  Host, program spans of step 0: engine.step 5-70 holding
    engine.batch 5-10, replica.forward 10-60 (replica.h2d 10-12,
    replica.dispatch 12-13, replica.wait 13-58 holding replica.h2d_wait
    13-30, replica.d2h 58-60) and engine.finish 60-70; engine.submit
    72-74 outside any step."""
    markers = [-30, -20]
    dev = plane("/device:TPU:0",
                XLA_Modules=[ev(f"jit_{devtrace.MARKER}(1)", m, 0.01)
                             for m in markers],
                XLA_Ops=[ev("%conv1.4 = f32[8]{0} " + KERNEL, 10, 10),
                         ev("%conv1.5 = f32[8]{0} " + KERNEL, 20, 5),
                         ev("%conv2 = f32[8]{0} " + KERNEL, 40, 15),
                         ev("%fusion.2 = f32[8]{0} fusion", 80, 10)])
    host = {"window": [0 - OFFSET, 100 * MS - OFFSET],
            "markers": [m * MS - OFFSET for m in markers], "spans": []}

    def sp(name, a, b, step=0):
        return (name, a * MS - OFFSET, b * MS - OFFSET, step)
    records = [sp("engine.batch", 5, 10), sp("replica.h2d", 10, 12),
               sp("replica.dispatch", 12, 13),
               sp("replica.h2d_wait", 13, 30), sp("replica.wait", 13, 58),
               sp("replica.d2h", 58, 60), sp("replica.forward", 10, 60),
               sp("engine.finish", 60, 70), sp("engine.step", 5, 70),
               sp("engine.submit", 72, 74, None),
               sp("engine.submit", -50, -49, None)]    # before the window
    return NS(planes=[plane("/host:CPU"), dev]), host, records


def test_kernel_time_by_name():
    pd, host, records = hand_trace()
    pt = progtrace.reduce_profile(pd, host, records)
    assert pt.window_s == pytest.approx(0.100)
    # one conv over two kernels sums under its name
    assert pt.kernel_s_by_layer == pytest.approx({"conv1": 0.015,
                                                  "conv2": 0.015})


def test_idle_by_program_span():
    pd, host, records = hand_trace()
    pt = progtrace.reduce_profile(pd, host, records)
    # idle: 0-10 (none 0-5, batch 5-10), 25-40 (h2d_wait 25-30, wait
    # 30-40), 55-80 (wait 55-58, d2h 58-60, finish 60-70, none 70-72,
    # submit 72-74, none 74-80), 90-100 (none)
    assert pt.idle_by_span == pytest.approx({
        progtrace.NO_SPAN: 0.023, "engine.batch": 0.005,
        "replica.h2d_wait": 0.005, "replica.wait": 0.013,
        "replica.d2h": 0.002, "engine.finish": 0.010,
        "engine.submit": 0.002})
    assert len(pt.spans) == 10      # the early submit is not the window's


def test_no_spans_no_names():
    """A program without span log or kernel names: kernel time under the
    instruction's own name, every idle instant in no program span."""
    pd, host, _ = hand_trace()
    pt = progtrace.reduce_profile(pd, host, [])
    assert pt.spans == []
    assert set(pt.idle_by_span) == {progtrace.NO_SPAN}
    assert progtrace.reduce_profile(NS(planes=pd.planes[:1]), host) is None


@pytest.mark.parametrize("text,name", [
    ("%conv1.13 = f32[1,231,224,64]{3,2,1,0} custom-call(...)", "conv1"),
    ("%trim_conv2d.13 = f32[1]{0} custom-call(...)", "trim_conv2d"),
    ("%dec0r = f32[8]{0} custom-call(...)", "dec0r"),
    ("%conv10.2.1 = f32[8]{0} custom-call(...)", "conv10.2"),
    ("fusion", None)])
def test_kernel_name(text, name):
    assert progtrace.kernel_name(text) == name


def test_span_arithmetic():
    _, _, records = hand_trace()
    assert progtrace.self_ms_per_step(records, "engine.step",
                                      ["replica.forward"]) == \
        pytest.approx(15.0)
    assert progtrace.mean_ms(records, "replica.h2d") == pytest.approx(2.0)
    assert progtrace.mean_ms(records, "nothing") is None
    assert sorted(progtrace.by_step(records)[0]) == sorted(
        {r[0] for r in records if r[3] == 0})


def _metric(name):
    return cellspec.load_module("metrics", name)


def test_full_res_convs_and_their_bound():
    vgg = cellspec.NetSpec(cellspec.find_cell("vgg16-f32.saturate").config)
    unet = cellspec.NetSpec(cellspec.find_cell("unet-f32.batch8").config)
    m = _metric("conv_roofline.full_res")
    assert [n.name for n in m.full_res_convs(vgg)] == ["conv1", "conv2"]
    assert [n.name for n in m.full_res_convs(unet)] == \
        ["enc0a", "enc0b", "dec0r", "dec0a", "dec0b", "out"]
    peak = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    conv1, conv2 = m.full_res_convs(vgg)
    # conv1 at batch 32 moves its 224x224x3 input and 224x224x64 output
    # per image (and 1,792 parameters once): memory-bound
    moved = 4 * (32 * (224 * 224 * 3 + 224 * 224 * 64) + 1792)
    assert m.conv_bound_s(conv1, 32, "float32", peak) == \
        pytest.approx(moved / 819e9)
    bound = sum(m.conv_bound_s(n, 32, "float32", peak)
                for n in (conv1, conv2))
    assert 1.4e-3 < bound < 1.6e-3


def test_readers_give_nothing_without_a_trace():
    run = NS(trace=None, peak={"flops": 1.0, "hbm_bytes_per_s": 1.0},
             steps=[1], completed=1, spec=None)
    for name in ("conv_roofline.full_res", "engine.host_ms_per_step",
                 "replica.h2d_ms_per_step",
                 "replica.device_idle_ms.single"):
        assert _metric(name).read(run) is None


def test_readers_on_a_run(monkeypatch):
    pd, host, records = hand_trace()
    pt = progtrace.reduce_profile(pd, host, records)
    monkeypatch.setattr(progtrace, "for_run", lambda run: pt)
    run = NS(trace=NS(window_s=0.1), completed=2, steps=[1],
             peak={"flops": 1e12, "hbm_bytes_per_s": 1e9},
             spec=cellspec.NetSpec(
                 cellspec.find_cell("vgg16-f32.single").config))
    assert _metric("engine.host_ms_per_step").read(run) == \
        pytest.approx(15.0)
    # the copy started (2 ms), then waited for (17 ms)
    assert _metric("replica.h2d_ms_per_step").read(run) == \
        pytest.approx(19.0)
    # wait 13 + h2d_wait 5 + d2h 2 ms of idle over two requests
    assert _metric("replica.device_idle_ms.single").read(run) == \
        pytest.approx(10.0)
    m = _metric("conv_roofline.full_res")
    want = sum(m.conv_bound_s(n, 1, "float32", run.peak)
               for n in m.full_res_convs(run.spec)) / 0.030 * 100
    assert m.read(run) == pytest.approx(want)
    # a conv whose kernel carries no name: no reading
    pt.kernel_s_by_layer.pop("conv2")
    assert m.read(run) is None


def test_span_run_at_rehearsal_size():
    root = cellspec.ROOT
    p = subprocess.run(
        [sys.executable, os.path.join(root, "bench", "spanrun.py"),
         "--workload", "vgg16-f32.single", "--seed", str(2**33 + 1),
         "--seconds", "0.4", "--trace", "0", "--rehearse"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    prog = res["program"]
    assert prog["steps"] > 0 and prog["dropped"] == 0
    count = prog["ms_count"]
    for name in ("engine.step", "engine.batch", "replica.forward",
                 "engine.finish", "replica.h2d", "replica.dispatch",
                 "replica.wait", "replica.h2d_wait", "replica.d2h",
                 "server.step", "server.resolve"):
        assert count[name] == prog["steps"], (name, count)
    assert list(res)[-1] == "checks"


DATA_SPANS = os.path.join(os.path.dirname(__file__), "data_spans")
STEP_SPANS = {"engine.step", "engine.batch", "replica.forward",
              "engine.finish", "replica.h2d", "replica.dispatch",
              "replica.wait", "replica.h2d_wait", "replica.d2h",
              "server.step", "server.resolve"}


def test_recorded_v5e_trace_by_name():
    """Half a second of ``vgg16-f32.single`` traced on one TPU v5e with
    the program's span log on (``spanrun.py --keep``): every conv kernel
    under its layer's name, and the window's idle time under program
    spans, each request one step."""
    with open(os.path.join(DATA_SPANS, "program_spans.json")) as f:
        records = [tuple(r) for r in json.load(f)]
    pt = progtrace.read_dir(DATA_SPANS, records)
    assert pt is not None and 0.2 < pt.window_s < 1.0
    convs = {f"conv{i}" for i in range(1, 14)}
    assert set(pt.kernel_s_by_layer) == convs
    assert pt.kernel_s_by_layer["conv1"] > pt.kernel_s_by_layer["conv13"]
    steps = progtrace.by_step(pt.spans)
    assert len(steps) > 50
    assert all(set(names) == STEP_SPANS and
               all(len(v) == 1 for v in names.values())
               for names in steps.values())
    idle = pt.idle_by_span
    assert {"replica.h2d", "replica.h2d_wait", "replica.wait",
            "replica.d2h", "server.step"} <= set(idle)
    assert idle.get(progtrace.NO_SPAN, 0.0) < 0.1 * sum(idle.values())
