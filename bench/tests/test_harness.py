"""Whole runs of the harness on the CPU at the configurations' rehearsal
size: sound runs are correct, runs with the timed path broken underneath
are not, a run without a TPU or without the program prints no result,
and a new cell made of new files alone runs."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import cellspec
import harness

CELLS = ["vgg16-f32.saturate", "unet-f32.batch8", "vgg16-f32.single"]


def rehearse(name, wrap_engine=None, seed=2**31 + 5):
    cell = cellspec.find_cell(name)
    return harness.run(cell, seed=seed, seconds=0.5, trace=False,
                       rehearse=True, t_start=time.monotonic(),
                       log=lambda m: None, wrap_engine=wrap_engine)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    res = rehearse(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {}          # a rehearsal names no metric
    assert all(k.startswith("cpu.") for k in res["rehearsal_metrics"])
    assert list(res)[-1] == "checks"


def _break(fault):
    def wrap(engine):
        def broken(fn):
            def call(batch):
                out = np.array(fn(batch))
                if fault == "answer_altered":
                    out.reshape(len(out), -1)[0, 0] += \
                        1e-3 * np.abs(out).max()
                else:                     # rows answered out of order
                    out = out[::-1].copy()
                return out
            return call
        engine.replicas = [dataclasses.replace(r, fn=broken(r.fn))
                           for r in engine.replicas]
    return wrap


@pytest.mark.parametrize("name,fault", [
    (name, "answer_altered") for name in CELLS] + [
    (name, "rows_swapped") for name in CELLS[:2]])
def test_broken_timed_path_is_not_correct(name, fault):
    res = rehearse(name, wrap_engine=_break(fault))
    assert res["correct"] is False
    assert res["checks"]["max_rel_err"]["value"] > \
        res["checks"]["max_rel_err"]["limit"]


def _run(root, *args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, os.path.join(root, "bench", "run.py"), *args],
        cwd=root, env=e, capture_output=True, text=True, timeout=600)


def _copy_benchmark(dst, with_program=True):
    shutil.copy(os.path.join(cellspec.ROOT, "BENCHMARK.json"), dst)
    shutil.copytree(cellspec.BENCH_DIR, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if with_program:
        os.symlink(os.path.join(cellspec.ROOT, "src"),
                   os.path.join(dst, "src"))


def test_no_tpu_prints_no_result():
    p = _run(cellspec.ROOT, "--workload", "vgg16-f32.single", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    _copy_benchmark(str(tmp_path), with_program=False)
    p = _run(str(tmp_path), "--workload", "vgg16-f32.single", "--seed", "1",
             "--seconds", "1", "--trace", "0", "--rehearse")
    assert p.returncode != 0
    assert p.stdout == ""


TINY_CONFIG = {
    "name": "tiny-f32", "source": "a test", "entry": "engine",
    "reference": "cnn", "dtype": "float32", "matmul_precision": "highest",
    "input": {"size": 16, "channels": 3},
    "nodes": [{"name": "c1", "op": "conv", "k": 3, "pad": 1, "cout": 8},
              {"name": "p1", "op": "pool", "window": 2, "stride": 2},
              {"name": "c2", "op": "conv", "k": 3, "pad": 1, "cout": 16}],
    "head": {"classes": 10},
    "rehearsal": {"image": 16, "channel_divisor": 1},
    "check": {"number": "max_rel_err", "limit": 1e-5}}
TINY_TRAFFIC = {"kind": "closed", "clients": 3, "buckets": [2, 4],
                "max_wait_ms": 1.0, "pool": 8, "warmup_s": 0.3}
TINY_METRIC = '''"""Mean rows per engine step."""


def read(run):
    return sum(run.steps) / len(run.steps) if run.steps else None
'''


def test_new_cell_from_new_files_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric, each a new
    file, plus their entries in BENCHMARK.json, make a cell that runs:
    no file of the harness changes.  So do entries for files that are
    there already."""
    root = str(tmp_path)
    _copy_benchmark(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "tiny-f32.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    with open(os.path.join(bench, "traffic", "trio.json"), "w") as f:
        json.dump(TINY_TRAFFIC, f)
    with open(os.path.join(bench, "metrics", "engine.rows_per_step.py"),
              "w") as f:
        f.write(TINY_METRIC)
    bj = os.path.join(root, "BENCHMARK.json")
    b = cellspec.read_json(bj)
    b["configs"].append({"name": "tiny-f32", "source": "a test",
                         "file": "bench/configs/tiny-f32.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "tiny-f32.trio", "config": "tiny-f32",
                           "traffic": "trio", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "latency_p50_ms":
            m["workloads"].append("tiny-f32.trio")
    b["per_layer"].append({"name": "engine.rows_per_step", "unit": "rows",
                           "better": "higher", "source": "program_counter",
                           "layer": "serving front",
                           "moves": "latency_p50_ms",
                           "workloads": ["tiny-f32.trio"]})
    # the open-loop mix and the queue-wait reader kept under bench/ for
    # the poisson cell (PERF.md): entries alone bring them back
    b["workloads"].append({"name": "vgg16-f32.poisson", "config": "vgg16-f32",
                           "traffic": "poisson", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "serve.queue_wait_p95_ms", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "serving front",
                           "moves": "latency_p95_ms",
                           "workloads": ["vgg16-f32.poisson"]})
    with open(bj, "w") as f:
        json.dump(b, f)
    p = _run(root, "--workload", "vgg16-f32.poisson", "--seed", "9",
             "--seconds", "0.5", "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["checks"]
    assert res["rehearsal_metrics"]["cpu.serve.queue_wait_p95_ms"]["value"] >= 0
    for trace in ("0", "1"):
        p = _run(root, "--workload", "tiny-f32.trio", "--seed",
                 str(2**40 + 3), "--seconds", "0.5", "--trace", trace,
                 "--rehearse")
        assert p.returncode == 0, p.stderr[-3000:]
        res = json.loads(p.stdout.strip().splitlines()[-1])
        assert res["correct"], res["checks"]
        got = res["rehearsal_metrics"]
        if trace == "1":
            assert 1 <= got["cpu.engine.rows_per_step"]["value"] <= 4
        else:
            assert set(got) == {"cpu.latency_p50_ms", "cpu.setup_s"}
