"""Self-tests of the benchmark, on the CPU at small sizes:

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests

They import the benchmark's modules from ``bench/`` and the program
from ``src/``; every autotune record goes to a per-test file."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _isolated_convtune_cache(tmp_path, monkeypatch):
    from repro.core import autotune
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "convtune.json"))
    autotune.reset_memory_cache()
    yield
    autotune.reset_memory_cache()
