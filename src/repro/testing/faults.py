"""Fault-injection harness for the guarded execution stack (DESIGN.md §9).

Context managers that break one tier (or one persistence path) in a
controlled, reversible way, so the chaos suite
(``tests/test_resilience.py``) can assert that every fallback edge of
``core.guard.run_chain`` still matches the ``ref`` oracle and emits
exactly the expected demotion events:

* :func:`lowering_failure` — the named conv tier raises
  :class:`InjectedFault` instead of lowering/running its kernel.
* :func:`nan_poison` — the named tier computes normally, then corrupts
  one output element to NaN (exercises the ``REPRO_CONV_GUARD=1``
  numerics guard).
* :func:`crash_before_publish` — the atomic-rename publish step of the
  autotune cache / checkpoint manager raises :class:`InjectedCrash`
  *before* the rename, simulating a mid-write process death: the
  published artifact must be untouched (and the next load must still
  see the previous consistent state).
* :func:`corrupt_cache` / :func:`flip_byte` / :func:`truncate_file` —
  on-disk corruption for the quarantine / integrity-verification tests.

Injection is by module-attribute patching of the exact names the
dispatch layer resolves at call time (``repro.kernels.ops.trim_conv2d``,
``...ops.sharded_conv2d``, ``repro.kernels.trim_conv2d_fused.
_fused_forward``) — the ``custom_vjp`` primal bodies look these up as
module globals per call, so a patch is seen without re-importing
anything.  Every manager restores the original attribute on exit and
yields a :class:`FaultHandle` whose ``calls`` counter records how many
times the fault actually fired (the memoized-demotion tests rely on it).

``python -m repro.testing.faults --report out.json`` runs a small conv
problem under each injected fault with the numerics guard on and dumps
``guard.events()`` — the CI chaos step uploads that JSON next to the
benchmark artifacts.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os

__all__ = [
    "InjectedFault", "InjectedCrash", "FaultHandle", "TIER_TARGETS",
    "PUBLISH_TARGETS", "lowering_failure", "nan_poison",
    "crash_before_publish", "corrupt_cache", "flip_byte", "truncate_file",
]


class InjectedFault(RuntimeError):
    """Raised by an injected kernel-lowering/runtime failure."""


class InjectedCrash(RuntimeError):
    """Raised by an injected mid-write crash (before the atomic rename)."""


class FaultHandle:
    """Returned by the injection context managers; ``calls`` counts how
    many times the injected fault actually fired."""

    def __init__(self) -> None:
        self.calls = 0


#: tier name -> the (module, attribute) pairs the dispatch layer
#: resolves per call: the ``pallas`` tier is the MXU kernel and, for a
#: depthwise conv, the VPU kernel
TIER_TARGETS = {
    "fused": (("repro.kernels.trim_conv2d_fused", "_fused_forward"),),
    "pallas": (("repro.kernels.ops", "trim_conv2d"),
               ("repro.kernels.ops", "trim_depthwise_conv2d")),
    "sharded": (("repro.kernels.ops", "sharded_conv2d"),),
    "q8": (("repro.kernels.ops", "_q8_forward"),),
}

#: persistence path -> (module, attribute) of its patchable publish alias
PUBLISH_TARGETS = {
    "autotune": ("repro.core.autotune", "_publish"),
    "checkpoint": ("repro.checkpoint.manager", "_publish"),
}


@contextlib.contextmanager
def _patched(targets, make_replacement):
    """Patch every ``(module, attr)`` of ``targets`` with
    ``make_replacement(original)`` for the duration of the block; always
    restore."""
    saved = []
    try:
        for module_name, attr in targets:
            mod = importlib.import_module(module_name)
            saved.append((mod, attr, getattr(mod, attr)))
            setattr(mod, attr, make_replacement(saved[-1][2]))
        yield
    finally:
        for mod, attr, orig in reversed(saved):
            setattr(mod, attr, orig)


@contextlib.contextmanager
def lowering_failure(tier: str, message: str = "injected lowering failure"):
    """Make the named conv tier (``fused``/``pallas``/``sharded``) raise
    :class:`InjectedFault` on every call."""
    targets = TIER_TARGETS[tier]
    handle = FaultHandle()

    def make(orig):
        def boom(*args, **kwargs):
            handle.calls += 1
            raise InjectedFault(f"{tier}: {message}")
        return boom

    with _patched(targets, make):
        yield handle


@contextlib.contextmanager
def nan_poison(tier: str = "pallas"):
    """Make the named tier compute normally, then poison one output
    element to NaN — detectable only by the ``REPRO_CONV_GUARD=1``
    numerics guard (eager execution)."""
    targets = TIER_TARGETS[tier]
    handle = FaultHandle()

    def make(orig):
        def poisoned(*args, **kwargs):
            import jax.numpy as jnp
            handle.calls += 1
            out = orig(*args, **kwargs)
            return out.at[(0,) * out.ndim].set(jnp.nan)
        return poisoned

    with _patched(targets, make):
        yield handle


@contextlib.contextmanager
def crash_before_publish(target: str):
    """Make the named persistence path (``autotune``/``checkpoint``)
    raise :class:`InjectedCrash` instead of performing its atomic rename:
    the write happened to the temp location, the publish never did."""
    targets = (PUBLISH_TARGETS[target],)
    handle = FaultHandle()

    def make(orig):
        def crash(*args, **kwargs):
            handle.calls += 1
            raise InjectedCrash(f"{target}: crashed before publish")
        return crash

    with _patched(targets, make):
        yield handle


def corrupt_cache(path: str, mode: str = "truncate") -> None:
    """Corrupt an autotune cache file in place.

    ``truncate``: cut the JSON mid-document; ``garbage``: non-JSON bytes;
    ``wrong_version``: valid JSON with an unknown schema version;
    ``empty``: zero bytes.
    """
    if mode == "truncate":
        with open(path, "r+", encoding="utf-8") as f:
            data = f.read()
            f.seek(0)
            f.write(data[: max(1, len(data) // 2)])
            f.truncate()
    elif mode == "garbage":
        with open(path, "wb") as f:
            f.write(b"\x00not json\xff")
    elif mode == "wrong_version":
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 999, "entries": {}}, f)
    elif mode == "empty":
        with open(path, "wb"):
            pass
    else:
        raise ValueError(f"unknown corruption mode {mode!r}")


def flip_byte(path: str, offset: int = 0) -> None:
    """XOR one byte of ``path`` (bit-flip corruption; offset from the
    middle of the file when the given offset is 0 and the file is big
    enough, so zip/npz headers stay intact and only sha256 catches it)."""
    size = os.path.getsize(path)
    if offset == 0 and size > 256:
        offset = size // 2
    with open(path, "r+b") as f:
        f.seek(offset)
        b = f.read(1)
        f.seek(offset)
        f.write(bytes([b[0] ^ 0xFF]))


def truncate_file(path: str, frac: float = 0.5) -> None:
    """Truncate ``path`` to ``frac`` of its size."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(max(0, int(size * frac)))


def _demo_report(out_path: str) -> None:
    """Run a small conv under injected faults with the numerics guard on
    and dump ``guard.events()`` — the CI chaos artifact."""
    os.environ.setdefault("REPRO_CONV_GUARD", "1")
    import numpy as np
    import jax.numpy as jnp
    from repro.core import guard
    from repro.kernels import ops, ref

    guard.reset()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 16, 16, 8), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 8, 16), jnp.float32)
    oracle = ref.conv2d(x, w, activation="relu")

    with lowering_failure("pallas"):
        y = ops.conv2d(x, w, activation="relu", layer="demo-lowering")
    lowering_ok = bool(np.allclose(np.asarray(y), np.asarray(oracle),
                                   atol=1e-5))
    events = guard.events()

    guard.reset()        # forget the memo so the pallas tier runs again
    with nan_poison("pallas"):
        y2 = ops.conv2d(x, w, activation="relu", layer="demo-numerics")
    numerics_ok = bool(np.allclose(np.asarray(y2), np.asarray(oracle),
                                   atol=1e-5))
    events += guard.events()

    payload = {
        "guard_env": os.environ.get(guard.GUARD_ENV),
        "lowering_demotion_matches_ref": lowering_ok,
        "numerics_demotion_matches_ref": numerics_ok,
        "events": events,
    }
    os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path}: {len(payload['events'])} events, "
          f"lowering_ok={lowering_ok} numerics_ok={numerics_ok}")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--report", required=True,
                    help="write guard.events() JSON after a demo fault run")
    _demo_report(ap.parse_args().report)
