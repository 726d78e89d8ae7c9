"""Guarded conv dispatch: tier fallback chains + demotion events (§9).

The execution stack has four conv tiers — fused residency-group
megakernels (§8), sharded shard_map execution (§6), the per-layer Pallas
carry/halo kernels (§2–§4), and the XLA ``ref`` oracle — and before this
module any lowering, compile, or runtime failure in a fast tier was a
hard crash.  The paper's silicon assumes fault-free fixed-function
datapaths; a production serving system cannot.  ``run_chain`` is the
defined failure model underneath the whole stack:

* **Demotion, not crash.**  A tier chain is a list of ``(tier, thunk)``
  attempts ordered fastest-first.  An exception raised by a non-final
  tier demotes the call to the next tier; the final tier runs unguarded
  (its errors propagate — a genuinely invalid problem still fails
  loudly, from the simplest engine that can diagnose it).

* **Bugs are not demoted.**  :data:`BUG_ERRORS` (``AttributeError``,
  ``TypeError``, ``NameError``, ``ImportError``) come from this
  repository's own code — a renamed API, a wrong argument — never from
  a backend's lowering, compile or runtime.  They propagate from every
  tier, so a broken fast path fails instead of running slower.

* **Structured events.**  Every demotion appends one event to a bounded
  ring buffer (:data:`RING_SIZE`); :func:`events` returns them for
  tests, benchmarks (the ``guard`` column of ``benchmarks/run.py
  --json``) and the examples' degraded-mode report.

* **Memoized demotions.**  A failed ``(problem key, tier)`` pair is
  remembered (:func:`demotions`) and skipped on subsequent calls, so a
  broken config is attempted — and reported — exactly once, not once
  per call.  ``reset()`` clears the memo (e.g. after upgrading a
  backend).

* **Opt-in numerics guard.**  With ``REPRO_CONV_GUARD=1`` the output of
  every non-final tier is finite-checked; NaN/Inf demotes with
  ``kind="numerics"`` and the producing layer named.  The check needs a
  concrete array, so it is active in eager execution and inert under a
  ``jax.jit`` trace (tracers cannot be inspected without a host
  callback) — run the chaos suite eager.

* **Strict mode.**  ``REPRO_CONV_GUARD_STRICT=1`` disables demotion
  entirely (first tier runs bare, errors propagate) — the debugging
  escape hatch when a silent fallback would mask the bug you are
  chasing.

Exceptions caught during a *trace* still demote: the thunk raises while
jax traces it, so a jitted ``cnn_apply_from_layers`` falls from fused to
per-layer within the same trace.  Only post-compile runtime faults of a
jitted computation are beyond the guard's reach.

This module imports nothing heavy at module level (no jax) so benchmark
entry points can import it before choosing an XLA device configuration.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading

GUARD_ENV = "REPRO_CONV_GUARD"          # "1" -> NaN/Inf numerics guard on
STRICT_ENV = "REPRO_CONV_GUARD_STRICT"  # "1" -> re-raise, never demote
RING_SIZE = 256

#: exception types that mean a bug in our code, not a tier fault: they
#: propagate from every tier instead of demoting
BUG_ERRORS = (AttributeError, TypeError, NameError, ImportError)

#: canonical tier order, fastest first — chains are sub-sequences of
#: this (the ``q8`` int8 kernel tier only appears in the quantized
#: chain ``q8 -> pallas -> ref`` of ``ops._conv2d_q8``, DESIGN.md §11)
TIER_CHAIN = ("fused", "sharded", "q8", "pallas", "ref")

_LOCK = threading.Lock()
_EVENTS: collections.deque = collections.deque(maxlen=RING_SIZE)
_DEMOTED: dict[tuple[str, str], dict] = {}     # (key, tier) -> event
_SEQ = itertools.count()


def numerics_enabled() -> bool:
    """True when ``REPRO_CONV_GUARD=1`` turned the NaN/Inf guard on."""
    return os.environ.get(GUARD_ENV, "0") not in ("", "0")


def strict() -> bool:
    """True when ``REPRO_CONV_GUARD_STRICT=1`` disables demotion."""
    return os.environ.get(STRICT_ENV, "0") not in ("", "0")


def events() -> list[dict]:
    """Demotion events, oldest first (bounded by :data:`RING_SIZE`).

    Event schema (every value JSON-serializable)::

        {"seq": int,            # monotonic within the process
         "tier": str,           # the tier that failed
         "to": str,             # the tier the call demoted to
         "key": str,            # problem key (shape/stride/groups/dtype)
         "kind": "error" | "numerics",
         "error": str,          # exception repr, or the numerics finding
         "layer": str | None}   # producing layer, when the caller knows
    """
    with _LOCK:
        return [dict(e) for e in _EVENTS]


def demotions() -> dict[tuple[str, str], dict]:
    """The memo of broken ``(problem key, tier)`` pairs -> first event."""
    with _LOCK:
        return {k: dict(v) for k, v in _DEMOTED.items()}


def is_demoted(key: str, tier: str) -> bool:
    """Has ``tier`` already failed for this problem key?"""
    with _LOCK:
        return (key, tier) in _DEMOTED


def clear_events() -> None:
    """Drop the event ring (the demotion memo survives)."""
    with _LOCK:
        _EVENTS.clear()


def reset() -> None:
    """Forget everything: events AND memoized demotions (tests; or after
    an environment change that may have fixed a previously broken tier).
    """
    with _LOCK:
        _EVENTS.clear()
        _DEMOTED.clear()


def problem_key(op: str, x_shape, w_shape, *, stride: int = 1,
                padding: str = "same", groups: int = 1,
                dtype: str = "float32") -> str:
    """Cheap structural key for one conv problem — what demotions are
    memoized under.  Deliberately backend-free (unlike autotune keys):
    the guard must not trigger jax initialization, and a tier broken on
    this process's backend is broken for the life of the process."""
    xs = "x".join(str(int(d)) for d in x_shape)
    ws = "x".join(str(int(d)) for d in w_shape)
    return f"{op}:i{xs}:w{ws}:s{stride}:{padding}:g{groups}:{dtype}"


def _record(tier: str, to: str, key: str, kind: str, error: str,
            layer: str | None) -> None:
    event = {"seq": next(_SEQ), "tier": tier, "to": to, "key": key,
             "kind": kind, "error": error[:500], "layer": layer}
    with _LOCK:
        # first failure wins the memo; the ring keeps every distinct one
        if (key, tier) not in _DEMOTED:
            _DEMOTED[(key, tier)] = event
            _EVENTS.append(event)


def _finite(out) -> bool:
    """All inexact leaves of ``out`` finite?  Returns True (check
    skipped) for tracers — only concrete arrays can be inspected."""
    import jax
    for leaf in jax.tree_util.tree_leaves(out):
        if not hasattr(leaf, "dtype"):
            continue
        try:
            import jax.numpy as jnp
            if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                continue
            if not bool(jnp.isfinite(leaf).all()):
                return False
        except Exception:       # tracer (jit trace): cannot concretize
            return True
    return True


def run_chain(key: str, attempts, *, layer: str | None = None):
    """Run the first healthy tier of ``attempts``; demote on failure.

    ``attempts`` is an ordered list of ``(tier_name, thunk)`` pairs,
    fastest tier first.  Semantics:

    * A tier already memoized as broken for ``key`` is skipped silently
      (no new event — demotions are reported exactly once per problem).
    * A non-final tier that raises records a ``kind="error"`` demotion
      event and falls through to the next tier — unless the exception
      is one of :data:`BUG_ERRORS`, which propagates.
    * With the numerics guard on (``REPRO_CONV_GUARD=1``), a non-final
      tier whose concrete output contains NaN/Inf records a
      ``kind="numerics"`` demotion and recomputes on the next tier.
    * The final tier runs unguarded: its exceptions propagate, and its
      output is returned as-is.
    * ``REPRO_CONV_GUARD_STRICT=1``: the first tier runs bare (crash
      semantics restored for debugging).

    ``layer`` names the producing layer in the event (the netplan
    execution path passes layer names through ``ops.conv2d``).
    """
    attempts = list(attempts)
    if not attempts:
        raise ValueError("run_chain needs at least one tier")
    if strict():
        return attempts[0][1]()
    last = len(attempts) - 1
    for i, (tier, thunk) in enumerate(attempts):
        final = i == last
        if not final and is_demoted(key, tier):
            continue
        if final:
            return thunk()
        to = attempts[i + 1][0]
        try:
            out = thunk()
        except BUG_ERRORS:
            raise
        except Exception as e:  # lowering/compile/runtime fault -> demote
            _record(tier, to, key, "error",
                    f"{type(e).__name__}: {e}", layer)
            continue
        if numerics_enabled() and not _finite(out):
            _record(tier, to, key, "numerics",
                    "non-finite output (NaN/Inf)", layer)
            continue
        return out
    raise AssertionError("unreachable: final tier always returns/raises")
