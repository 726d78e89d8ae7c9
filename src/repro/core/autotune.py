"""Per-layer conv autotuner with a persistent JSON cache (DESIGN.md §4).

The companion TrIM paper (arXiv:2408.01254) shows tile-shape choice
dominates achievable efficiency per layer, and "Computing-In-Memory
Dataflow for Minimal Buffer Traffic" (arXiv:2508.14375) picks its dataflow
per layer from an analytical buffer-traffic model.  This module is that
selection layer for the TPU kernel: it searches the
``(tile_h, tile_cout, dataflow)`` space of :class:`~repro.core.conv_plan.
ConvPlan`, scores candidates by the plan's own roofline step time
(``max(T_comp, T_mem)`` over the plan's analytical HBM bytes), optionally
refines the leaders by wall-clock measurement of the real kernel, and
persists the winner in a JSON cache that ``ops.conv2d`` consults on every
call.

Cache location: ``$REPRO_CONVTUNE_CACHE`` if set, else
``~/.cache/repro/convtune.json``.  Schema (version 1)::

    {"version": 1,
     "entries": {"<key>": {"tile_h": int, "tile_cout": int,
                           "dataflow": "carry"|"halo",
                           "source": "model"|"measured",
                           "model_step_time_s": float,
                           "measured_us": float|null}}}

Keys are ``conv2d:n..h..w..cin..cout..k..s..p..g..:<dtype>:<backend>`` —
one entry per (shape, stride, pad, groups, dtype, backend) problem, so a
cache tuned on TPU never feeds knobs to an interpret-mode CPU run and
vice versa.

Robustness (DESIGN.md §9): ``store`` takes a ``.lock`` sidecar file
lock and re-reads + merges the on-disk entries before the atomic
``os.replace``, so concurrent processes sharing a cache path (e.g. CI
jobs) never drop each other's records.  An unreadable or
wrong-schema-version cache file is *quarantined* — renamed to
``convtune.json.corrupt-<pid>`` with a warning — never silently reset,
so a corruption event stays diagnosable.  Consult-site lookups validate
each record structurally AND against the current plan geometry
(``ConvPlan.build`` with the record's knobs); a malformed record is a
miss, warned once per (path, key).
"""

from __future__ import annotations

import contextlib
import json
import math
import os
import time
import warnings

from repro.core.conv_plan import (KERNEL_VMEM_BUDGET, ConvPlan,
                                   DepthwisePlan, input_grad_geometry)
from repro.core.roofline import conv_plan_roofline, dtype_width


def _resolve_bytes(dtype_bytes, dtype: str) -> int:
    """Width of the tuned problem's activations: an explicit
    ``dtype_bytes`` wins, otherwise it is derived from ``dtype`` via the
    shared :func:`repro.core.roofline.dtype_width` table (so a bf16 or
    int8 tune never scores with f32 traffic)."""
    return dtype_width(dtype) if dtype_bytes is None else dtype_bytes

try:
    import fcntl
except ImportError:          # non-POSIX: cooperative locking unavailable
    fcntl = None

DATAFLOWS = ("carry", "halo")
CACHE_ENV = "REPRO_CONVTUNE_CACHE"
AUTOTUNE_ENV = "REPRO_CONV_AUTOTUNE"      # set to "0" to disable lookups
_SCHEMA_VERSION = 1

# path -> entries dict; "missing file" memoized as {} so the hot-path
# lookup in ops.conv2d costs one dict probe, not a stat per call.
_MEM: dict[str, dict] = {}

# (path, key) pairs already warned about — one warning per bad record,
# not one per conv call.
_WARNED: set = set()

# patchable alias: the fault harness (repro.testing.faults) swaps this
# to simulate a crash after the temp write but before the publish
_publish = os.replace


# ---------------------------------------------------------------------------
# Cache file
# ---------------------------------------------------------------------------

def cache_path(path: str | None = None) -> str:
    """Resolve the cache file: explicit arg > $REPRO_CONVTUNE_CACHE >
    ~/.cache/repro/convtune.json."""
    if path:
        return path
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "convtune.json")


def reset_memory_cache() -> None:
    """Drop the in-process cache memo (tests / after external writes)."""
    _MEM.clear()
    _WARNED.clear()


def _quarantine(path: str, reason: str) -> None:
    """Move an unusable cache file aside (never silently discard it)."""
    dest = f"{path}.corrupt-{os.getpid()}"
    try:
        os.replace(path, dest)
    except OSError:
        dest = "<unmovable>"
    warnings.warn(
        f"autotune cache {path} is unusable ({reason}); quarantined to "
        f"{dest} and starting a fresh cache", RuntimeWarning,
        stacklevel=3)


def _read_disk(path: str) -> dict:
    """Fresh (un-memoized) read of the on-disk entries.

    A missing file is an empty cache.  Corrupt JSON, a non-dict
    document, or an empty file is quarantined.  A ``version`` other than
    ours is also quarantined: version 1 is the first schema, so there is
    nothing to migrate from — a future reader that understands newer
    versions should migrate here instead; until then the file is
    preserved under its ``.corrupt-<pid>`` name for inspection rather
    than silently dropped.
    """
    try:
        with open(path) as f:
            data = json.load(f)
    except FileNotFoundError:
        return {}
    except (OSError, ValueError) as e:
        _quarantine(path, f"unreadable: {type(e).__name__}: {e}")
        return {}
    if not isinstance(data, dict) or not isinstance(
            data.get("entries", {}), dict):
        _quarantine(path, "not a cache document")
        return {}
    version = data.get("version")
    if version != _SCHEMA_VERSION:
        _quarantine(path, f"schema version {version!r} != "
                          f"{_SCHEMA_VERSION} (no migration path)")
        return {}
    return dict(data["entries"]) if "entries" in data else {}


def _entries(path: str) -> dict:
    if path not in _MEM:
        _MEM[path] = _read_disk(path)
    return _MEM[path]


@contextlib.contextmanager
def _locked(path: str):
    """Hold the cache's ``.lock`` sidecar (blocking flock) — serializes
    the read-merge-replace in :func:`store` across processes.  The
    sidecar (not the cache file itself) carries the lock so the atomic
    ``os.replace`` of the data file never invalidates a held fd."""
    if fcntl is None:
        yield
        return
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path + ".lock", "w") as lk:
        fcntl.flock(lk, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lk, fcntl.LOCK_UN)


def lookup(key: str, path: str | None = None) -> dict | None:
    """Cached record for ``key``, or None."""
    return _entries(cache_path(path)).get(key)


def store(key: str, record: dict, path: str | None = None) -> str:
    """Insert/overwrite one record and persist the cache atomically.

    Under the ``.lock`` sidecar: re-read the on-disk entries and merge
    them over the in-memory memo (disk wins per key — last writer wins,
    no lost updates), apply this record, write a temp file, and publish
    with an atomic rename."""
    path = cache_path(path)
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with _locked(path):
        merged = {**_MEM.get(path, {}), **_read_disk(path)}
        merged[key] = dict(record)
        _MEM[path] = merged
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"version": _SCHEMA_VERSION, "entries": merged}, f,
                      indent=1, sort_keys=True)
        try:
            _publish(tmp, path)
        except BaseException:
            # a simulated (or real) crash-before-publish must not leave
            # the temp file looking like a cache
            with contextlib.suppress(OSError):
                os.remove(tmp)
            raise
    return path


# ---------------------------------------------------------------------------
# Keys
# ---------------------------------------------------------------------------

def make_key(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
             groups: int = 1, dtype: str = "float32",
             backend: str | None = None, op: str = "conv2d") -> str:
    """Cache key for one conv problem.  ``x_shape`` is the shape the
    kernel actually sees (i.e. *after* any 'same' pre-padding, with
    ``pad`` the residual symmetric padding).  ``op`` namespaces the
    record: ``"conv2d"`` for forward (and the input-grad conv, which IS
    a forward problem over its transformed shapes), ``"conv2d_wgrad"``
    for the weight-gradient kernel — backward records can never collide
    with forward ones even when the raw shape tuple matches."""
    if backend is None:
        import jax
        backend = jax.default_backend()
    n, h, w, cin = x_shape
    kh, kw, _, cout = w_shape
    return (f"{op}:n{n}h{h}w{w}cin{cin}cout{cout}k{kh}x{kw}"
            f"s{stride}p{pad}g{groups}:{dtype}:{backend}")


def _valid_record(rec, stride: int) -> bool:
    return (isinstance(rec, dict)
            and isinstance(rec.get("tile_h"), int)
            and isinstance(rec.get("tile_cout"), int)
            and rec.get("dataflow") in DATAFLOWS
            and rec["tile_h"] >= stride and rec["tile_h"] % stride == 0
            and rec["tile_cout"] >= 1)


def _reject(key: str, reason: str, path: str | None) -> None:
    """Treat a bad record as a miss; warn once per (path, key) so a
    hand-edited/truncated record is visible without flooding the hot
    path (one conv may be called millions of times)."""
    tag = (cache_path(path), key)
    if tag in _WARNED:
        return
    _WARNED.add(tag)
    warnings.warn(
        f"ignoring malformed autotune record {key!r}: {reason} "
        "(treated as a cache miss; delete or re-tune the entry)",
        RuntimeWarning, stacklevel=3)


def knobs_for(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
              groups: int = 1, dtype: str = "float32",
              backend: str | None = None, op: str = "conv2d",
              path: str | None = None) -> dict | None:
    """The cached (validated) knobs for a problem, or None — the lookup
    ``ops.conv2d`` performs by default.  Honors ``REPRO_CONV_AUTOTUNE=0``.

    Validation is structural (required keys/types/knob invariants) AND
    geometric: the record's knobs must build a :class:`ConvPlan` for the
    *current* problem.  Either failure is a miss + one warning — a
    truncated or hand-edited record degrades to the default plan instead
    of raising ``KeyError`` inside the dispatch path.
    """
    if os.environ.get(AUTOTUNE_ENV, "1") == "0":
        return None
    key = make_key(x_shape, w_shape, stride=stride, pad=pad,
                   groups=groups, dtype=dtype, backend=backend, op=op)
    rec = lookup(key, path)
    if rec is None:
        return None
    if not _valid_record(rec, stride):
        _reject(key, f"bad shape/type/knobs: {rec!r}", path)
        return None
    try:        # knob sanity against the current plan geometry
        plan = ConvPlan.build(x_shape, w_shape, stride=stride, pad=pad,
                              groups=groups, dtype_bytes=dtype_width(dtype),
                              tile_h=rec["tile_h"],
                              tile_cout=rec["tile_cout"],
                              dataflow=rec["dataflow"])
        if plan.vmem_resident_bytes > KERNEL_VMEM_BUDGET:
            raise ValueError(
                f"resident {plan.vmem_resident_bytes} > VMEM budget "
                f"{KERNEL_VMEM_BUDGET} (the tuner only writes feasible "
                "plans)")
    except ValueError as e:
        _reject(key, f"knobs infeasible for current geometry: {e}", path)
        return None
    return rec


def _valid_wgrad_record(rec) -> bool:
    return (isinstance(rec, dict)
            and isinstance(rec.get("tile_go"), int)
            and isinstance(rec.get("tile_cout"), int)
            and rec["tile_go"] >= 1 and rec["tile_cout"] >= 1)


def weight_grad_knobs_for(x_shape, w_shape, *, stride: int = 1,
                          pad: int = 0, groups: int = 1,
                          dtype: str = "float32",
                          backend: str | None = None,
                          path: str | None = None) -> dict | None:
    """Cached (validated) knobs for the weight-gradient kernel of one
    forward problem, or None — the lookup the conv backward pass
    performs by default.  Honors ``REPRO_CONV_AUTOTUNE=0``."""
    if os.environ.get(AUTOTUNE_ENV, "1") == "0":
        return None
    key = make_key(x_shape, w_shape, stride=stride, pad=pad,
                   groups=groups, dtype=dtype, backend=backend,
                   op="conv2d_wgrad")
    rec = lookup(key, path)
    if rec is None:
        return None
    if not _valid_wgrad_record(rec):
        _reject(key, f"bad shape/type/knobs: {rec!r}", path)
        return None
    try:
        plan = ConvPlan.build_weight_grad(x_shape, w_shape, stride=stride,
                                          pad=pad, groups=groups,
                                          tile_go=rec["tile_go"],
                                          tile_cout=rec["tile_cout"])
        if plan.vmem_resident_bytes > KERNEL_VMEM_BUDGET:
            raise ValueError(
                f"resident {plan.vmem_resident_bytes} > VMEM budget "
                f"{KERNEL_VMEM_BUDGET} (the tuner only writes feasible "
                "plans)")
    except ValueError as e:
        _reject(key, f"knobs infeasible for current geometry: {e}", path)
        return None
    return rec


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------

def candidate_knobs(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                    groups: int = 1, dtype_bytes: int = 4,
                    vmem_bytes: int = KERNEL_VMEM_BUDGET) -> list[ConvPlan]:
    """VMEM-feasible candidate plans over (tile_h, tile_cout, dataflow).

    Strip-height ticks cover powers of two plus the two structurally
    special points: the auto default and the full-height strip
    ``(h_out + delta) * stride`` that collapses the grid to one strip per
    (image, group) — zero carry/halo traffic and the fewest grid steps.
    C_out ticks are whole 128-lane tiles or the whole per-group C_out:
    the chip's compiler tiles no other block width.
    """
    base = ConvPlan.build(x_shape, w_shape, stride=stride, pad=pad,
                          groups=groups, dtype_bytes=dtype_bytes)
    s = base.stride
    full_h = (base.h_out + base.delta) * s
    h_ticks = sorted({t for t in (s, 2 * s, 4 * s, 8 * s, 16 * s, 32 * s,
                                  base.tile_h, full_h) if t <= full_h})
    cout_pg = base.cout_per_group
    c_ticks = sorted({t for t in (128, 256, base.tile_cout,
                                  cout_pg) if t <= cout_pg})
    plans = []
    for dataflow in DATAFLOWS:
        for th in h_ticks:
            for tc in c_ticks:
                try:
                    plan = ConvPlan.build(
                        x_shape, w_shape, stride=stride, pad=pad,
                        groups=groups, dtype_bytes=dtype_bytes,
                        tile_h=th, tile_cout=tc, dataflow=dataflow)
                except ValueError:
                    continue
                if plan.vmem_resident_bytes <= vmem_bytes:
                    plans.append(plan)
    return plans


def _model_score(plan: ConvPlan) -> tuple:
    """Deterministic comparison key: modeled step time, then total HBM
    bytes, then prefer the order-independent halo grid on exact ties
    (its axes parallelize; the model cannot see that), then fewer grid
    steps."""
    terms = conv_plan_roofline("tune", plan)
    steps = plan.g_tiles * plan.co_tiles
    return (terms.step_time_s, plan.hbm_bytes()["total"],
            0 if plan.dataflow == "halo" else 1, steps, plan.tile_cout)


def _as_record(plan: ConvPlan, *, source: str,
               measured_us: float | None = None) -> dict:
    return dict(tile_h=plan.tile_h, tile_cout=plan.tile_cout,
                dataflow=plan.dataflow, source=source,
                model_step_time_s=conv_plan_roofline("tune",
                                                     plan).step_time_s,
                measured_us=measured_us)


def _measure_plan(plan: ConvPlan, *, stride, pad, groups,
                  dtype: str = "float32", warmup: int = 1,
                  iters: int = 2) -> float:
    """Wall-clock the real kernel for one candidate (us per call)."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.trim_conv2d import trim_conv2d
    rng = np.random.default_rng(0)
    dt = jnp.dtype(dtype)
    scale = None
    if jnp.issubdtype(dt, jnp.integer):
        # the int8 route: integer operands + a unit dequant scale row
        # (the knobs are timing-relevant, the calibration is not)
        x = jnp.asarray(rng.integers(-128, 128,
                                     (plan.n, plan.h, plan.w, plan.cin)), dt)
        w = jnp.asarray(rng.integers(-128, 128,
                                     (plan.kh, plan.kw, plan.cin_per_group,
                                      plan.cout)), dt)
        scale = jnp.ones((plan.cout,), jnp.float32)
    else:
        x = jnp.asarray(rng.standard_normal(
            (plan.n, plan.h, plan.w, plan.cin)), dt)
        w = jnp.asarray(rng.standard_normal(
            (plan.kh, plan.kw, plan.cin_per_group, plan.cout)) * 0.1, dt)

    def call():
        trim_conv2d(x, w, None, scale, stride=stride, pad=pad,
                    groups=groups, tile_h=plan.tile_h,
                    tile_cout=plan.tile_cout,
                    dataflow=plan.dataflow).block_until_ready()

    for _ in range(warmup):
        call()
    t0 = time.perf_counter()
    for _ in range(iters):
        call()
    return (time.perf_counter() - t0) / iters * 1e6


def tune(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
         groups: int = 1, dtype: str = "float32",
         dtype_bytes: int | None = None,
         backend: str | None = None, op: str = "conv2d",
         measure: bool = False,
         measure_top_k: int = 4, write: bool = True,
         path: str | None = None) -> dict:
    """Tune one conv problem and (by default) persist the winner.

    Model-guided: candidates are ranked by the plan's analytical roofline
    step time.  With ``measure=True`` the ``measure_top_k`` leaders are
    wall-clocked through the actual kernel and the fastest wins — this is
    how grid-step overheads the byte model cannot see (e.g. per-step
    interpreter cost, pipeline ramp) get captured.
    """
    plans = candidate_knobs(x_shape, w_shape, stride=stride, pad=pad,
                            groups=groups,
                            dtype_bytes=_resolve_bytes(dtype_bytes, dtype))
    if not plans:
        raise ValueError(f"no feasible candidates for {x_shape}/{w_shape}")
    ranked = sorted(plans, key=_model_score)
    if measure:
        leaders = ranked[:measure_top_k]
        timed = [(_measure_plan(p, stride=stride, pad=pad, groups=groups,
                                dtype=dtype),
                  i, p) for i, p in enumerate(leaders)]
        us, _, best = min(timed)
        record = _as_record(best, source="measured", measured_us=us)
    else:
        record = _as_record(ranked[0], source="model")
    if write:
        store(make_key(x_shape, w_shape, stride=stride, pad=pad,
                       groups=groups, dtype=dtype, backend=backend, op=op),
              record, path)
    return record


# ---------------------------------------------------------------------------
# The depthwise VPU kernel (DESIGN.md §14): its own knobs and keys
# ---------------------------------------------------------------------------

DEPTHWISE_OP = "conv2d_dw"


def depthwise_key(x_shape, k: int, *, stride: int = 1,
                  dtype: str = "float32",
                  backend: str | None = None) -> str:
    """Cache key of one depthwise problem (``x_shape`` padded, as the
    kernel sees it) in the ``conv2d_dw:`` namespace."""
    return make_key(x_shape, (k, k, 1, x_shape[3]), stride=stride, pad=0,
                    groups=x_shape[3], dtype=dtype, backend=backend,
                    op=DEPTHWISE_OP)


def depthwise_knobs_for(x_shape, k: int, *, stride: int = 1,
                        dtype: str = "float32",
                        backend: str | None = None,
                        path: str | None = None) -> dict | None:
    """The cached (validated) ``tile_h``/``tile_c``/``tile_n`` of one
    depthwise problem, or None — the lookup ``ops.conv2d`` makes for a
    conv it runs on the depthwise kernel.  Honors
    ``REPRO_CONV_AUTOTUNE=0``; a record whose knobs do not build a plan
    within the VMEM budget is a miss and one warning."""
    if os.environ.get(AUTOTUNE_ENV, "1") == "0":
        return None
    key = depthwise_key(x_shape, k, stride=stride, dtype=dtype,
                        backend=backend)
    rec = lookup(key, path)
    if rec is None:
        return None
    try:
        plan = DepthwisePlan.build(x_shape, k, stride=stride,
                                   dtype_bytes=dtype_width(dtype),
                                   tile_h=rec["tile_h"],
                                   tile_c=rec["tile_c"],
                                   tile_n=rec["tile_n"])
        if plan.vmem_resident_bytes > KERNEL_VMEM_BUDGET:
            raise ValueError(f"resident {plan.vmem_resident_bytes} > "
                             f"VMEM budget {KERNEL_VMEM_BUDGET}")
    except (KeyError, TypeError, ValueError) as e:
        _reject(key, f"knobs infeasible for current geometry: {e!r}", path)
        return None
    return rec


def candidate_depthwise_knobs(x_shape, k: int, *, stride: int = 1,
                              dtype_bytes: int = 4,
                              vmem_bytes: int = KERNEL_VMEM_BUDGET
                              ) -> list[DepthwisePlan]:
    """VMEM-feasible depthwise plans: strips that divide the output rows
    (no row is computed only to be dropped), channel tiles of whole
    128-lane multiples or all of C, and powers of two of images."""
    base = DepthwisePlan.build(x_shape, k, stride=stride,
                               dtype_bytes=dtype_bytes)
    n, c = x_shape[0], base.c
    h_ticks = [d for d in range(1, base.h_out + 1) if base.h_out % d == 0]
    c_ticks = sorted({t for t in (128, 256, 512, c)
                      if c % t == 0 and (t % 128 == 0 or t == c)})
    n_ticks = [t for t in (1, 2, 4, 8, 16, 32) if n % t == 0]
    plans = []
    for th in h_ticks:
        for tc in c_ticks:
            for tn in n_ticks:
                try:
                    plan = DepthwisePlan.build(
                        x_shape, k, stride=stride, dtype_bytes=dtype_bytes,
                        tile_h=th, tile_c=tc, tile_n=tn)
                except ValueError:
                    continue
                if plan.vmem_resident_bytes <= vmem_bytes:
                    plans.append(plan)
    return plans


def tune_depthwise(x_shape, k: int, *, stride: int = 1,
                   dtype: str = "float32",
                   dtype_bytes: int | None = None) -> dict:
    """The record of one depthwise problem, tuned by the plan's model
    (:attr:`DepthwisePlan.model_step_time_s`; ties go to fewer grid
    steps); :func:`tune_network` stores it under its ``conv2d_dw:``
    key."""
    plans = candidate_depthwise_knobs(
        x_shape, k, stride=stride,
        dtype_bytes=_resolve_bytes(dtype_bytes, dtype))
    if not plans:
        raise ValueError(f"no feasible depthwise plan for {x_shape}, K={k}")
    best = min(plans, key=lambda p: (p.model_step_time_s,
                                     math.prod(p.grid), p.tile_n))
    return dict(tile_h=best.tile_h, tile_c=best.tile_c,
                tile_n=best.tile_n, source="model",
                model_step_time_s=best.model_step_time_s)


# ---------------------------------------------------------------------------
# Backward shapes (DESIGN.md §5)
# ---------------------------------------------------------------------------

def candidate_weight_grad_knobs(x_shape, w_shape, *, stride: int = 1,
                                pad: int = 0, groups: int = 1,
                                dtype_bytes: int = 4,
                                vmem_bytes: int = KERNEL_VMEM_BUDGET) -> list:
    """VMEM-feasible ``WeightGradPlan`` candidates over
    (tile_go, tile_cout) — cotangent-strip ticks at powers of two plus
    the full-height strip, per-group C_out tiles as in the forward
    search."""
    base = ConvPlan.build_weight_grad(x_shape, w_shape, stride=stride,
                                      pad=pad, groups=groups,
                                      dtype_bytes=dtype_bytes)
    go_ticks = sorted({t for t in (1, 2, 4, 8, 16, 32, base.tile_go,
                                   base.h_out) if t <= base.h_out})
    cout_pg = base.cout_per_group
    c_ticks = sorted({t for t in (128, base.tile_cout, cout_pg)
                      if t <= cout_pg})
    plans = []
    for tg in go_ticks:
        for tc in c_ticks:
            try:
                plan = ConvPlan.build_weight_grad(
                    x_shape, w_shape, stride=stride, pad=pad,
                    groups=groups, dtype_bytes=dtype_bytes, tile_go=tg,
                    tile_cout=tc)
            except ValueError:
                continue
            if plan.vmem_resident_bytes <= vmem_bytes:
                plans.append(plan)
    return plans


def tune_weight_grad(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                     groups: int = 1, dtype: str = "float32",
                     dtype_bytes: int | None = None,
                     backend: str | None = None,
                     write: bool = True, path: str | None = None) -> dict:
    """Tune the weight-gradient kernel for one forward problem and (by
    default) persist the winner under its ``conv2d_wgrad`` key.  Ranked
    by the plan's analytical roofline step time; fewer grid steps win
    ties (the accumulating output block serializes the sweep, so grid
    overhead is pure latency)."""
    plans = candidate_weight_grad_knobs(x_shape, w_shape, stride=stride,
                                        pad=pad, groups=groups,
                                        dtype_bytes=_resolve_bytes(
                                            dtype_bytes, dtype))
    if not plans:
        raise ValueError(f"no feasible wgrad candidates for "
                         f"{x_shape}/{w_shape}")
    def score(p):
        terms = conv_plan_roofline("tune", p)
        return (terms.step_time_s, p.hbm_bytes()["total"],
                p.go_tiles * p.co_tiles, p.tile_cout)
    best = min(plans, key=score)
    record = dict(tile_go=best.tile_go, tile_cout=best.tile_cout,
                  source="model",
                  model_step_time_s=conv_plan_roofline(
                      "tune", best).step_time_s, measured_us=None)
    if write:
        store(make_key(x_shape, w_shape, stride=stride, pad=pad,
                       groups=groups, dtype=dtype, backend=backend,
                       op="conv2d_wgrad"), record, path)
    return record


# ---------------------------------------------------------------------------
# Sharded shapes (DESIGN.md §6)
# ---------------------------------------------------------------------------

def sharded_key_op(batch_shards: int, spatial_shards: int) -> str:
    """The op namespace of a sharded conv record:
    ``conv2d_shard:<ndev>:b<bs>x<ss>`` — the device count AND the
    (batch, spatial) split are part of the namespace because both change
    the per-shard strip geometry: a knob tuned on one shard grid must
    never be served to another split of the same size, to a different
    mesh size, or to the single-device path."""
    ndev = int(batch_shards) * int(spatial_shards)
    return (f"conv2d_shard:{ndev}:"
            f"b{int(batch_shards)}x{int(spatial_shards)}")


def sharded_knobs_for(x_shape, w_shape, *, batch_shards: int = 1,
                      spatial_shards: int = 1, stride: int = 1,
                      pad: int = 0, groups: int = 1,
                      dtype: str = "float32", backend: str | None = None,
                      path: str | None = None) -> dict | None:
    """Cached (validated) knobs for one sharded conv problem, or None —
    the lookup the ``ops.conv2d(..., mesh=)`` path performs.  Keys are
    the *global* kernel-seen shape under the shard-grid namespace of
    :func:`sharded_key_op`.  Honors ``REPRO_CONV_AUTOTUNE=0``."""
    if os.environ.get(AUTOTUNE_ENV, "1") == "0":
        return None
    key = make_key(x_shape, w_shape, stride=stride, pad=pad,
                   groups=groups, dtype=dtype, backend=backend,
                   op=sharded_key_op(batch_shards, spatial_shards))
    rec = lookup(key, path)
    if rec is None:
        return None
    if not _valid_record(rec, stride):
        _reject(key, f"bad shape/type/knobs: {rec!r}", path)
        return None
    try:
        from repro.core.conv_shard import ShardedConvPlan
        plan = ShardedConvPlan.build(
            x_shape, w_shape, stride=stride, pad=pad, groups=groups,
            tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
            dataflow=rec["dataflow"], batch_shards=batch_shards,
            spatial_shards=spatial_shards)
        if plan.local_plan().vmem_resident_bytes > KERNEL_VMEM_BUDGET:
            raise ValueError(
                "per-shard resident bytes exceed VMEM "
                "(the tuner only writes feasible plans)")
    except ValueError as e:
        _reject(key, f"knobs infeasible for current geometry: {e}", path)
        return None
    return rec


def tune_sharded(x_shape, w_shape, *, batch_shards: int = 1,
                 spatial_shards: int = 1, stride: int = 1, pad: int = 0,
                 groups: int = 1, dtype: str = "float32",
                 dtype_bytes: int | None = None,
                 backend: str | None = None,
                 write: bool = True, path: str | None = None) -> dict:
    """Tune one *sharded* conv problem and (by default) persist the
    winner under its ``conv2d_shard:<ndev>`` key.

    Candidates are the VMEM-feasible knobs of the *per-shard* problem
    (the assembled local window — device count changes the strip
    geometry, which is why sharded records are namespaced), scored by
    the sharded roofline: ``max(T_comp, T_mem, T_collective)`` with the
    cross-device halo bytes on the collective term.
    """
    from repro.core.conv_shard import ShardedConvPlan
    from repro.core.roofline import sharded_conv_roofline
    dtype_bytes = _resolve_bytes(dtype_bytes, dtype)
    base = ShardedConvPlan.build(x_shape, w_shape, stride=stride, pad=pad,
                                 groups=groups, dtype_bytes=dtype_bytes,
                                 batch_shards=batch_shards,
                                 spatial_shards=spatial_shards)
    local = candidate_knobs(base.local_x_shape, w_shape, stride=stride,
                            pad=0, groups=groups, dtype_bytes=dtype_bytes)
    if not local:
        raise ValueError(f"no feasible sharded candidates for "
                         f"{x_shape}/{w_shape}")
    plans = [ShardedConvPlan.build(
        x_shape, w_shape, stride=stride, pad=pad, groups=groups,
        dtype_bytes=dtype_bytes, tile_h=p.tile_h, tile_cout=p.tile_cout,
        dataflow=p.dataflow, batch_shards=batch_shards,
        spatial_shards=spatial_shards) for p in local]

    def score(p):
        terms = sharded_conv_roofline("tune", p)
        return (terms.step_time_s, p.sharded_traffic()["total"],
                0 if p.dataflow == "halo" else 1,
                p.local_plan().g_tiles, p.tile_cout)

    best = min(plans, key=score)
    record = dict(tile_h=best.tile_h, tile_cout=best.tile_cout,
                  dataflow=best.dataflow, source="model",
                  model_step_time_s=sharded_conv_roofline(
                      "tune", best).step_time_s, measured_us=None)
    if write:
        store(make_key(x_shape, w_shape, stride=stride, pad=pad,
                       groups=groups, dtype=dtype, backend=backend,
                       op=sharded_key_op(batch_shards, spatial_shards)),
              record, path)
    return record


# ---------------------------------------------------------------------------
# Whole-network sweep (DESIGN.md §7)
# ---------------------------------------------------------------------------

def tune_network(network="vgg16", *, n: int = 1, dtype: str = "float32",
                 dtype_bytes: int | None = None,
                 backend: str | None = None, op: str = "conv2d",
                 batch_shards: int = 1, spatial_shards: int = 1,
                 measure: bool = False, include_backward: bool = False,
                 write: bool = True, path: str | None = None) -> dict:
    """Tune every conv layer of a topology in one sweep.

    ``network`` is a name ("vgg16" | "alexnet" | "mobilenet") or an
    explicit ``list[ConvLayer]`` (e.g. a :func:`~repro.core.netplan.
    scale_layers` reduction).  Each layer is tuned over the *kernel-seen*
    shape (the 'same' pre-pad folded in, exactly the key ``ops.conv2d``
    looks up at call time), so after one sweep the whole forward pass of
    ``examples/cnn_inference.py --net ...`` runs on cached plans.  With
    a shard grid the records land under the ``conv2d_shard:`` namespace
    instead.  One device, float: each layer is tuned as the problem its
    ``ops.conv_route`` runs, under the key that route reads (DESIGN.md
    §16); a ``tiled`` layer (AlexNet's 11x11) reads none and is recorded
    as skipped.  The sharded and ``conv2d_q8`` namespaces tune every
    other layer unfolded, on the MXU kernel.  Layers sharing a key
    (VGG-16's repeated blocks) are tuned once.  ``include_backward``
    additionally seeds both cotangent records per layer
    (:func:`tune_backward`).  ``op`` selects the single-device key
    namespace (``"conv2d_q8"`` seeds the int8 inference path; pair it
    with ``dtype="int8"``).

    Returns ``{layer_name: record}`` with ``record["key"]`` the cache
    key written (or ``{"skipped": reason}``).
    """
    from repro.core.netplan import layer_kernel_problem, network_layers
    from repro.kernels.ops import ConvRoute, conv_route
    sharded = batch_shards > 1 or spatial_shards > 1
    if measure and sharded:
        raise ValueError(
            "measure=True is not supported with a shard grid: "
            "tune_sharded ranks by the sharded roofline model only")
    results: dict[str, dict] = {}
    seen: dict[str, dict] = {}
    for layer in network_layers(network):
        if layer.name in results:
            # results are keyed by layer name; a silent overwrite would
            # make the returned dict undercount the topology
            raise ValueError(
                f"duplicate layer name {layer.name!r} in topology; "
                "give repeated blocks unique names")
        # the shared layer -> executed-problem mapping (raises on
        # padding the execution path cannot reproduce)
        x_shape, pad, w_shape, _ = layer_kernel_problem(layer, n=n)
        route = conv_route(x_shape, w_shape, stride=layer.stride,
                           groups=layer.groups, dtype=dtype)
        if route.kind == "tiled":
            results[layer.name] = {
                "skipped": f"K={layer.kernel}: kernel-tiled path "
                           "(no cache)"}
            continue
        if sharded or op != "conv2d":
            # the sharded and int8 tiers run the layer unfolded on the
            # MXU kernel, under their own namespaces
            route = ConvRoute("mxu", x_shape, w_shape, layer.stride,
                              layer.groups)
            key = make_key(x_shape, w_shape, stride=layer.stride, pad=pad,
                           groups=layer.groups, dtype=dtype,
                           backend=backend,
                           op=sharded_key_op(batch_shards, spatial_shards)
                           if sharded else op)
        else:
            key = route.key(dtype, backend)
        if key in seen:
            results[layer.name] = seen[key]
            continue
        common = dict(stride=route.stride, pad=pad, groups=route.groups,
                      dtype=dtype, dtype_bytes=dtype_bytes,
                      backend=backend)
        if sharded:
            rec = tune_sharded(route.x_shape, route.w_shape,
                               batch_shards=batch_shards,
                               spatial_shards=spatial_shards, write=False,
                               **common)
        elif route.kind == "depthwise":
            rec = tune_depthwise(route.x_shape, route.w_shape[0],
                                 stride=route.stride, dtype=dtype,
                                 dtype_bytes=dtype_bytes)
        else:
            rec = tune(route.x_shape, route.w_shape, measure=measure,
                       write=False, **common)
        if write:
            store(key, rec, path)
        rec = dict(rec, key=key)
        if include_backward and not sharded:
            bx_shape = route.x_shape
            if route.kind == "folded":   # the 1x1 kernel sees whole strips
                rows = ConvPlan.build(
                    bx_shape, route.w_shape, dtype_bytes=dtype,
                    tile_h=rec["tile_h"], tile_cout=rec["tile_cout"],
                    dataflow=rec["dataflow"]).rows_padded
                bx_shape = (bx_shape[0], rows) + bx_shape[2:]
            rec["backward"] = tune_backward(bx_shape, route.w_shape,
                                            write=write, path=path,
                                            **common)
        seen[key] = rec
        results[layer.name] = rec
    return results


def tune_graph(graph, *, n: int = 1, dtype: str = "float32",
               dtype_bytes: int | None = None,
               backend: str | None = None, op: str = "conv2d",
               fused: bool = False, measure: bool = False,
               include_backward: bool = False, write: bool = True,
               path: str | None = None) -> dict:
    """Tune every conv node of a DAG topology in one sweep — the graph
    analogue of :func:`tune_network`.

    ``graph`` is anything ``core.netplan.graph_nodes`` resolves
    ("resnet18" | "unet" | ``list[GraphNode]`` | a linear topology).
    Conv nodes key the same ``conv2d:`` namespace over the same
    kernel-seen shapes (node names are unique by graph validation, and
    nodes sharing a problem — ResNet's repeated blocks — are tuned
    once), so ``cnn_apply_from_graph`` / ``cnn_pack_params_from_graph``
    run on cached plans afterwards.  Joins execute as jnp epilogues and
    have nothing to tune.  ``fused=True`` additionally sweeps each
    fusable linear segment (``core.fuse_plan.graph_segments``) through
    :func:`tune_fused_network`, seeding the ``conv2d_fused:`` records
    the segment megakernels consult.

    Returns ``{"layers": {node: record}[, "fused": {segment: record}]}``.
    """
    from repro.core.netplan import graph_nodes
    nodes = graph_nodes(graph)
    layers = [nd.layer for nd in nodes if nd.op == "conv"]
    out = {"layers": tune_network(
        layers, n=n, dtype=dtype, dtype_bytes=dtype_bytes,
        backend=backend, op=op, measure=measure,
        include_backward=include_backward, write=write, path=path)}
    if fused:
        from repro.core.fuse_plan import graph_segments
        fused_recs: dict[str, dict] = {}
        for names, seg_layers in graph_segments(nodes):
            if len(seg_layers) < 2:
                continue
            fused_recs.update(tune_fused_network(
                list(seg_layers), n=n, dtype=dtype,
                dtype_bytes=dtype_bytes, backend=backend, write=write,
                path=path))
        out["fused"] = fused_recs
    return out


def prewarm_buckets(network, buckets, *, dtype: str = "float32",
                    dtype_bytes: int | None = None,
                    backend: str | None = None, op: str = "conv2d",
                    batch_shards: int = 1, spatial_shards: int = 1,
                    fused: bool = False, include_backward: bool = False,
                    measure: bool = False, write: bool = True,
                    path: str | None = None) -> dict:
    """Warm the plan cache across a serving bucket grid (DESIGN.md §10).

    Runs :func:`tune_network` once per batch bucket — every conv layer
    of ``network`` tuned at every bucket's kernel-seen shape, so no
    serving request (whose batch is always rounded up to a bucket) ever
    hits a cold tune.  ``fused=True`` additionally sweeps
    :func:`tune_fused_network` per bucket, seeding the
    ``conv2d_fused:`` group records the megakernel path consults.
    Buckets are deduplicated and swept ascending, so concurrent
    prewarmers (multiple serving replicas starting at once) write the
    same records in the same order and merge cleanly through the
    flock+merge store.

    Returns ``{bucket: {"layers": tune_network results[, "fused":
    tune_fused_network results]}}``.
    """
    results: dict[int, dict] = {}
    for n in sorted({int(b) for b in buckets}):
        if n < 1:
            raise ValueError(f"batch bucket must be >= 1, got {n}")
        per = {"layers": tune_network(
            network, n=n, dtype=dtype, dtype_bytes=dtype_bytes,
            backend=backend, op=op, batch_shards=batch_shards,
            spatial_shards=spatial_shards, measure=measure,
            include_backward=include_backward, write=write, path=path)}
        if fused:
            per["fused"] = tune_fused_network(
                network, n=n, dtype=dtype, dtype_bytes=dtype_bytes,
                backend=backend, write=write, path=path)
        results[n] = per
    return results


def tune_backward(x_shape, w_shape, *, stride: int = 1, pad: int = 0,
                  groups: int = 1, dtype: str = "float32",
                  dtype_bytes: int | None = None,
                  backend: str | None = None,
                  measure: bool = False, write: bool = True,
                  path: str | None = None) -> dict:
    """Tune both cotangents of one forward problem.

    The input-gradient conv IS a forward problem over its transformed
    (stride-dilated, edge-padded) shapes, so it reuses :func:`tune` —
    and its record lands under the plain ``conv2d`` key of that
    transformed problem, exactly where the backward pass looks it up.
    The weight-gradient kernel gets its own ``conv2d_wgrad`` record.
    Returns ``{"input_grad": rec, "weight_grad": rec}``.
    """
    geo = input_grad_geometry(x_shape, w_shape, stride=stride, pad=pad,
                              groups=groups)
    igrad = tune(geo["g_padded_shape"], geo["wt_shape"], stride=1, pad=0,
                 groups=groups, dtype=dtype, dtype_bytes=dtype_bytes,
                 backend=backend, measure=measure, write=write, path=path)
    wgrad = tune_weight_grad(x_shape, w_shape, stride=stride, pad=pad,
                             groups=groups, dtype=dtype,
                             dtype_bytes=dtype_bytes, backend=backend,
                             write=write, path=path)
    return {"input_grad": igrad, "weight_grad": wgrad}


# ---------------------------------------------------------------------------
# Fused residency groups (DESIGN.md §8)
# ---------------------------------------------------------------------------

def fused_key(signature: str, *, n: int = 1, dtype: str = "float32",
              backend: str | None = None) -> str:
    """Cache key for one fused residency group.

    ``signature`` is the group's per-stage signature chain
    (:attr:`~repro.core.fuse_plan.FusedGroup.signature` — per-stage
    problem geometry joined with ``-``), so the namespace is
    ``conv2d_fused:d<depth>:n<n>:<chain>:<dtype>:<backend>``.  The
    ``conv2d_fused`` prefix guarantees a fused record can never alias a
    per-layer ``conv2d:``, ``conv2d_wgrad:`` or ``conv2d_shard:`` key,
    and depth + chain make distinct groups distinct even when they share
    a leading stage.
    """
    if backend is None:
        import jax
        backend = jax.default_backend()
    depth = signature.count("-") + 1 if signature else 0
    return f"conv2d_fused:d{depth}:n{n}:{signature}:{dtype}:{backend}"


def _valid_fused_record(rec) -> bool:
    return (isinstance(rec, dict)
            and isinstance(rec.get("strip_rows"), int)
            and rec["strip_rows"] >= 1)


def fused_knobs_for(signature: str, *, n: int = 1, dtype: str = "float32",
                    backend: str | None = None,
                    path: str | None = None) -> dict | None:
    """The cached (validated) group knob for a fused-group signature, or
    None — the lookup ``FusedGroupPlan.build(use_autotune_cache=True)``
    performs.  Honors ``REPRO_CONV_AUTOTUNE=0``."""
    if os.environ.get(AUTOTUNE_ENV, "1") == "0":
        return None
    key = fused_key(signature, n=n, dtype=dtype, backend=backend)
    rec = lookup(key, path)
    if rec is None:
        return None
    if not _valid_fused_record(rec):
        _reject(key, f"bad shape/type/knobs: {rec!r}", path)
        return None
    return rec


def tune_fused(layers, *, start: int = 0, pools=None, n: int = 1,
               dtype: str = "float32", dtype_bytes: int | None = None,
               backend: str | None = None, vmem_budget: int | None = None,
               write: bool = True, path: str | None = None) -> dict:
    """Tune the strip height of one fused group (a layer chain) and (by
    default) persist the winner under its ``conv2d_fused:`` key.

    Candidates are the VMEM-feasible power-of-two strip heights of the
    group; each is scored by the *grouped roofline* — the fused
    schedule's executed bytes (overlapping stage-0 windows + per-strip
    weight streams + pooled output) against the group's FLOPs — and the
    minimal modeled step time wins, with total bytes then fewer strips
    as tie-breakers.
    """
    from repro.core.fuse_plan import (FUSED_VMEM_BUDGET, build_group,
                                      _strip_candidates)
    from repro.core.roofline import conv_plan_roofline
    dtype_bytes = _resolve_bytes(dtype_bytes, dtype)
    if vmem_budget is None:
        vmem_budget = FUSED_VMEM_BUDGET
    probe = build_group(layers, start, n=n, strip_rows=1,
                        dtype_bytes=dtype_bytes, pools=pools)
    feasible = []
    for t in _strip_candidates(probe.last.h_pool):
        g = build_group(layers, start, n=n, strip_rows=t,
                        dtype_bytes=dtype_bytes, pools=pools)
        if g.vmem_resident_bytes <= vmem_budget:
            feasible.append(g)
    if not feasible:
        raise ValueError(
            f"no VMEM-feasible strip height for fused group "
            f"{probe.signature} (budget {vmem_budget})")

    def score(g):
        terms = conv_plan_roofline("tune", g)
        return (terms.step_time_s, g.hbm_bytes()["total"], g.n_strips)

    best = min(feasible, key=score)
    record = dict(strip_rows=best.strip_rows, depth=best.depth,
                  source="model",
                  model_step_time_s=conv_plan_roofline(
                      "tune", best).step_time_s,
                  hbm_total=best.hbm_bytes()["total"], measured_us=None)
    if write:
        store(fused_key(best.signature, n=n, dtype=dtype, backend=backend),
              record, path)
    return record


def tune_fused_network(network="vgg16", *, n: int = 1,
                       dtype: str = "float32",
                       dtype_bytes: int | None = None,
                       backend: str | None = None,
                       residency: str = "auto",
                       write: bool = True, path: str | None = None) -> dict:
    """Tune every fused residency group of a topology in one sweep.

    Partitions the network with :class:`~repro.core.fuse_plan.
    FusedGroupPlan` (model-driven, no cache) and writes one
    ``conv2d_fused:`` record per depth>=2 group, so a subsequent
    ``FusedGroupPlan.build(use_autotune_cache=True)`` — and therefore
    ``cnn_apply_from_layers(..., fused=True)`` — runs on cached group
    knobs.  Returns ``{"<first>..<last>": record}`` per fused group.
    """
    from repro.core.fuse_plan import FusedGroupPlan
    from repro.core.netplan import infer_pools, network_layers
    layers = list(network_layers(network))
    pools = list(infer_pools(layers))
    dtype_bytes = _resolve_bytes(dtype_bytes, dtype)
    plan = FusedGroupPlan.build(layers, n=n, dtype_bytes=dtype_bytes,
                                residency=residency)
    results: dict[str, dict] = {}
    for g in plan.groups:
        if not g.fused:
            continue
        sub = layers[g.start:g.start + g.depth]
        rec = tune_fused(sub, start=g.start,
                         pools=pools[g.start:g.start + g.depth], n=n,
                         dtype=dtype, dtype_bytes=dtype_bytes,
                         backend=backend, write=write, path=path)
        rec = dict(rec, key=fused_key(g.signature, n=n, dtype=dtype,
                                      backend=backend))
        results[f"{sub[0].name}..{sub[-1].name}"] = rec
    return results
