"""Graceful degradation + runtime verification for kernel dispatch
(DESIGN.md §17).

PR 8 made ``backend="pallas"`` mean *compiled-when-available*, but ROADMAP
item 1 is honest about what host CI cannot prove: no CPU runner can show
that Mosaic accepts every kernel body on a real TPU, that the VMEM cost
constants hold, or that a compiled kernel never miscompiles.  Until then —
and on real hardware after then — any lowering failure, resource exhaustion
or silent wrong answer would surface as an unhandled exception (or worse,
wrong data) in the middle of a serving step.  This module is the safety
net between the plan layer and its callers:

* **Failure taxonomy.** :func:`classify` wraps raw XLA/Mosaic/runtime
  exceptions into :class:`KernelLoweringError` (persistent — the body will
  never lower), :class:`KernelResourceError` (persistent but
  tile-shrinkable — VMEM/HBM exhaustion scales with the tile working set),
  or a *transient* :class:`KernelDispatchError` (preemption, link flap —
  worth retrying in place).  Programming errors (``ValueError`` from shape
  validation etc.) classify as ``None`` and always propagate untouched:
  the ladder degrades EXECUTION failures, never masks caller bugs.
* **Degradation ladder.** :func:`dispatch` runs one operation with bounded
  fallback: transient errors retry in place; a resource error first
  halves the tile (down to ``_MIN_TILE``, pinning the survivor in the tile
  cache so the shape class never re-learns the lesson); persistent errors
  demote the backend along :data:`DEMOTION_ORDER`
  (``pallas → pallas-interpret → vmap → reference``; ``pallas-interpret``
  is skipped when a TPU is attached).  The reference
  oracle is the floor — a failure there re-raises.  ``REPRO_STRICT=1`` /
  :func:`set_strict` disables all fallback (CI/debug: fail loud).
* **Circuit breaker.** Per ``(spec, shape, backend)`` plan class, repeated
  persistent failures (:data:`BREAKER_THRESHOLD`) quarantine the class in
  a persistent autotune-style JSON sidecar (same directory, same atomic
  write/lazy-load/fingerprint discipline as
  :mod:`repro.core.pipeline.autotune`), so later *processes* skip the
  doomed attempt and start one rung down.
  ``clear_tile_cache()`` drops only the in-memory snapshot — the
  quarantine survives the reload, like a fresh process against a warm
  cache file; ``clear_tile_cache(disk=True)`` deletes it.
* **Runtime verification.** :func:`set_verify` / ``REPRO_VERIFY`` arm
  opt-in output checking: level 1 is O(m) — counts conservation
  (Σcounts == n) and offset monotonicity (starts == exclusive cumsum);
  level 2 is O(n log n) — the output is a true permutation of the input
  with non-decreasing bucket ids and a valid permutation vector.  On
  mismatch the op re-runs on the reference backend (the returned result is
  always trustworthy), emits a minimal structured repro report
  (spec, shape, backend, seed), counts a ``verify_mismatch``, and strikes
  the breaker so the lying backend demotes like any other failure.
* **Fault injection.** :func:`set_fault_injector` arms a
  :class:`~repro.runtime.supervisor.FaultInjector` at the dispatch site
  (seeded, per-backend), so the whole ladder is exercisable without a TPU
  — the chaos suite (``tests/test_resilience.py``) and the CI chaos-smoke
  step drive it at rate 0.05.

Everything here is host-side and eager: exceptions cannot cross a jit
trace, so the facade (:mod:`repro.ops`) bypasses the ladder under tracing
and the serving loop (:mod:`repro.serving.engine`) applies it at its own
eager flush boundary.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
import re
import tempfile
import threading
from collections import deque
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

log = logging.getLogger("repro.resilience")

SCHEMA_VERSION = 1

# The fallback chain, best first.  Backends outside the chain (future
# registrations) demote straight to the oracle.
DEMOTION_ORDER = ("pallas", "pallas-interpret", "vmap", "reference")

# Persistent failures per plan class before the breaker trips and the
# class is quarantined on disk.
BREAKER_THRESHOLD = 3

# In-place retries per rung for transient failures before demoting anyway.
MAX_TRANSIENT_RETRIES = 2

_ENV_STRICT = "REPRO_STRICT"
_ENV_VERIFY = "REPRO_VERIFY"

_TRUE = ("1", "true", "yes", "on")


# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------

class KernelDispatchError(RuntimeError):
    """A classified kernel-dispatch failure wrapping the raw exception.

    ``transient`` marks failures worth retrying in place (preemption,
    link flap); persistent failures go straight to tile-shrink/demotion.
    ``original`` is the exception as raised; ``backend``/``plan_class``
    locate the failure for the breaker and the repro report.
    """

    transient = False

    def __init__(self, message: str, *, original: Optional[BaseException] = None,
                 backend: Optional[str] = None,
                 plan_class: Optional[Tuple] = None):
        super().__init__(message)
        self.original = original
        self.backend = backend
        self.plan_class = plan_class
        self.__cause__ = original


class KernelLoweringError(KernelDispatchError):
    """The kernel body does not lower (Mosaic rejection, unimplemented
    primitive): persistent — retrying the same program cannot succeed."""


class KernelResourceError(KernelDispatchError):
    """Resource exhaustion (VMEM/HBM OOM): persistent for THIS tile, but
    the working set scales with the tile — halve-and-retry first."""


class KernelResultError(KernelDispatchError):
    """The kernel ran but produced a wrong answer (runtime verification
    mismatch): the most dangerous class — recover via the oracle."""


class TransientDispatchError(KernelDispatchError):
    """Environmental failure (preemption, DEADLINE_EXCEEDED, link flap):
    worth a bounded in-place retry before degrading."""

    transient = True


# Marker → class tables.  XLA/Mosaic error surfaces are strings, not types;
# the injected-fault messages deliberately carry the same markers so the
# chaos suite exercises the real classifier, not a test-only side door.
_RESOURCE_MARKERS = (
    "resource_exhausted", "out of memory", "oom", "vmem", "smem",
    "scratch limit", "allocat",
)
_LOWERING_MARKERS = (
    "mosaic", "lowering", "unsupported", "not implemented", "unimplemented",
    "internal: failed to compile", "does not lower",
)
_TRANSIENT_MARKERS = (
    "deadline_exceeded", "unavailable", "aborted", "cancelled", "preempt",
    "connection reset", "transient",
)


def _marked(msg: str, markers: Tuple[str, ...]) -> bool:
    # left word boundary only: "oom" must not match "boom", but "allocat"
    # must still match "allocating"/"allocation"
    return any(re.search(r"(?<![a-z0-9])" + re.escape(m), msg)
               for m in markers)


def classify(exc: BaseException, *, backend: Optional[str] = None,
             plan_class: Optional[Tuple] = None) -> Optional[KernelDispatchError]:
    """Wrap a raw dispatch exception into the taxonomy, or return ``None``
    for exceptions the ladder must NOT handle (programming/validation
    errors — ``ValueError``/``TypeError`` raised by our own argument
    checks propagate untouched, on every rung)."""
    if isinstance(exc, KernelDispatchError):
        return exc
    msg = f"{type(exc).__name__}: {exc}".lower()
    kw: Dict[str, Any] = dict(original=exc, backend=backend, plan_class=plan_class)
    if isinstance(exc, (ValueError, TypeError)) and not _marked(
            msg, _RESOURCE_MARKERS + _LOWERING_MARKERS):
        return None
    if isinstance(exc, MemoryError) or _marked(msg, _RESOURCE_MARKERS):
        return KernelResourceError(f"[{backend}] {exc}", **kw)
    if isinstance(exc, NotImplementedError) or _marked(msg, _LOWERING_MARKERS):
        return KernelLoweringError(f"[{backend}] {exc}", **kw)
    if _marked(msg, _TRANSIENT_MARKERS):
        return TransientDispatchError(f"[{backend}] {exc}", **kw)
    # Unknown runtime failure: treat as a persistent dispatch error — the
    # ladder degrades it, the breaker learns it, strict mode re-raises it.
    if isinstance(exc, (RuntimeError, OSError)):
        return KernelDispatchError(f"[{backend}] {exc}", **kw)
    return None


# ---------------------------------------------------------------------------
# Configuration: strict + verify (env-resolved, override via setters)
# ---------------------------------------------------------------------------

_STRICT_OVERRIDE: Optional[bool] = None
_VERIFY_OVERRIDE: Optional[int] = None


def set_strict(enabled: Optional[bool]) -> None:
    """Disable (``True``) all fallback: no ladder, no quarantine skip, no
    verify recovery — the original exception propagates.  ``None`` defers
    back to the ``REPRO_STRICT`` environment variable."""
    global _STRICT_OVERRIDE
    _STRICT_OVERRIDE = None if enabled is None else bool(enabled)


def strict() -> bool:
    if _STRICT_OVERRIDE is not None:
        return _STRICT_OVERRIDE
    return os.environ.get(_ENV_STRICT, "").strip().lower() in _TRUE


def set_verify(level: Optional[int]) -> None:
    """Arm runtime output verification: 0 off, 1 = O(m) counts conservation
    + offset monotonicity, 2 = full permutation + bucket-order check
    (DESIGN.md §17).  ``None`` defers back to ``REPRO_VERIFY``."""
    global _VERIFY_OVERRIDE
    if level is None:
        _VERIFY_OVERRIDE = None
        return
    level = int(level)
    if not 0 <= level <= 2:
        raise ValueError(f"verify level must be 0, 1 or 2, got {level}")
    _VERIFY_OVERRIDE = level


def verify_level() -> int:
    if _VERIFY_OVERRIDE is not None:
        return _VERIFY_OVERRIDE
    raw = os.environ.get(_ENV_VERIFY, "").strip()
    if not raw:
        return 0
    try:
        return max(0, min(2, int(raw)))
    except ValueError:
        return 1 if raw.lower() in _TRUE else 0


# ---------------------------------------------------------------------------
# Counters, events, repro reports
# ---------------------------------------------------------------------------

_COUNTER_KEYS = (
    "degradations", "tile_shrinks", "backend_demotions", "transient_retries",
    "quarantine_skips", "breaker_trips", "verify_checks", "verify_mismatches",
    "reference_reruns",
)
_STATS: Dict[str, int] = {k: 0 for k in _COUNTER_KEYS}
_EVENTS: deque = deque(maxlen=256)
_REPORTS: deque = deque(maxlen=32)
_LOCK = threading.Lock()


def stats() -> Dict[str, int]:
    """Snapshot of the degradation/verification counters since process
    start (or :func:`reset_stats`)."""
    with _LOCK:
        return dict(_STATS)


def reset_stats() -> None:
    with _LOCK:
        for k in _COUNTER_KEYS:
            _STATS[k] = 0
        _EVENTS.clear()
        _REPORTS.clear()


def _count(key: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[key] += n


def _event(kind: str, **fields) -> None:
    with _LOCK:
        _EVENTS.append({"kind": kind, **fields})


def events() -> Tuple[Dict[str, Any], ...]:
    """The last ≤256 degradation events (the CI chaos-smoke step renders
    these as the markdown step summary)."""
    with _LOCK:
        return tuple(dict(e) for e in _EVENTS)


def reports() -> Tuple[Dict[str, Any], ...]:
    """The last ≤32 structured verify-mismatch repro reports."""
    with _LOCK:
        return tuple(dict(r) for r in _REPORTS)


def last_report() -> Optional[Dict[str, Any]]:
    with _LOCK:
        return dict(_REPORTS[-1]) if _REPORTS else None


def _emit_report(ctx: "DispatchContext", backend: str, detail: str) -> Dict[str, Any]:
    """The minimal structured repro report of one verify mismatch: enough
    to rebuild the failing plan (spec, shape, backend, seed), nothing
    process-local."""
    report = {
        "spec": ctx.spec_name,
        "shape": ctx.shape,
        "num_buckets": ctx.num_buckets,
        "method": ctx.method,
        "key_value": ctx.key_value,
        "mode": ctx.mode,
        "layout": ctx.layout,
        "backend": backend,
        "seed": ctx.seed,
        "detail": detail,
    }
    with _LOCK:
        _REPORTS.append(report)
    log.error("verify mismatch: %s", json.dumps(report, sort_keys=True, default=str))
    return report


# ---------------------------------------------------------------------------
# Circuit breaker + persistent quarantine (the autotune-cache discipline)
# ---------------------------------------------------------------------------

_BREAKER: Dict[str, int] = {}        # class key -> persistent-failure strikes
_QUAR_MEM: Dict[str, str] = {}       # class key -> reason (process-local view)
_QUAR_LOADED: Optional[Dict[str, str]] = None   # lazy disk snapshot


def quarantine_path():
    """The quarantine sidecar lives next to the autotune cache (same
    ``REPRO_AUTOTUNE_DIR`` / ``set_autotune(cache_dir=...)`` override), but
    in its OWN file: tuning facts and failure facts have different
    lifetimes and clearing one must not clear the other."""
    from repro.core.pipeline import autotune as _at

    return _at.cache_path().parent / "multisplit_resilience.json"


def _q_entries() -> Dict[str, str]:
    """Lazily-loaded disk snapshot; missing/corrupt/stale-version files
    load as empty (clean fallback, mirroring the autotune layer)."""
    global _QUAR_LOADED
    if _QUAR_LOADED is None:
        _QUAR_LOADED = {}
        try:
            with open(quarantine_path()) as f:
                raw = json.load(f)
            if (isinstance(raw, dict)
                    and raw.get("version") == SCHEMA_VERSION
                    and isinstance(raw.get("entries"), dict)):
                _QUAR_LOADED = {str(k): str(v) for k, v in raw["entries"].items()}
        except (OSError, ValueError):
            pass
    return _QUAR_LOADED


def _q_flush(entries: Dict[str, str]) -> None:
    """Atomic tempfile + ``os.replace`` write; best-effort (an unwritable
    dir degrades to in-memory quarantine, never an error)."""
    path = quarantine_path()
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(path.parent), prefix=".resilience-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump({"version": SCHEMA_VERSION, "entries": entries},
                          f, sort_keys=True)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except OSError:
        pass


def class_key(plan_class: Tuple, backend: str) -> str:
    """fingerprint | quarantine | plan-class parts | backend — the same
    key discipline as the autotune disk layer, so a quarantine entry is a
    per-host fact like a tuned tile."""
    from repro.core.pipeline import autotune as _at

    parts = "|".join(str(x) for x in plan_class)
    return f"{_at.host_fingerprint()}|quarantine|{parts}|{backend}"


def quarantine(key: str, reason: str) -> None:
    """Quarantine one (plan class, backend): in memory AND on disk, so a
    later process skips the doomed attempt."""
    _QUAR_MEM[key] = reason
    ent = dict(_q_entries())
    ent[key] = reason
    _q_flush(ent)
    global _QUAR_LOADED
    _QUAR_LOADED = ent


def is_quarantined(key: str) -> Optional[str]:
    """The quarantine reason for a class key, or None.  Consults the
    process-local view first, then the (lazily loaded) disk snapshot —
    the survival path across ``clear_tile_cache()`` / process restarts."""
    hit = _QUAR_MEM.get(key)
    if hit is not None:
        return hit
    return _q_entries().get(key)


def record_failure(key: str, err: KernelDispatchError) -> bool:
    """One persistent failure strike against a plan class; trips the
    breaker (and quarantines) at :data:`BREAKER_THRESHOLD`.  Returns True
    when this strike tripped it."""
    strikes = _BREAKER.get(key, 0) + 1
    _BREAKER[key] = strikes
    if strikes >= BREAKER_THRESHOLD and key not in _QUAR_MEM:
        reason = f"{type(err).__name__} x{strikes}: {err}"
        quarantine(key, reason)
        _count("breaker_trips")
        _event("breaker_trip", key=key, reason=reason)
        log.warning("circuit breaker tripped: %s", reason)
        return True
    return False


def breaker_strikes() -> Dict[str, int]:
    return dict(_BREAKER)


def quarantine_snapshot() -> Dict[str, str]:
    """Every quarantined class visible right now (memory ∪ disk)."""
    merged = dict(_q_entries())
    merged.update(_QUAR_MEM)
    return merged


def drop_loaded() -> None:
    """Forget the in-process quarantine view; the next check re-reads the
    file (what a fresh process would see).  Called by
    ``clear_tile_cache()`` so the quarantine *survives* the reload."""
    global _QUAR_LOADED
    _QUAR_LOADED = None
    _QUAR_MEM.clear()
    _BREAKER.clear()


def clear_quarantine(disk: bool = False) -> None:
    """Drop the quarantine: memory always; ``disk=True`` deletes the
    sidecar file too (``clear_tile_cache(disk=True)``)."""
    global _QUAR_LOADED
    _QUAR_MEM.clear()
    _BREAKER.clear()
    if disk:
        _QUAR_LOADED = {}
        try:
            os.remove(quarantine_path())
        except OSError:
            pass
    else:
        _QUAR_LOADED = None


# ---------------------------------------------------------------------------
# Dispatch-level fault injection (exercising the ladder without a TPU)
# ---------------------------------------------------------------------------

_FAULT_INJECTOR: Optional[Any] = None


def set_fault_injector(injector: Optional[Any]) -> None:
    """Arm a :class:`~repro.runtime.supervisor.FaultInjector` (anything
    with ``check_dispatch(backend)``) at the kernel-dispatch site; ``None``
    disarms.  Injected exceptions carry classifiable messages, so the real
    classifier — not a test-only door — routes them down the ladder."""
    global _FAULT_INJECTOR
    _FAULT_INJECTOR = injector


def fault_injector() -> Optional[Any]:
    return _FAULT_INJECTOR


def check_faults(backend: str) -> None:
    """The injection site: called once per dispatch attempt (facade AND
    serving launch) with the attempt's backend."""
    if _FAULT_INJECTOR is not None:
        _FAULT_INJECTOR.check_dispatch(backend)


# ---------------------------------------------------------------------------
# The degradation ladder
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DispatchContext:
    """The plan-class identity of one dispatch: what the breaker keys on
    and the repro report serializes.  ``spec_name`` is the bucket spec's
    stable name (never an object id), ``shape`` the input key shape."""

    spec_name: str
    shape: Tuple[int, ...]
    num_buckets: int
    method: str = "bms"
    key_value: bool = False
    mode: str = "reorder"
    layout: str = "flat"            # flat | batched | segmented
    seed: Optional[int] = None

    def plan_class(self) -> Tuple:
        return (self.spec_name, self.shape, self.num_buckets, self.method,
                self.key_value, self.mode, self.layout)


def demote(backend: str) -> Optional[str]:
    """The next rung down, or None at (or below) the reference floor."""
    if backend == "reference":
        return None
    try:
        i = DEMOTION_ORDER.index(backend)
    except ValueError:
        return "reference"          # unknown/future backend: fall to the oracle
    nxt = DEMOTION_ORDER[i + 1]
    if nxt == "pallas-interpret":
        from repro.kernels import ops as kops

        if kops._tpu_available():
            # interpreting the kernels on a TPU host is never a useful rung
            nxt = DEMOTION_ORDER[i + 2]
    return nxt


def _block(result: Any) -> Any:
    """Force async dispatch errors to surface inside the try (jax errors
    are lazy; an unconsumed result can fail after dispatch returns)."""
    import jax

    jax.block_until_ready(jax.tree.leaves(result))
    return result


def dispatch(
    run: Callable[[str, Optional[int]], Any],
    ctx: DispatchContext,
    *,
    backend: str,
    tile: Optional[int] = None,
    resolved_tile: Optional[Callable[[str], int]] = None,
    pin_tile: Optional[Callable[[str, int], None]] = None,
    verifier: Optional[Callable[[Any, str], None]] = None,
) -> Any:
    """Execute ``run(backend, tile)`` under the degradation ladder.

    ``run`` must be re-invocable with any (backend, tile) pair;
    ``resolved_tile(backend)`` reports the tile the plan would auto-resolve
    (the halve-and-retry starting point); ``pin_tile(backend, tile)`` pins
    a shrink survivor in the tile cache; ``verifier(result, backend)``
    raises :class:`KernelResultError` on an output-invariant violation
    (skipped on the reference rung — the oracle defines correctness).

    Strict mode runs the requested config once, verifying if armed, and
    re-raises everything.  Otherwise: quarantined rungs are skipped
    (statically — no attempt), transient failures retry in place
    (:data:`MAX_TRANSIENT_RETRIES`), resource failures halve the tile to
    ``_MIN_TILE`` then demote, other persistent failures demote, verify
    mismatches recover via one reference re-run.  Only a failure on the
    reference rung itself propagates.
    """
    level = verify_level()
    if strict():
        check_faults(backend)
        result = run(backend, tile)
        if verifier is not None and level > 0 and backend != "reference":
            _count("verify_checks")
            verifier(_block(result), backend)
        return result

    from repro.core.pipeline.tiles import _MIN_TILE

    b, t = backend, tile
    transient_left = MAX_TRANSIENT_RETRIES
    shrunk = False
    degraded = False
    # sync inside the try whenever a failure is plausible or must be caught
    # here: verification armed, faults armed, or already degraded once.
    while True:
        key = class_key(ctx.plan_class(), b)
        if b != "reference" and is_quarantined(key):
            _count("quarantine_skips")
            _event("quarantine_skip", key=key, backend=b)
            nb = demote(b)
            _count("backend_demotions")
            _count("degradations")
            b, t, shrunk = nb, None, False
            transient_left = MAX_TRANSIENT_RETRIES
            degraded = True
            continue
        try:
            check_faults(b)
            result = run(b, t)
            sync = degraded or (level > 0) or (_FAULT_INJECTOR is not None)
            if sync:
                _block(result)
            if verifier is not None and level > 0 and b != "reference":
                _count("verify_checks")
                verifier(result, b)
            if shrunk and t is not None and pin_tile is not None:
                pin_tile(b, t)
            return result
        except Exception as exc:  # noqa: BLE001 — the resilience boundary
            err = classify(exc, backend=b, plan_class=ctx.plan_class())
            if err is None or b == "reference":
                raise
            if isinstance(err, KernelResultError):
                _count("verify_mismatches")
                _emit_report(ctx, b, str(err))
                record_failure(key, err)
                _count("reference_reruns")
                _count("degradations")
                _event("verify_fallback", backend=b, spec=ctx.spec_name,
                       shape=ctx.shape, detail=str(err))
                log.warning("verify mismatch on %r; recovering via reference", b)
                return _block(run("reference", None))
            if err.transient and transient_left > 0:
                transient_left -= 1
                _count("transient_retries")
                log.info("transient dispatch failure on %r, retrying: %s", b, err)
                degraded = True
                continue
            record_failure(key, err)
            if isinstance(err, KernelResourceError):
                base = t if t is not None else (
                    resolved_tile(b) if resolved_tile is not None else None)
                if base is not None and base // 2 >= _MIN_TILE:
                    t = base // 2
                    shrunk = True
                    degraded = True
                    _count("tile_shrinks")
                    _count("degradations")
                    _event("tile_shrink", backend=b, tile=t,
                           spec=ctx.spec_name, shape=ctx.shape)
                    log.warning("resource failure on %r; retrying tile=%d", b, t)
                    continue
            nb = demote(b)
            if nb is None:
                raise
            _count("backend_demotions")
            _count("degradations")
            _event("backend_demotion", frm=b, to=nb, spec=ctx.spec_name,
                   shape=ctx.shape, error=type(err).__name__)
            log.warning("demoting backend %r -> %r after %s: %s",
                        b, nb, type(err).__name__, err)
            b, t, shrunk = nb, None, False
            transient_left = MAX_TRANSIENT_RETRIES
            degraded = True


# ---------------------------------------------------------------------------
# Runtime verification (the level-1/level-2 invariants)
# ---------------------------------------------------------------------------

def _fail(detail: str, backend: Optional[str], ctx: Optional[DispatchContext]):
    raise KernelResultError(
        f"[{backend}] output verification failed: {detail}",
        backend=backend,
        plan_class=None if ctx is None else ctx.plan_class(),
    )


def verify_result(
    result: Any,
    *,
    keys: Any,
    spec: Any,
    n: int,
    values: Any = None,
    segment_starts: Any = None,
    mode: str = "reorder",
    level: Optional[int] = None,
    backend: Optional[str] = None,
    ctx: Optional[DispatchContext] = None,
) -> None:
    """Check a :class:`~repro.core.pipeline.stages.MultisplitResult`
    against the paper's invariants (host-side, on concrete arrays).

    Level 1 (O(m)): every counts row sums to its row's element count and
    ``bucket_starts`` is the exclusive cumsum of counts (hence monotone
    non-decreasing).  Level 2 (O(n log n)) additionally proves the output
    keys are a true permutation of the input with non-decreasing bucket
    ids (per row / per segment) and that ``permutation`` is a valid
    (segment-local) permutation vector.  Raises :class:`KernelResultError`
    on the first violated invariant.
    """
    level = verify_level() if level is None else level
    if level <= 0:
        return
    counts = np.asarray(result.bucket_counts)
    starts = np.asarray(result.bucket_starts)
    seg = None if segment_starts is None else np.asarray(segment_starts)

    # ---- level 1: conservation + monotonicity (O(m)) ----
    if (counts < 0).any():
        _fail(f"negative bucket counts: min={counts.min()}", backend, ctx)
    if counts.ndim == 1:                      # flat
        if int(counts.sum()) != n:
            _fail(f"counts conservation: sum={int(counts.sum())} != n={n}",
                  backend, ctx)
    elif seg is not None:                     # segmented: rows are segments
        seg_len = np.diff(np.append(seg, n))
        row_sums = counts.sum(axis=1)
        if not np.array_equal(row_sums, seg_len):
            _fail(f"segment counts conservation: row sums {row_sums.tolist()} "
                  f"!= segment lengths {seg_len.tolist()}", backend, ctx)
    else:                                     # batched: every row is one n
        if not (counts.sum(axis=1) == n).all():
            _fail(f"batched counts conservation: row sums "
                  f"{counts.sum(axis=1).tolist()} != n={n}", backend, ctx)
    expect_starts = np.cumsum(counts, axis=-1) - counts
    if not np.array_equal(starts, expect_starts):
        _fail("bucket_starts is not the exclusive cumsum of counts "
              "(offset monotonicity violated)", backend, ctx)
    if level == 1 or mode == "counts_only":
        return

    # ---- level 2: true permutation + non-decreasing bucket ids ----
    keys_in = np.asarray(keys)
    if result.permutation is not None:
        perm = np.asarray(result.permutation)
        if seg is None:
            flatp = perm.reshape(-1, perm.shape[-1])
            for row in flatp:
                if not np.array_equal(np.sort(row), np.arange(row.shape[0])):
                    _fail("permutation is not a permutation of arange(n)",
                          backend, ctx)
        else:
            bounds = np.append(seg, n)
            for s0, s1 in zip(bounds[:-1], bounds[1:]):
                p = perm[s0:s1]
                if not np.array_equal(np.sort(p), np.arange(s1 - s0)):
                    _fail(f"segment [{s0}:{s1}] permutation is not "
                          "segment-local arange", backend, ctx)
    if mode != "reorder" or result.keys is None:
        return
    keys_out = np.asarray(result.keys)
    ids_out = np.asarray(spec(result.keys))
    ids_in = np.asarray(spec(keys))

    def _check_span(kin, kout, iin, iout, what):
        if not np.array_equal(np.sort(kin), np.sort(kout)):
            _fail(f"{what}: output keys are not a permutation of the input",
                  backend, ctx)
        if iout.shape[0] > 1 and (np.diff(iout) < 0).any():
            _fail(f"{what}: output bucket ids are not non-decreasing",
                  backend, ctx)
        del kin, iin

    if seg is not None:
        bounds = np.append(seg, n)
        for s0, s1 in zip(bounds[:-1], bounds[1:]):
            _check_span(keys_in[s0:s1], keys_out[s0:s1],
                        ids_in[s0:s1], ids_out[s0:s1], f"segment [{s0}:{s1}]")
    elif keys_in.ndim > 1:
        for r in range(keys_in.shape[0]):
            _check_span(keys_in[r], keys_out[r], ids_in[r], ids_out[r],
                        f"batch row {r}")
    else:
        _check_span(keys_in, keys_out, ids_in, ids_out, "flat")
    if values is not None and result.values is not None \
            and result.permutation is not None and seg is None \
            and keys_in.ndim == 1:
        vals_in = np.asarray(values)
        vals_out = np.asarray(result.values)
        perm = np.asarray(result.permutation)
        if not np.array_equal(vals_out[perm], vals_in):
            _fail("values were not carried by the key permutation",
                  backend, ctx)


def verify_routing(out: Any, ids: Any, starts: Any, num_experts: int,
                   capacity: int, *, level: Optional[int] = None,
                   backend: Optional[str] = None) -> None:
    """The serving-step variant (DESIGN.md §16/§17): check one
    ``route_tokens_segmented`` output ``(slot, keep, counts)``.  Level 1:
    per-request expert loads conserve every token.  Level 2: kept slots
    are unique, in range, and each (request, expert) keeps exactly
    ``min(load, capacity)`` tokens.  Raises :class:`KernelResultError`."""
    level = verify_level() if level is None else level
    if level <= 0:
        return
    slot, keep, counts = (np.asarray(x) for x in out)
    ids = np.asarray(ids)
    n = int(ids.shape[0])
    if (counts < 0).any():
        _fail(f"negative routing counts: min={counts.min()}", backend, None)
    if int(counts.sum()) != n:
        _fail(f"routing counts conservation: sum={int(counts.sum())} "
              f"!= tokens={n}", backend, None)
    if level == 1:
        return
    s = counts.shape[0]
    kept = slot[keep.astype(bool)]
    if kept.size != np.unique(kept).size:
        _fail("kept dispatch slots collide", backend, None)
    if kept.size and (kept.min() < 0 or kept.max() >= s * num_experts * capacity):
        _fail("kept dispatch slot out of range", backend, None)
    expect_kept = np.minimum(counts, capacity).sum()
    if int(keep.sum()) != int(expect_kept):
        _fail(f"kept token count {int(keep.sum())} != "
              f"sum(min(load, capacity))={int(expect_kept)}", backend, None)
