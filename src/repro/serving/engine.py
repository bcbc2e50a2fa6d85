"""The continuous-batching step engine (DESIGN.md §16).

One :meth:`ServerLoop.step` = one admission decision + ONE segmented plan
launch for every admitted request:

    queue -> admit (RangeSpec length bucketing) -> pad to a shape class ->
    route_tokens_segmented (ONE segmented positions_only multisplit) ->
    per-request completion + metrics

Warm-plan reuse is structural, not incidental: admitted batches are padded
to a small ladder of ``(tokens, segments)`` shape classes, the step function
is one ``jax.jit`` callable, and the plan layer underneath hashes by value —
so after the first step of each shape class NOTHING retraces and NO plan is
rebuilt, step after step (counter-tested). ``REPRO_AUTOTUNE=1`` +
:meth:`ServerLoop.prewarm` moves even the first-miss autotune search out of
the serving path.

Robustness reuses the :class:`~repro.runtime.supervisor.FaultInjector`
pattern: a failed launch retries in-step (bounded), then requeues the batch
at the queue head (bounded per request, then counted ``failed``); submit
past the queue bound sheds (counted); :meth:`ServerLoop.drain` flushes the
queue ignoring the batching deadline on shutdown. Request accounting is
conservation-checked: ``dropped_by_bug`` must be zero always.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core.pipeline import backend_for
from repro.runtime import resilience as _rz
from repro.serving.admission import AdmissionConfig, AdmissionPolicy
from repro.serving.metrics import ServingMetrics, StepRecord
from repro.serving.request import Request, RequestQueue

log = logging.getLogger("repro.serving")

__all__ = ["ServingConfig", "ServerLoop"]


@dataclasses.dataclass
class _Inflight:
    """One asynchronously launched, not-yet-finalized serving step."""

    batch: List["Request"]
    ids: np.ndarray
    starts: np.ndarray
    idx: int
    depth_at_admit: int
    n_tok: int
    t0: float
    attempts: int
    out: Any                 # device output (None if the launch itself raised)
    err: Optional[Exception]


@functools.lru_cache(maxsize=32)
def _routing_op(num_experts: int, capacity: int, backend: str):
    """(eager_fn, jitted_fn) for the default routing step, shared across
    ServerLoop instances — a second loop with the same (experts, capacity,
    backend) reuses the trace/compile cache instead of rebuilding it."""
    def run(expert_ids, segment_starts):
        from repro.models.moe import route_tokens_segmented

        return route_tokens_segmented(
            expert_ids, segment_starts, num_experts, capacity, backend=backend,
        )

    return run, jax.jit(run)


def _default_token_classes(max_batch_tokens: int) -> Tuple[int, ...]:
    """Padded flat-buffer ladder: x4 steps up to the batch-token cap, so a
    lightly loaded step doesn't pay the full-batch buffer and the jit/plan
    cache stays at a handful of shapes."""
    classes = []
    c = min(256, max_batch_tokens)
    while c < max_batch_tokens:
        classes.append(c)
        c *= 4
    classes.append(max_batch_tokens)
    return tuple(classes)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Continuous-batching server configuration (hashable, all-static)."""

    num_experts: int = 8
    capacity: int = 64               # per-(request, expert) dispatch slots
    max_batch_requests: int = 64
    max_batch_tokens: int = 4096
    max_wait: float = 0.02           # flush deadline (s)
    length_splitters: Tuple[int, ...] = (32, 128)
    token_pad_classes: Tuple[int, ...] = ()     # () -> derived ladder
    backend: Optional[str] = None    # None -> default_backend at the batch cap
    max_step_attempts: int = 3       # in-step launch tries (1 = no retry)
    max_requeues: int = 1            # failed-step requeues before a request fails
    max_queue_depth: int = 4096
    lookahead_batches: int = 4       # admission window, in max-size batches
    verify_sample_rate: float = 1.0  # launch-sampling rate once REPRO_VERIFY
    verify_seed: int = 0             # is armed (DESIGN.md §17)

    def __post_init__(self) -> None:
        object.__setattr__(self, "backend", backend_for(
            self.backend, self.max_batch_tokens, np.int32))
        if not self.token_pad_classes:
            object.__setattr__(
                self, "token_pad_classes",
                _default_token_classes(self.max_batch_tokens),
            )
        classes = self.token_pad_classes
        if list(classes) != sorted(set(classes)):
            raise ValueError(f"token_pad_classes must ascend, got {classes}")
        if classes[-1] < self.max_batch_tokens:
            raise ValueError(
                f"largest token class {classes[-1]} < max_batch_tokens "
                f"{self.max_batch_tokens}: a full batch has no shape class"
            )
        if self.max_step_attempts < 1:
            raise ValueError("max_step_attempts must be >= 1")
        if self.lookahead_batches < 1:
            raise ValueError("lookahead_batches must be >= 1")
        if list(self.length_splitters) != sorted(set(self.length_splitters)):
            raise ValueError(
                f"length_splitters must be strictly ascending, got "
                f"{self.length_splitters}"
            )
        if not 0.0 <= self.verify_sample_rate <= 1.0:
            raise ValueError(
                f"verify_sample_rate must be in [0, 1], got "
                f"{self.verify_sample_rate}"
            )

    def admission(self) -> AdmissionConfig:
        return AdmissionConfig(
            max_batch_requests=self.max_batch_requests,
            max_batch_tokens=self.max_batch_tokens,
            max_wait=self.max_wait,
            length_splitters=self.length_splitters,
            backend=self.backend,
            lookahead_batches=self.lookahead_batches,
        )


class ServerLoop:
    """Request-level continuous batching over the segmented plan layer.

    ``step_fn(expert_ids, segment_starts)`` is the per-step device program
    (default: :func:`~repro.models.moe.route_tokens_segmented` with this
    config's experts/capacity/backend); it always sees the PADDED shapes.
    ``fault_injector`` follows the
    :class:`~repro.runtime.supervisor.FaultInjector` protocol (``check(step)``
    raises to simulate a failure); ``clock`` is injectable for deterministic
    tests.
    """

    def __init__(
        self,
        cfg: ServingConfig,
        *,
        step_fn: Optional[Callable[[Any, Any], Any]] = None,
        fault_injector: Optional[Any] = None,
        clock: Callable[[], float] = time.monotonic,
    ) -> None:
        self.cfg = cfg
        self.clock = clock
        self.queue = RequestQueue(cfg.max_queue_depth)
        self.policy = AdmissionPolicy(cfg.admission())
        self.metrics = ServingMetrics()
        self.faults = fault_injector
        self._default_step = step_fn is None
        if step_fn is None:
            self._step_fn, self._jit_step = _routing_op(
                cfg.num_experts, cfg.capacity, cfg.backend)
        else:
            self._step_fn, self._jit_step = step_fn, jax.jit(step_fn)
        self._verify_rng = np.random.RandomState(cfg.verify_seed)
        self._step_idx = 0
        self._next_rid = 0
        self._inflight: Optional[_Inflight] = None
        self.completed: List[Tuple[int, float]] = []   # (rid, latency_s)

    # -- shape classes ------------------------------------------------------
    @property
    def _s_pad(self) -> int:
        # +1: the trailing PAD segment that absorbs pad tokens — a full
        # batch must never leak its padding into a real request's counts
        return self.cfg.max_batch_requests + 1

    def _token_class(self, n_tok: int) -> int:
        for c in self.cfg.token_pad_classes:
            if c >= n_tok:
                return c
        return self.cfg.token_pad_classes[-1]

    def _pack(self, batch: List[Request]) -> Tuple[np.ndarray, np.ndarray, int]:
        """Coalesce a batch into the padded flat buffer + segment starts.

        Pad tokens carry expert ``E-1`` and live in the pad segment (rows
        ``>= len(batch)`` of the counts are synthetic and ignored); empty
        requests are zero-length segments — both exercised every step, which
        is why their plan-layer behavior is regression-pinned (ISSUE 9 S1).
        """
        lengths = [r.length for r in batch]
        n_tok = int(sum(lengths))
        n_pad = self._token_class(n_tok)
        ids = np.full((n_pad,), self.cfg.num_experts - 1, np.int32)
        if n_tok:
            ids[:n_tok] = np.concatenate([r.expert_ids for r in batch])
        starts = np.full((self._s_pad,), n_tok, np.int32)
        starts[0] = 0
        if len(lengths) > 1:
            starts[1:len(lengths)] = np.cumsum(lengths[:-1])
        return ids, starts, n_tok

    # -- ingress -------------------------------------------------------------
    def submit(self, expert_ids, *, arrival: Optional[float] = None,
               rid: Optional[int] = None) -> bool:
        """Enqueue one request; False = load-shed (queue full / oversized)."""
        arrival = self.clock() if arrival is None else arrival
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid) + 1
        req = Request(rid, expert_ids, arrival)
        self.metrics.observe_submit(arrival)
        if req.length > self.cfg.max_batch_tokens:
            self.metrics.observe_shed()          # can never fit a batch
            return False
        ok = self.queue.submit(req)
        if not ok:
            self.metrics.observe_shed()
        self.metrics.observe_queue_depth(self.queue.depth)
        return ok

    # -- one serving step ----------------------------------------------------
    def step(self, force: bool = False) -> Optional[Dict[str, Any]]:
        """Admit + launch once (PIPELINED). Returns None when nothing was
        admissible (not ready and not forced), else a launch report.

        The launch is asynchronous: step ``k``'s dispatch happens BEFORE
        step ``k-1`` is blocked on, so admission/packing host work overlaps
        device execution and the device never idles between steps. The
        previous step's completions (and failure handling) are finalized
        here; call :meth:`flush` to finalize the last in-flight step when
        going idle."""
        now = self.clock()
        batch = self.policy.admit(self.queue, now, force=force)
        if not batch:
            self.metrics.observe_empty_step()
            return None
        depth_at_admit = self.queue.depth + self.policy.pending() + len(batch)
        ids, starts, n_tok = self._pack(batch)
        idx = self._step_idx
        self._step_idx += 1
        t0 = self.clock()
        out, launch_err = None, None
        try:
            out = self._launch(ids, starts, idx)     # async dispatch
        except Exception as e:  # noqa: BLE001 — serving boundary
            launch_err = e
            log.warning("step %d attempt 1 failed at launch: %s", idx, e)
        self.flush()             # block on the PREVIOUS step while this one runs
        self._inflight = _Inflight(batch, ids, starts, idx, depth_at_admit,
                                   n_tok, t0, 1, out, launch_err)
        return {"step": idx, "ok": True, "requests": len(batch),
                "tokens": n_tok, "tokens_padded": int(ids.shape[0])}

    def _launch(self, ids, starts, idx: int):
        """Fault-injection check + asynchronous device dispatch."""
        if self.faults is not None:
            self.faults.check(idx)
        _rz.check_faults(self.cfg.backend)   # dispatch-level injection (§17)
        return self._jit_step(ids, starts)

    def _reference_rerun(self, p: "_Inflight"):
        """Re-run one step EAGERLY on the reference backend (the last rung
        of the §17 ladder at the serving boundary)."""
        ref_run, _ = _routing_op(
            self.cfg.num_experts, self.cfg.capacity, "reference")
        out = ref_run(p.ids, p.starts)
        jax.block_until_ready(out)
        self.metrics.degradations += 1
        _rz._count("degradations")
        return out

    def _degrade(self, p: "_Inflight", err: Exception):
        """Persistent kernel failures (lowering / resource) never heal by
        requeueing — the step re-runs on the reference backend instead so
        its requests still complete (degraded, counted). Transient faults
        and non-kernel errors keep the requeue path; a custom ``step_fn``
        has no reference twin; ``REPRO_STRICT`` disables all fallback."""
        if not self._default_step or _rz.strict():
            return None
        kerr = _rz.classify(err, backend=self.cfg.backend)
        if not isinstance(kerr, (_rz.KernelLoweringError,
                                 _rz.KernelResourceError)):
            return None
        try:
            out = self._reference_rerun(p)
        except Exception as ref_e:  # noqa: BLE001 — fall back to requeue
            log.warning("step %d reference fallback failed: %s", p.idx, ref_e)
            return None
        _rz._count("backend_demotions")
        _rz._event("serving_degrade", step=p.idx, frm=self.cfg.backend,
                   to="reference", error=type(kerr).__name__)
        log.warning("step %d degraded to reference after %s: %s",
                    p.idx, type(kerr).__name__, err)
        return out

    def _verify_ctx(self, p: "_Inflight") -> _rz.DispatchContext:
        return _rz.DispatchContext(
            spec_name="route_tokens_segmented", shape=(int(p.ids.shape[0]),),
            num_buckets=self.cfg.num_experts, mode="positions",
            layout="segmented", seed=self.cfg.verify_seed,
        )

    def _maybe_verify(self, p: "_Inflight", out):
        """Sampled runtime verification of one routing launch (§17): on a
        mismatch, count it, emit the structured repro report, and return
        the reference re-run so the degraded result is still correct."""
        if (not self._default_step or _rz.verify_level() <= 0
                or self.cfg.backend == "reference"
                or self._verify_rng.random_sample()
                >= self.cfg.verify_sample_rate):
            return out
        _rz._count("verify_checks")
        try:
            _rz.verify_routing(out, p.ids, p.starts, self.cfg.num_experts,
                               self.cfg.capacity, backend=self.cfg.backend)
            return out
        except _rz.KernelResultError as ve:
            if _rz.strict():
                raise
            self.metrics.verify_mismatches += 1
            _rz._count("verify_mismatches")
            _rz._count("reference_reruns")
            _rz._event("serving_verify_mismatch", step=p.idx,
                       backend=self.cfg.backend, detail=str(ve))
            _rz._emit_report(self._verify_ctx(p), self.cfg.backend, str(ve))
            log.warning("step %d verify mismatch, re-running on reference: %s",
                        p.idx, ve)
            return self._reference_rerun(p)

    def flush(self) -> None:
        """Finalize the in-flight step: block for its completion, retry its
        launch in place on failure (bounded), then record completions or
        requeue/fail its batch."""
        p = self._inflight
        if p is None:
            return
        self._inflight = None
        out, attempts, err = p.out, p.attempts, p.err
        while True:
            if out is None and err is not None:       # last attempt failed
                if attempts >= self.cfg.max_step_attempts:
                    break
                attempts += 1
                self.metrics.retries += 1
            try:
                if out is None:
                    out = self._launch(p.ids, p.starts, p.idx)
                jax.block_until_ready(out)
                err = None
                break
            except Exception as e:  # noqa: BLE001 — serving boundary
                err, out = e, None
                log.warning("step %d attempt %d failed: %s", p.idx, attempts, e)

        if err is not None:
            out = self._degrade(p, err)      # §17: reference rung, not requeue
            if out is not None:
                err = None
        if err is not None:
            # bounded requeue: the batch goes back to the queue HEAD in
            # order; requests over their requeue budget fail (counted).
            kept, dead = [], []
            for r in p.batch:
                r.requeues += 1
                (kept if r.requeues <= self.cfg.max_requeues else dead).append(r)
            # plan back first, then the failed batch AHEAD of it (it is older)
            self.policy.invalidate(self.queue)
            self.queue.requeue_front(kept)
            self.metrics.requeued += len(kept)
            self.metrics.failed += len(dead)
            rec = StepRecord(p.idx, len(p.batch), p.n_tok, p.ids.shape[0],
                             p.depth_at_admit, self.clock() - p.t0,
                             attempts=attempts, ok=False)
            self.metrics.observe_step(rec)
            return

        out = self._maybe_verify(p, out)     # §17: sampled output checking
        done = self.clock()
        for r in p.batch:
            self.metrics.observe_completion(r.arrival, done)
            self.completed.append((r.rid, done - r.arrival))
        rec = StepRecord(p.idx, len(p.batch), p.n_tok, p.ids.shape[0],
                         p.depth_at_admit, done - p.t0, attempts=attempts)
        self.metrics.observe_step(rec)

    # -- lifecycle -----------------------------------------------------------
    def prewarm(self) -> None:
        """Trace/compile every shape class before traffic, and — when
        autotuning is armed (``REPRO_AUTOTUNE=1`` /
        ``repro.ops.set_autotune(True)``) — run each class EAGERLY first so
        the measured (tile, family) resolution happens here, not under the
        first user-visible step (autotune defers inside a trace)."""
        from repro.core.pipeline import autotune as _at

        starts = np.zeros((self._s_pad,), np.int32)
        for c in self.cfg.token_pad_classes:
            ids = np.zeros((c,), np.int32)
            if _at.armed():
                # autotune defers under a trace: one EAGER pass per class
                # lets the measured (tile, family) search run here
                jax.block_until_ready(self._step_fn(ids, starts))
            jax.block_until_ready(self._jit_step(ids, starts))   # compile
        # the admission-side length-bucketing op, over the queue-depth
        # padding ladder (powers of two) up to the admission window, so a
        # depth class first seen under traffic doesn't compile mid-step
        window = self.cfg.lookahead_batches * self.cfg.max_batch_requests
        depth, probes = 8, []
        while depth <= min(self.cfg.max_queue_depth, window):
            probes.append(depth)
            depth *= 2
        for d in probes:
            dummy = [Request(-1, np.zeros((1,), np.int32), 0.0)] * d
            self.policy.length_groups(dummy)
        log.info("prewarmed %d shape classes, %d admission depths",
                 len(self.cfg.token_pad_classes), len(probes))

    def drain(self) -> Dict[str, float]:
        """Graceful shutdown: flush the queue ignoring the batching deadline
        (bounded — failing requests exhaust their requeue budget and are
        counted), finalize the last in-flight step, then return the final
        metrics summary."""
        while True:
            while self.queue.depth or self.policy.pending():
                self.step(force=True)
            self.flush()          # may requeue a failed in-flight batch
            if not (self.queue.depth or self.policy.pending()):
                return self.metrics_summary()

    # -- observability -------------------------------------------------------
    def metrics_summary(self) -> Dict[str, float]:
        """The exported metrics dict (+ live queue depth and the
        conservation check — ``dropped_by_bug`` MUST be 0)."""
        s = self.metrics.summary()
        queued = self.queue.depth + self.policy.pending()
        if self._inflight is not None:
            queued += len(self._inflight.batch)
        s["queued"] = queued
        s["dropped_by_bug"] = self.metrics.dropped_by_bug(queued)
        return s
