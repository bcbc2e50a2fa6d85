"""Serving subsystem acceptance (ISSUE 9): one segmented plan launch per
step, warm-plan reuse, fault retry/requeue/shed robustness, admission
behavior (deadline, caps, bucketing order, windowed planning), exact
percentiles, and the zero-length-segment regressions (S1)."""

import numpy as np
import jax.numpy as jnp
import pytest

from repro import ops
from repro.core.identifiers import delta_buckets
from repro.core.multisplit import segmented_multisplit as core_segmented
from repro.models import moe
from repro.runtime.supervisor import FaultInjector
from repro.serving import (
    ServerLoop,
    ServingConfig,
    closed_loop,
    open_loop,
    percentiles,
    poisson_arrivals,
    synthetic_requests,
)

BACKENDS = ["reference", "vmap", "pallas-interpret"]

E = 4  # experts in the small test config


def _cfg(**kw) -> ServingConfig:
    base = dict(
        num_experts=E,
        capacity=8,
        max_batch_requests=8,
        max_batch_tokens=64,
        max_wait=0.0,          # deadline always expired: step fires when polled
        max_queue_depth=64,
    )
    base.update(kw)
    return ServingConfig(**base)


def _reqs(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, E, size=n).astype(np.int32) for n in lengths]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class AlwaysFail:
    def check(self, step):
        raise RuntimeError("boom")


# ---------------------------------------------------------------------------
# Tentpole: k concurrent requests -> ONE segmented routing launch per step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_one_step_is_one_segmented_routing_call(backend, monkeypatch):
    """The coalescing claim, counter-tested: a step over k requests makes
    exactly ONE route_tokens_segmented call (the one segmented plan launch),
    on every segment backend."""
    loop = ServerLoop(_cfg(backend=backend))
    loop._jit_step = loop._step_fn    # eager, so the spy fires per call
    calls = []
    orig = moe.route_tokens_segmented

    def spy(ids, starts, *a, **k):
        calls.append((int(ids.shape[0]), int(np.asarray(starts).shape[0])))
        return orig(ids, starts, *a, **k)

    monkeypatch.setattr(moe, "route_tokens_segmented", spy)
    for r in _reqs([3, 5, 0, 7, 2]):       # ragged + one empty request
        assert loop.submit(r)
    rep = loop.step(force=True)
    loop.flush()
    assert rep["requests"] == 5 and rep["tokens"] == 17
    assert len(calls) == 1                 # 5 requests, ONE segmented launch
    n_pad, s_pad = calls[0]
    assert n_pad == rep["tokens_padded"] and s_pad == loop._s_pad
    s = loop.metrics_summary()
    assert s["completed"] == 5 and s["dropped_by_bug"] == 0


def test_pack_pads_with_last_expert_into_pad_segment():
    loop = ServerLoop(_cfg())
    reqs = _reqs([3, 0, 5])
    batch = []
    for r in reqs:
        loop.submit(r)
    batch = loop.queue.snapshot()
    ids, starts, n_tok = loop._pack(batch)
    assert n_tok == 8
    np.testing.assert_array_equal(ids[:8], np.concatenate([reqs[0], reqs[2]]))
    assert (ids[8:] == E - 1).all()        # pad tokens carry the last expert
    # starts: real cumsum then every remaining segment pinned at n_tok, so
    # pad tokens land in the trailing synthetic segment only
    assert starts.shape == (loop._s_pad,)
    np.testing.assert_array_equal(starts[:4], [0, 3, 3, 8])
    assert (starts[4:] == 8).all()


def test_warm_plan_reuse_zero_retraces():
    """Second same-shape-class step must not retrace the step function."""
    traces = []

    def step_fn(ids, starts):
        traces.append((ids.shape, starts.shape))
        return jnp.sum(ids) + jnp.sum(starts)

    loop = ServerLoop(_cfg(), step_fn=step_fn)
    for r in _reqs([4, 4]):
        loop.submit(r)
    loop.step(force=True)
    loop.flush()
    assert len(traces) == 1
    for r in _reqs([2, 3, 5], seed=1):     # different raggedness, same class
        loop.submit(r)
    loop.step(force=True)
    loop.flush()
    assert len(traces) == 1                # zero new traces
    assert loop.metrics_summary()["completed"] == 2 + 3


def test_routing_op_shared_across_loops():
    a = ServerLoop(_cfg())
    b = ServerLoop(_cfg())
    assert a._jit_step is b._jit_step      # lru-cached per (E, cap, backend)
    c = ServerLoop(_cfg(capacity=16))
    assert c._jit_step is not a._jit_step


# ---------------------------------------------------------------------------
# Robustness: in-step retry, requeue, bounded failure, load shedding
# ---------------------------------------------------------------------------

def test_fault_transient_retries_in_step():
    loop = ServerLoop(_cfg(), fault_injector=FaultInjector(fail_at={0: 1}))
    for r in _reqs([2, 3, 4]):
        loop.submit(r)
    loop.step(force=True)
    loop.flush()
    s = loop.metrics_summary()
    assert s["completed"] == 3 and s["failed"] == 0 and s["requeued"] == 0
    assert s["retries"] == 1 and s["dropped_by_bug"] == 0
    rec = loop.metrics.step_records[0]
    assert rec.ok and rec.attempts == 2


def test_fault_exhausts_attempts_requeues_then_succeeds():
    """A step that fails max_step_attempts times requeues its batch at the
    queue head; the next step completes it. Nothing is lost."""
    loop = ServerLoop(
        _cfg(max_step_attempts=3),
        fault_injector=FaultInjector(fail_at={0: 3}),
    )
    for r in _reqs([2, 3, 4]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 3 and s["failed"] == 0
    assert s["requeued"] == 3 and s["retries"] == 2
    assert s["dropped_by_bug"] == 0 and s["queued"] == 0
    # FIFO order survived the requeue
    assert [rid for rid, _ in loop.completed] == [0, 1, 2]
    recs = loop.metrics.step_records
    assert [r.ok for r in recs] == [False, True]


def test_fault_persistent_fails_requests_counted():
    """Requests over their requeue budget fail (counted, deliberate) —
    drain terminates and conservation still holds."""
    loop = ServerLoop(
        _cfg(max_step_attempts=1, max_requeues=1),
        fault_injector=AlwaysFail(),
    )
    for r in _reqs([2, 3, 4, 5]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 0 and s["failed"] == 4
    assert s["dropped_by_bug"] == 0 and s["queued"] == 0


def test_load_shed_on_queue_bound():
    loop = ServerLoop(_cfg(max_queue_depth=4))
    oks = [loop.submit(r) for r in _reqs([1] * 6)]
    assert oks == [True] * 4 + [False] * 2
    s = loop.drain()
    assert s["shed"] == 2 and s["completed"] == 4
    assert s["dropped_by_bug"] == 0


def test_load_shed_oversized_request():
    loop = ServerLoop(_cfg(max_batch_tokens=16))
    assert not loop.submit(np.zeros(17, np.int32))  # can never fit a batch
    s = loop.metrics_summary()
    assert s["shed"] == 1 and loop.queue.depth == 0


def test_fault_injector_rate_mode():
    fi = FaultInjector(rate=0.5, seed=0)
    hits = 0
    for i in range(200):
        try:
            fi.check(i)
        except RuntimeError:
            hits += 1
    assert hits == fi.injected and 50 < hits < 150
    with pytest.raises(ValueError):
        FaultInjector(rate=1.0)


# ---------------------------------------------------------------------------
# Admission: deadline, caps, bucketing order, windowed plan
# ---------------------------------------------------------------------------

def test_deadline_flush_fires_step():
    clk = FakeClock()
    loop = ServerLoop(_cfg(max_wait=0.05), clock=clk)
    loop.submit(np.zeros(3, np.int32))
    assert loop.step() is None             # underfull + deadline not expired
    clk.t += 0.06
    assert loop.step() is not None         # oldest waited past max_wait
    loop.flush()
    assert loop.metrics_summary()["completed"] == 1
    assert loop.metrics.empty_steps == 1


def test_full_batch_fires_without_deadline():
    clk = FakeClock()
    loop = ServerLoop(_cfg(max_wait=10.0, max_batch_requests=4), clock=clk)
    for r in _reqs([1, 1, 1]):
        loop.submit(r)
    assert loop.step() is None
    loop.submit(np.zeros(1, np.int32))     # request cap reached
    assert loop.step()["requests"] == 4


def test_token_cap_splits_batches():
    loop = ServerLoop(_cfg(max_batch_tokens=64, max_wait=10.0))
    for r in _reqs([30, 30, 30]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 3
    sizes = [r.requests for r in loop.metrics.step_records]
    assert sizes == [2, 1]                 # 60 tokens, then the deferred 30
    assert all(r.tokens <= 64 for r in loop.metrics.step_records)


def test_bucketing_orders_by_length_class_oldest_first():
    """Admission order groups by RangeSpec length class, FIFO within a
    class, and the OLDEST request's class leads (no starvation)."""
    loop = ServerLoop(_cfg(length_splitters=(4, 16), max_wait=10.0))
    for r in _reqs([20, 2, 2, 20, 2]):
        loop.submit(r)
    loop.step(force=True)
    loop.flush()
    assert loop.metrics_summary()["completed"] == 5
    assert [rid for rid, _ in loop.completed] == [0, 3, 1, 2, 4]


def test_windowed_plan_pops_queue_once():
    """One admit carves the whole lookahead window: later steps pop the
    pending plan without touching the queue."""
    loop = ServerLoop(_cfg(max_batch_requests=2, max_batch_tokens=1000,
                           lookahead_batches=2))
    for r in _reqs([1] * 5):
        loop.submit(r)
    assert loop.step(force=True)["requests"] == 2
    assert loop.policy.pending() == 2      # second window batch, pre-carved
    assert loop.queue.depth == 1           # only the out-of-window request
    assert loop.step(force=True)["requests"] == 2
    assert loop.policy.pending() == 0
    loop.drain()
    assert loop.metrics_summary()["completed"] == 5


def test_trailing_underfull_remainder_requeued():
    """The window's trailing underfull batch goes back to the queue head to
    be rebatched densely with the next window, not shipped sparse."""
    loop = ServerLoop(_cfg(max_batch_requests=2, max_batch_tokens=1000,
                           lookahead_batches=2))
    for r in _reqs([1] * 3):
        loop.submit(r)
    assert loop.step(force=True)["requests"] == 2
    assert loop.policy.pending() == 0      # [r2] deferred, NOT planned
    assert loop.queue.depth == 1
    assert [q.rid for q in loop.queue.snapshot()] == [2]


def test_invalidate_returns_plan_to_queue_head_in_order():
    loop = ServerLoop(_cfg(max_batch_requests=2, max_batch_tokens=1000,
                           lookahead_batches=2))
    for r in _reqs([1] * 5):
        loop.submit(r)
    loop.step(force=True)                  # plan now holds [r2, r3]
    loop.flush()
    assert loop.policy.pending() == 2
    loop.policy.invalidate(loop.queue)
    assert loop.policy.pending() == 0
    assert [q.rid for q in loop.queue.snapshot()] == [2, 3, 4]


# ---------------------------------------------------------------------------
# Percentiles (S2): exact nearest-rank, pinned to numpy's inverted_cdf
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 10, 100, 997])
def test_percentiles_match_numpy_inverted_cdf(n):
    xs = np.random.RandomState(n).uniform(0, 1e3, size=n)
    ps = (1.0, 25.0, 50.0, 95.0, 99.0, 99.9, 100.0)
    got = percentiles(xs.tolist(), ps)
    want = np.percentile(xs, ps, method="inverted_cdf")
    for p, w in zip(ps, want):
        assert got[p] == w, (n, p)
        assert got[p] in xs                # an OBSERVED sample, never a blend


def test_percentiles_edges():
    assert percentiles([5.0], (0.0,))[0.0] == 5.0
    assert all(np.isnan(v) for v in percentiles([]).values())
    with pytest.raises(ValueError):
        percentiles([1.0], (101.0,))


# ---------------------------------------------------------------------------
# Open loop + engine edges
# ---------------------------------------------------------------------------

def test_open_loop_smoke_conserves_requests():
    cfg = _cfg(max_batch_tokens=256, max_queue_depth=512, max_wait=0.002)
    loop = ServerLoop(cfg)
    loop.prewarm()
    n = 300
    reqs = synthetic_requests(n, cfg.num_experts, seed=7)
    arrivals = poisson_arrivals(n, qps=20_000.0, seed=7)
    s = open_loop(loop, reqs, arrivals)
    assert s["submitted"] == n
    assert s["completed"] + s["shed"] == n and s["failed"] == 0
    assert s["dropped_by_bug"] == 0 and s["queued"] == 0
    assert np.isfinite(s["latency_p99_ms"]) and s["latency_p99_ms"] >= 0
    assert 0 < s["batch_token_occupancy"] <= 1.0


def test_empty_step_and_empty_drain():
    loop = ServerLoop(_cfg())
    assert loop.step(force=True) is None   # nothing queued: a no-op poll
    s = loop.drain()
    assert s["steps"] == 0 and s["dropped_by_bug"] == 0
    assert np.isnan(s["latency_p50_ms"])   # no latency distribution yet


def test_config_validation():
    with pytest.raises(ValueError):
        _cfg(token_pad_classes=(16,))      # largest class < max_batch_tokens
    with pytest.raises(ValueError):
        _cfg(max_step_attempts=0)
    with pytest.raises(ValueError):
        _cfg(lookahead_batches=0)
    with pytest.raises(ValueError):
        _cfg(length_splitters=(16, 4))


# ---------------------------------------------------------------------------
# S1 regressions: zero-length segments and the s == 0 step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_multisplit_zero_segments(backend):
    bf = delta_buckets(4, 2**10)
    keys = jnp.zeros((0,), jnp.uint32)
    for fn in (
        lambda: ops.segmented_multisplit(keys, bf, np.zeros((0,), np.int32),
                                         backend=backend),
        lambda: core_segmented(keys, bf, np.zeros((0,), np.int32),
                               backend=backend),
    ):
        out = fn()
        assert out.bucket_counts.shape == (0, 4)
        assert out.bucket_starts.shape == (0, 4)
        assert out.keys.shape == (0,)


def test_segmented_multisplit_zero_segments_rejects_nonempty_keys():
    bf = delta_buckets(4, 2**10)
    with pytest.raises(ValueError):
        ops.segmented_multisplit(
            jnp.zeros((8,), jnp.uint32), bf, np.zeros((0,), np.int32)
        )


@pytest.mark.parametrize("backend", BACKENDS)
def test_route_tokens_segmented_zero_requests(backend):
    slot, keep, counts = moe.route_tokens_segmented(
        jnp.zeros((0,), jnp.int32), np.zeros((0,), np.int32), E, 8,
        backend=backend,
    )
    assert slot.shape == (0,) and keep.shape == (0,)
    assert counts.shape == (0, E)


@pytest.mark.parametrize("backend", BACKENDS)
def test_route_tokens_segmented_zero_length_segments(backend):
    """Leading / interior / trailing empty segments: all-zero count rows,
    and the non-empty segments bitwise match their independent routing."""
    rng = np.random.RandomState(21)
    ids = jnp.asarray(rng.randint(0, E, 40, dtype=np.int32))
    starts = [0, 0, 10, 10, 10, 40]        # segs 0,2,3,5 are empty
    slot, keep, counts = moe.route_tokens_segmented(
        ids, starts, E, 8, backend=backend
    )
    counts_np = np.asarray(counts)
    assert counts_np.shape == (6, E)
    for empty_seg in (0, 2, 3, 5):
        assert (counts_np[empty_seg] == 0).all()
    ends = starts[1:] + [40]
    for i, (a, b) in enumerate(zip(starts, ends)):
        for ex in range(E):
            assert counts_np[i, ex] == int((np.asarray(ids[a:b]) == ex).sum())
    assert bool(np.asarray(keep)[: 0].all())  # vacuous on empties, no crash


# ---------------------------------------------------------------------------
# ISSUE 10 (S6): degradation + runtime-verification counters in serving
# ---------------------------------------------------------------------------

class AlwaysKernelFault:
    """Raises with a RESOURCE marker the resilience classifier recognizes —
    unlike AlwaysFail's generic 'boom', this is a persistent KERNEL failure
    and must degrade to the reference rung instead of requeueing."""

    def check(self, step):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory in VMEM scratch")


@pytest.fixture
def rz_clean():
    from repro.runtime import resilience as rz

    rz.reset_stats()
    rz.set_verify(None)
    rz.set_strict(None)
    rz.set_fault_injector(None)
    yield rz
    rz.reset_stats()
    rz.set_verify(None)
    rz.set_strict(None)
    rz.set_fault_injector(None)


def test_metrics_summary_has_resilience_counters(rz_clean):
    loop = ServerLoop(_cfg())
    for r in _reqs([2, 3]):
        loop.submit(r)
    s = loop.drain()
    assert s["degradations"] == 0 and s["verify_mismatches"] == 0
    assert s["completed"] == 2 and s["dropped_by_bug"] == 0


def test_persistent_kernel_fault_degrades_to_reference(rz_clean):
    """Every launch hits a persistent resource fault: without the §17
    ladder all requests would exhaust their requeue budget and FAIL; with
    it, each step re-runs on the reference backend and completes."""
    loop = ServerLoop(
        _cfg(max_step_attempts=1, max_requeues=0),
        fault_injector=AlwaysKernelFault(),
    )
    for r in _reqs([2, 3, 4, 5]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 4 and s["failed"] == 0
    assert s["degradations"] >= 1 and s["dropped_by_bug"] == 0
    assert rz_clean.stats()["degradations"] >= 1


def test_degrade_respects_strict_mode(rz_clean):
    """REPRO_STRICT disables the serving fallback too: the pre-§17
    requeue-then-fail accounting returns."""
    rz_clean.set_strict(True)
    loop = ServerLoop(
        _cfg(max_step_attempts=1, max_requeues=0),
        fault_injector=AlwaysKernelFault(),
    )
    for r in _reqs([2, 3]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 0 and s["failed"] == 2
    assert s["degradations"] == 0 and s["dropped_by_bug"] == 0


def test_verify_mismatch_counted_and_healed_by_reference(rz_clean):
    """A lying step function (tampered routing counts) is caught by the
    sampled REPRO_VERIFY check; the step re-runs on reference and the
    mismatch is counted in the summary + the structured repro report."""
    rz_clean.set_verify(2)
    loop = ServerLoop(_cfg(verify_sample_rate=1.0))
    real = loop._jit_step

    def lying(ids, starts):
        slot, keep, counts = real(ids, starts)
        bad = np.asarray(counts).copy()
        bad[0, 0] += 1                       # breaks token conservation
        return slot, keep, jnp.asarray(bad)

    loop._jit_step = lying
    for r in _reqs([2, 3, 4]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 3 and s["failed"] == 0
    assert s["verify_mismatches"] >= 1 and s["degradations"] >= 1
    assert s["dropped_by_bug"] == 0
    report = rz_clean.last_report()
    assert report is not None and report["spec"] == "route_tokens_segmented"
    assert rz_clean.stats()["verify_mismatches"] == s["verify_mismatches"]


def test_dispatch_faults_under_verify_never_corrupt_or_lose_requests(rz_clean):
    """Chaos: seeded dispatch faults on 5% of launches under level-2
    verification. Every injected fault must retry or degrade; none may
    corrupt a routing result or lose a request."""
    rz_clean.set_verify(2)
    injector = FaultInjector(dispatch_rate=0.05, seed=13)
    rz_clean.set_fault_injector(injector)
    cfg = _cfg(max_batch_tokens=256, max_batch_requests=4, max_queue_depth=512)
    n = 200                                  # 50 steps, 5 of them faulted
    s = closed_loop(ServerLoop(cfg), synthetic_requests(n, E, seed=13))
    assert injector.dispatch_injected > 0
    assert s["verify_mismatches"] == 0 and s["dropped_by_bug"] == 0
    assert s["submitted"] == n
    assert s["completed"] + s["shed"] + s["failed"] == n


def test_verify_sample_rate_zero_never_checks(rz_clean):
    rz_clean.set_verify(2)
    loop = ServerLoop(_cfg(verify_sample_rate=0.0))
    for r in _reqs([2, 3]):
        loop.submit(r)
    s = loop.drain()
    assert s["completed"] == 2 and s["verify_mismatches"] == 0
    assert rz_clean.stats()["verify_checks"] == 0


def test_verify_sample_rate_validation():
    with pytest.raises(ValueError, match="verify_sample_rate"):
        _cfg(verify_sample_rate=1.5)
