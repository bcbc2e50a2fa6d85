"""Chaos + resilience suite (ISSUE 10, DESIGN.md §17): the failure
taxonomy, the graceful-degradation ladder (tile shrink → backend demotion →
reference), the persistent circuit breaker, strict mode, runtime output
verification, and seeded dispatch-level fault injection — everything the
host CI can prove about surviving kernel failures without a TPU."""

import json
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import ops
from repro.core.identifiers import EvenSpec
from repro.core.pipeline import autotune as at
from repro.core.pipeline import clear_tile_cache, set_autotune
from repro.kernels import ops as kops
from repro.runtime import resilience as rz
from repro.runtime.supervisor import FaultInjector, Supervisor, TrainLoopConfig

N = 1024
M = 8
FAULT_RATE = 0.05

BACKENDS = ("reference", "vmap", "pallas-interpret", "pallas")


def _spec(m=M):
    return EvenSpec(0.0, float(1 << 20), m)


def _keys(n=N, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, 1 << 20, n, dtype=np.uint32))


@pytest.fixture(autouse=True)
def iso(tmp_path):
    """Every test runs against a throwaway quarantine/autotune directory
    with clean counters, no injector, and default strict/verify."""
    prev = at._CONFIG
    set_autotune(cache_dir=str(tmp_path))
    rz.clear_quarantine(disk=True)
    rz.reset_stats()
    rz.set_fault_injector(None)
    rz.set_strict(None)
    rz.set_verify(None)
    clear_tile_cache()
    yield tmp_path
    rz.set_fault_injector(None)
    rz.set_strict(None)
    rz.set_verify(None)
    rz.clear_quarantine(disk=True)
    rz.reset_stats()
    at._CONFIG = prev
    at._LOADED = None
    clear_tile_cache()


# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("exc,cls", [
    (RuntimeError("RESOURCE_EXHAUSTED: out of memory allocating VMEM scratch"),
     rz.KernelResourceError),
    (MemoryError("oom"), rz.KernelResourceError),
    (RuntimeError("Mosaic lowering failed: unsupported primitive"),
     rz.KernelLoweringError),
    (NotImplementedError("no kernel for this"), rz.KernelLoweringError),
    (RuntimeError("UNAVAILABLE: transient backend interruption"),
     rz.TransientDispatchError),
    (RuntimeError("DEADLINE_EXCEEDED: preempted"), rz.TransientDispatchError),
    (RuntimeError("something else entirely"), rz.KernelDispatchError),
    (OSError("device file vanished"), rz.KernelDispatchError),
])
def test_classify_taxonomy(exc, cls):
    err = rz.classify(exc, backend="pallas", plan_class=("s", (N,)))
    assert type(err) is cls
    assert err.original is exc and err.__cause__ is exc
    assert err.backend == "pallas"
    assert err.transient == (cls is rz.TransientDispatchError)


def test_classify_programming_errors_propagate():
    """Validation errors are caller bugs, not execution failures."""
    assert rz.classify(ValueError("keys must be rank-1")) is None
    assert rz.classify(TypeError("expected a BucketSpec")) is None
    assert rz.classify(KeyError("nope")) is None
    # ...unless the message proves a kernel-side failure
    assert isinstance(rz.classify(ValueError("mosaic lowering rejected op")),
                      rz.KernelLoweringError)


def test_classify_word_boundary_markers():
    """'oom' must not classify 'boom' (the marker is a word, not a
    substring) while 'allocating' still hits the 'allocat' prefix."""
    assert type(rz.classify(RuntimeError("boom"))) is rz.KernelDispatchError
    assert isinstance(rz.classify(RuntimeError("OOM on device 0")),
                      rz.KernelResourceError)
    assert isinstance(rz.classify(RuntimeError("failed allocating 4MiB")),
                      rz.KernelResourceError)


def test_classify_passthrough_and_demote_chain():
    err = rz.KernelLoweringError("x")
    assert rz.classify(err) is err
    chain = []
    b = "pallas"
    while b is not None:
        chain.append(b)
        b = rz.demote(b)
    assert chain == list(rz.DEMOTION_ORDER)
    assert rz.demote("some-future-backend") == "reference"


# ---------------------------------------------------------------------------
# Tentpole acceptance: chaos at rate 0.05 across the backend x layout
# matrix — every facade call returns bitwise-reference-identical results
# with zero unhandled exceptions.
# ---------------------------------------------------------------------------

def _assert_bitwise(got, want):
    for field in got._fields:
        g, w = getattr(got, field), getattr(want, field)
        assert (g is None) == (w is None), field
        if g is not None:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), err_msg=field)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kv", [False, True])
def test_chaos_flat_bitwise_identical(backend, kv):
    spec, keys = _spec(), _keys()
    vals = jnp.arange(N, dtype=jnp.int32)
    want = (ops.multisplit_key_value(keys, vals, spec, backend="reference")
            if kv else ops.multisplit(keys, spec, backend="reference"))
    rz.set_fault_injector(FaultInjector(dispatch_rate=FAULT_RATE, seed=3))
    for trial in range(12):
        got = (ops.multisplit_key_value(keys, vals, spec, backend=backend)
               if kv else ops.multisplit(keys, spec, backend=backend))
        _assert_bitwise(got, want)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("kv", [False, True])
def test_chaos_segmented_bitwise_identical(backend, kv):
    spec, keys = _spec(), _keys()
    vals = jnp.arange(N, dtype=jnp.int32) if kv else None
    seg = jnp.asarray([0, 100, 100, 700], jnp.int32)   # incl. an empty segment
    want = ops.segmented_multisplit(keys, spec, seg, vals, backend="reference")
    rz.set_fault_injector(FaultInjector(dispatch_rate=FAULT_RATE, seed=5))
    for trial in range(12):
        got = ops.segmented_multisplit(keys, spec, seg, vals, backend=backend)
        _assert_bitwise(got, want)


@pytest.mark.parametrize("backend", ["vmap", "pallas-interpret"])
def test_chaos_batched_vmap_bitwise_identical(backend):
    """Batched layout reaches the plan layer via jax.vmap: the ladder is
    bypassed under tracing (exceptions cannot cross a jit trace), so faults
    never fire inside the trace and results stay bitwise-correct."""
    spec = _spec()
    rng = np.random.RandomState(1)
    keys = jnp.asarray(rng.randint(0, 1 << 20, (4, 256), dtype=np.uint32))
    want = jax.vmap(lambda k: ops.multisplit(k, spec, backend="reference"))(keys)
    rz.set_fault_injector(FaultInjector(dispatch_rate=FAULT_RATE, seed=7))
    got = jax.vmap(lambda k: ops.multisplit(k, spec, backend=backend))(keys)
    _assert_bitwise(got, want)


def test_chaos_verify2_still_bitwise_identical():
    """Faults + full verification together: the ladder heals, the verifier
    never fires (the kernels are honest), results stay reference-exact."""
    spec, keys = _spec(), _keys(seed=11)
    want = ops.multisplit(keys, spec, backend="reference")
    ops.set_verify(2)
    rz.set_fault_injector(FaultInjector(dispatch_rate=FAULT_RATE, seed=13))
    for trial in range(8):
        _assert_bitwise(ops.multisplit(keys, spec, backend="pallas"), want)
    assert rz.stats()["verify_mismatches"] == 0
    assert rz.stats()["verify_checks"] > 0


# ---------------------------------------------------------------------------
# The ladder, rung by rung (driven through rz.dispatch directly)
# ---------------------------------------------------------------------------

def _ctx(**kw):
    base = dict(spec_name="even", shape=(N,), num_buckets=M)
    base.update(kw)
    return rz.DispatchContext(**base)


def test_demotion_order_respected():
    attempts = []

    def run(backend, tile):
        attempts.append(backend)
        if backend != "reference":
            raise RuntimeError("Mosaic lowering failed: unsupported primitive")
        return "ok"

    assert rz.dispatch(run, _ctx(), backend="pallas") == "ok"
    assert attempts == list(rz.DEMOTION_ORDER)
    s = rz.stats()
    assert s["backend_demotions"] == 3 and s["degradations"] == 3


def test_demotion_skips_interpret_rung_when_tpu_attached(monkeypatch):
    """On a TPU host the ladder goes pallas -> vmap: interpreting the
    kernels there would leave the chip idle behind a CPU emulation."""
    monkeypatch.setattr(kops, "_tpu_available", lambda: True)
    assert rz.demote("pallas") == "vmap"
    attempts = []

    def run(backend, tile):
        attempts.append(backend)
        if backend != "reference":
            raise RuntimeError("Mosaic lowering failed: unsupported primitive")
        return "ok"

    assert rz.dispatch(run, _ctx(), backend="pallas") == "ok"
    assert attempts == ["pallas", "vmap", "reference"]
    assert rz.stats()["backend_demotions"] == 2


def test_tpu_probe_raises_on_backend_init_failure(monkeypatch):
    """A backend that fails to initialize must surface, not read as "no
    TPU" (which would silently select interpret mode)."""
    def broken_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    kops._tpu_available.cache_clear()
    monkeypatch.setattr(jax, "devices", broken_backend)
    try:
        with pytest.raises(RuntimeError, match="initialize"):
            kops._tpu_available()
    finally:
        kops._tpu_available.cache_clear()


def test_resource_error_halves_tile_and_pins_survivor():
    tried, pinned = [], []

    def run(backend, tile):
        tried.append((backend, tile))
        if tile is None or tile > 512:
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory in VMEM")
        return "ok"

    out = rz.dispatch(run, _ctx(), backend="pallas",
                      resolved_tile=lambda b: 2048,
                      pin_tile=lambda b, t: pinned.append((b, t)))
    assert out == "ok"
    assert tried == [("pallas", None), ("pallas", 1024), ("pallas", 512)]
    assert pinned == [("pallas", 512)]
    s = rz.stats()
    assert s["tile_shrinks"] == 2 and s["backend_demotions"] == 0


def test_resource_error_demotes_below_min_tile():
    """When the shrink ladder bottoms out, the rung demotes like any other
    persistent failure."""
    def run(backend, tile):
        if backend == "pallas":
            raise RuntimeError("RESOURCE_EXHAUSTED: out of memory in VMEM")
        return backend

    out = rz.dispatch(run, _ctx(), backend="pallas",
                      resolved_tile=lambda b: 512)
    assert out == "pallas-interpret"
    s = rz.stats()
    assert s["tile_shrinks"] == 1 and s["backend_demotions"] == 1


def test_transient_retries_in_place_then_demotes():
    calls = {"pallas": 0}

    def run(backend, tile):
        if backend == "pallas":
            calls["pallas"] += 1
            raise RuntimeError("UNAVAILABLE: transient link flap")
        return backend

    out = rz.dispatch(run, _ctx(), backend="pallas")
    assert out == "pallas-interpret"
    assert calls["pallas"] == 1 + rz.MAX_TRANSIENT_RETRIES
    assert rz.stats()["transient_retries"] == rz.MAX_TRANSIENT_RETRIES


def test_transient_recovery_no_demotion():
    calls = {"n": 0}

    def run(backend, tile):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("UNAVAILABLE: preempted")
        return backend

    assert rz.dispatch(run, _ctx(), backend="pallas") == "pallas"
    assert rz.stats()["backend_demotions"] == 0


def test_programming_error_propagates_on_every_rung():
    def run(backend, tile):
        raise ValueError("keys must be rank-1")

    with pytest.raises(ValueError, match="rank-1"):
        rz.dispatch(run, _ctx(), backend="pallas")
    assert rz.stats()["degradations"] == 0


def test_reference_failure_propagates():
    def run(backend, tile):
        raise RuntimeError("Mosaic lowering failed everywhere")

    with pytest.raises(RuntimeError):
        rz.dispatch(run, _ctx(), backend="reference")


# ---------------------------------------------------------------------------
# Circuit breaker + persistent quarantine
# ---------------------------------------------------------------------------

def _always_lowering(backend, tile):
    if backend == "pallas":
        raise RuntimeError("Mosaic lowering failed: unsupported primitive")
    return backend


def test_breaker_trips_after_threshold_then_skips_statically():
    ctx = _ctx()
    for i in range(rz.BREAKER_THRESHOLD):
        assert rz.dispatch(_always_lowering, ctx, backend="pallas") \
            == "pallas-interpret"
    s = rz.stats()
    assert s["breaker_trips"] == 1 and s["quarantine_skips"] == 0

    attempts = []

    def spy(backend, tile):
        attempts.append(backend)
        return _always_lowering(backend, tile)

    assert rz.dispatch(spy, ctx, backend="pallas") == "pallas-interpret"
    assert attempts == ["pallas-interpret"]        # pallas never attempted
    assert rz.stats()["quarantine_skips"] == 1


def test_breaker_keys_are_per_plan_class():
    for i in range(rz.BREAKER_THRESHOLD):
        rz.dispatch(_always_lowering, _ctx(), backend="pallas")
    other = _ctx(shape=(2 * N,))
    key_hit = rz.class_key(_ctx().plan_class(), "pallas")
    key_other = rz.class_key(other.plan_class(), "pallas")
    assert rz.is_quarantined(key_hit) and not rz.is_quarantined(key_other)


def test_quarantine_survives_clear_tile_cache_roundtrip(iso):
    """The acceptance round-trip: plain clear_tile_cache() drops only the
    in-memory view — the disk sidecar rehydrates the quarantine like a
    fresh process against a warm cache file; disk=True deletes it."""
    ctx = _ctx()
    for i in range(rz.BREAKER_THRESHOLD):
        rz.dispatch(_always_lowering, ctx, backend="pallas")
    key = rz.class_key(ctx.plan_class(), "pallas")
    assert rz.is_quarantined(key)
    path = rz.quarantine_path()
    assert path.exists() and str(path).startswith(str(iso))
    raw = json.loads(path.read_text())
    assert raw["version"] == rz.SCHEMA_VERSION and key in raw["entries"]

    clear_tile_cache()                    # memory dropped, disk kept
    assert not rz.breaker_strikes()
    assert rz.is_quarantined(key)         # rehydrated from disk
    assert key in rz.quarantine_snapshot()

    clear_tile_cache(disk=True)           # sidecar deleted too
    assert not rz.is_quarantined(key)
    assert not path.exists()


def test_quarantine_unwritable_dir_degrades_to_memory():
    set_autotune(cache_dir="/proc/definitely/not/writable")
    at._LOADED = None
    rz.drop_loaded()
    rz.quarantine("some|key", "reason")   # must not raise
    assert rz.is_quarantined("some|key")


# ---------------------------------------------------------------------------
# Strict mode
# ---------------------------------------------------------------------------

def test_strict_reraises_original():
    ops.set_strict(True)
    boom = RuntimeError("Mosaic lowering failed: unsupported primitive")

    def run(backend, tile):
        raise boom

    with pytest.raises(RuntimeError) as ei:
        rz.dispatch(run, _ctx(), backend="pallas")
    assert ei.value is boom               # the ORIGINAL, unwrapped
    assert rz.stats()["degradations"] == 0


def test_strict_env_var(monkeypatch):
    monkeypatch.setenv("REPRO_STRICT", "1")
    rz.set_strict(None)                   # defer to the environment
    assert rz.strict()

    def run(backend, tile):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of memory")

    with pytest.raises(RuntimeError):
        rz.dispatch(run, _ctx(), backend="pallas", resolved_tile=lambda b: 2048)


def test_strict_facade_reraises_injected_fault():
    spec, keys = _spec(), _keys()
    ops.multisplit(keys, spec, backend="vmap")       # warm the plan cache
    ops.set_strict(True)
    inj = FaultInjector(dispatch_rate=0.999999, seed=0)
    rz.set_fault_injector(inj)
    with pytest.raises(RuntimeError, match="injected dispatch fault"):
        ops.multisplit(keys, spec, backend="vmap")
    assert inj.dispatch_injected == 1


# ---------------------------------------------------------------------------
# Runtime verification
# ---------------------------------------------------------------------------

def test_verify_level1_catches_count_tampering():
    spec, keys = _spec(), _keys()
    good = ops.multisplit(keys, spec, backend="vmap")
    rz.verify_result(good, keys=keys, spec=spec, n=N, level=2)   # clean passes
    bad_counts = np.asarray(good.bucket_counts).copy()
    bad_counts[0] += 1
    with pytest.raises(rz.KernelResultError, match="conservation"):
        rz.verify_result(good._replace(bucket_counts=jnp.asarray(bad_counts)),
                         keys=keys, spec=spec, n=N, level=1)
    bad_starts = np.asarray(good.bucket_starts).copy()
    bad_starts[-1] -= 1
    with pytest.raises(rz.KernelResultError, match="monotonicity"):
        rz.verify_result(good._replace(bucket_starts=jnp.asarray(bad_starts)),
                         keys=keys, spec=spec, n=N, level=1)


def test_verify_level2_catches_key_and_perm_tampering():
    spec, keys = _spec(), _keys()
    good = ops.multisplit(keys, spec, backend="vmap")
    swapped = np.asarray(good.keys).copy()
    swapped[[0, -1]] = swapped[[-1, 0]]              # breaks bucket order
    with pytest.raises(rz.KernelResultError):
        rz.verify_result(good._replace(keys=jnp.asarray(swapped)),
                         keys=keys, spec=spec, n=N, level=2)
    bad_perm = np.asarray(good.permutation).copy()
    bad_perm[0] = bad_perm[1]                        # no longer a permutation
    with pytest.raises(rz.KernelResultError, match="permutation"):
        rz.verify_result(good._replace(permutation=jnp.asarray(bad_perm)),
                         keys=keys, spec=spec, n=N, level=2)


def test_verify_segmented_segment_local_invariants():
    spec, keys = _spec(), _keys()
    seg = jnp.asarray([0, 100, 700], jnp.int32)
    good = ops.segmented_multisplit(keys, spec, seg, backend="vmap")
    rz.verify_result(good, keys=keys, spec=spec, n=N, segment_starts=seg,
                     level=2)
    bad = np.asarray(good.bucket_counts).copy()
    bad[1, 0] += 1                                   # breaks one segment's sum
    with pytest.raises(rz.KernelResultError, match="segment"):
        rz.verify_result(good._replace(bucket_counts=jnp.asarray(bad)),
                         keys=keys, spec=spec, n=N, segment_starts=seg, level=1)


def test_verify2_recovers_corrupted_backend_via_reference(monkeypatch):
    """The acceptance scenario: a lying backend (monkeypatched to corrupt
    its output) is caught at REPRO_VERIFY=2, the call transparently
    returns the reference answer, and a structured repro report exists."""
    spec, keys = _spec(), _keys()
    want = ops.multisplit(keys, spec, backend="reference")
    real_flat_op = ops._flat_op

    def corrupting_flat_op(spec_, n_, method_, backend_, tile_, mode_, family_):
        inner = real_flat_op(spec_, n_, method_, backend_, tile_, mode_, family_)
        if backend_ == "reference":
            return inner

        def corrupted(k):
            r = inner(k)
            return r._replace(keys=r.keys[::-1])     # silent wrong answer
        return corrupted

    monkeypatch.setattr(ops, "_flat_op", corrupting_flat_op)
    ops.set_verify(2)
    got = ops.multisplit(keys, spec, backend="vmap")
    _assert_bitwise(got, want)                       # healed via reference
    s = rz.stats()
    assert s["verify_mismatches"] == 1 and s["reference_reruns"] == 1
    report = rz.last_report()
    assert report is not None
    assert report["backend"] == "vmap" and report["shape"] == (N,)
    assert report["spec"] == getattr(spec, "name", type(spec).__name__)
    assert report["num_buckets"] == M


def test_verify_strict_raises_instead_of_recovering():
    ops.set_strict(True)
    ops.set_verify(2)

    def run(backend, tile):
        spec, keys = _spec(), _keys()
        r = ops.multisplit(keys, spec, backend="reference")
        return r._replace(keys=r.keys[::-1])

    def verifier(result, backend):
        spec, keys = _spec(), _keys()
        rz.verify_result(result, keys=keys, spec=spec, n=N, backend=backend)

    with pytest.raises(rz.KernelResultError):
        rz.dispatch(run, _ctx(), backend="vmap", verifier=verifier)


def test_verify_routing_invariants():
    from repro.models.moe import route_tokens_segmented

    ids = jnp.asarray(np.random.RandomState(0).randint(0, 4, 64, dtype=np.int64)
                      .astype(np.int32))
    starts = jnp.asarray([0, 16, 48], jnp.int32)
    out = route_tokens_segmented(ids, starts, 4, 8, backend="vmap")
    rz.verify_routing(out, ids, starts, 4, 8, level=2)           # clean passes
    slot, keep, counts = out
    bad_counts = np.asarray(counts).copy()
    bad_counts[0, 0] += 1
    with pytest.raises(rz.KernelResultError, match="conservation"):
        rz.verify_routing((slot, keep, jnp.asarray(bad_counts)), ids, starts,
                          4, 8, level=1)
    bad_keep = np.asarray(keep).copy()
    flip = int(np.flatnonzero(bad_keep)[0])
    bad_keep[flip] = 0
    with pytest.raises(rz.KernelResultError):
        rz.verify_routing((slot, jnp.asarray(bad_keep), counts), ids, starts,
                          4, 8, level=2)


def test_set_verify_validation_and_env(monkeypatch):
    with pytest.raises(ValueError):
        ops.set_verify(3)
    ops.set_verify(2)
    assert rz.verify_level() == 2
    ops.set_verify(None)
    monkeypatch.setenv("REPRO_VERIFY", "2")
    assert rz.verify_level() == 2
    monkeypatch.setenv("REPRO_VERIFY", "true")
    assert rz.verify_level() == 1
    monkeypatch.setenv("REPRO_VERIFY", "garbage")
    assert rz.verify_level() == 0


# ---------------------------------------------------------------------------
# Registry capability summary (tentpole observability)
# ---------------------------------------------------------------------------

def test_capability_summary_exposes_resilience():
    from repro.core.pipeline.registry import capability_summary

    ops.set_verify(1)
    s = capability_summary()
    assert set(s["backends"]) == set(BACKENDS)
    assert s["backends"]["pallas"]["demotes_to"] == "pallas-interpret"
    assert s["backends"]["reference"]["demotes_to"] is None
    r = s["resilience"]
    assert r["verify"] == 1 and r["strict"] is False
    assert tuple(r["demotion_order"]) == rz.DEMOTION_ORDER
    assert r["breaker_threshold"] == rz.BREAKER_THRESHOLD
    assert set(r["counters"]) == set(rz._COUNTER_KEYS)


# ---------------------------------------------------------------------------
# S1: REPRO_INTERPRET unrecognized-value warning (once per value)
# ---------------------------------------------------------------------------

def test_interpret_env_unrecognized_warns_once(monkeypatch):
    monkeypatch.setenv("REPRO_INTERPRET", "ture")    # the classic typo
    monkeypatch.setattr(kops, "_WARNED_INTERPRET", set())
    with pytest.warns(RuntimeWarning, match="unrecognized REPRO_INTERPRET"):
        assert kops.resolve_interpret(True) is True  # treated as unset, no TPU
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        kops.resolve_interpret(True)                 # same value: silent
    assert not caught
    monkeypatch.setenv("REPRO_INTERPRET", "yse")     # NEW typo warns again
    with pytest.warns(RuntimeWarning):
        kops.resolve_interpret(True)


def test_interpret_env_recognized_values_silent(monkeypatch):
    monkeypatch.setattr(kops, "_WARNED_INTERPRET", set())
    for val, expect in (("1", True), ("true", True), ("0", False),
                        ("no", False), ("", None)):
        monkeypatch.setenv("REPRO_INTERPRET", val)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            got = kops.resolve_interpret(True)
        assert not caught, val
        if expect is not None:
            assert got is expect


# ---------------------------------------------------------------------------
# S2: supervisor — seeded capped backoff + taxonomy-aware retry skip
# ---------------------------------------------------------------------------

def _toy_step(state, batch):
    return {"w": state["w"] + batch}, {"loss": jnp.asarray(0.0)}


def _sup(tmp_path, *, step=None, faults=None, sleeps=None, **cfg_kw):
    cfg = dict(total_steps=4, checkpoint_every=2, checkpoint_dir=str(tmp_path),
               max_retries_per_step=2, max_restores=2, log_every=100)
    cfg.update(cfg_kw)
    return Supervisor(
        step or _toy_step, lambda s: jnp.asarray(1.0), TrainLoopConfig(**cfg),
        fault_injector=faults,
        sleep_fn=(sleeps.append if sleeps is not None else (lambda dt: None)),
    )


def test_backoff_between_retries_seeded_and_capped(tmp_path):
    sleeps = []
    sup = _sup(tmp_path, faults=FaultInjector(fail_at={1: 2}), sleeps=sleeps)
    sup.run({"w": jnp.asarray(0.0)})
    cfg = sup.cfg
    assert len(sleeps) == 2                          # one per failed attempt
    for i, dt in enumerate(sleeps):
        hi = min(cfg.retry_backoff_cap, cfg.retry_backoff_base * 2 ** i) * 1.5
        assert 0.0 < dt <= hi
    # deterministic: the same seed replays the same backoff schedule
    sleeps2 = []
    sup2 = _sup(tmp_path / "b", faults=FaultInjector(fail_at={1: 2}),
                sleeps=sleeps2)
    sup2.run({"w": jnp.asarray(0.0)})
    assert sleeps2 == sleeps


def test_backoff_never_exceeds_cap(tmp_path):
    sup = _sup(tmp_path, max_retries_per_step=8)
    for attempt in range(32):
        assert 0.0 < sup._backoff(attempt) <= sup.cfg.retry_backoff_cap * 1.5


def test_persistent_lowering_skips_straight_to_restore(tmp_path):
    """A Mosaic-style persistent failure must not burn the retry budget:
    no backoff sleeps, one attempt per restore cycle."""
    def bad_step(state, batch):
        raise NotImplementedError("unsupported primitive in kernel body")

    sleeps = []
    sup = _sup(tmp_path, step=bad_step, sleeps=sleeps, max_restores=1)
    with pytest.raises(RuntimeError, match="budgets exhausted"):
        sup.run({"w": jnp.asarray(0.0)})
    assert sleeps == []                              # retries were skipped
    assert sup.stats["retries"] == sup.stats["restores"]  # 1 attempt per cycle


def test_transient_fault_still_uses_retry_budget(tmp_path):
    """Generic/transient step failures keep the pre-§17 retry behavior."""
    sleeps = []
    sup = _sup(tmp_path, faults=FaultInjector(fail_at={2: 1}), sleeps=sleeps)
    sup.run({"w": jnp.asarray(0.0)})
    assert sup.stats["retries"] == 1 and sup.stats["restores"] == 0
    assert len(sleeps) == 1
