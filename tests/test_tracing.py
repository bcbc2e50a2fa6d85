"""repro.runtime.tracing: the program's host spans and the compile-path
counts on them, read back from a profiler trace of eager calls on the CPU."""

import glob
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import ops
from repro.core.pipeline import registry
from repro.runtime import tracing

N, M = 1 << 14, 256
STAGE_STATS = {"backend", "family", "tile", "tiles", "map_batch"}
MULTISPLIT_STAGES = ["repro.stage.layout", "repro.stage.prescan", "repro.stage.scan",
                     "repro.stage.postscan", "repro.stage.scatter"]


def _inputs():
    keys = jax.random.randint(jax.random.PRNGKey(7), (N,), 0, 2**31 - 1).astype(jnp.uint32)
    return keys, jnp.arange(N, dtype=jnp.uint32), ops.delta_buckets(M, 2**31)


class _Profiled:
    """Runs ``fn`` under the profiler as the benchmark does (no Python
    tracer); ``spans`` are then the ``repro.`` spans as a tree of
    ``(name, stats, children)``, in order."""

    def __init__(self, fn):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as log_dir:
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            try:
                self.result = fn()
                jax.block_until_ready(self.result)
            finally:
                jax.profiler.stop_trace()
            path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True)[0]
            planes = ProfileData.from_file(path).planes
            events = [(int(e.start_ns), int(e.end_ns), e.name, dict(e.stats))
                      for plane in planes for line in plane.lines
                      for e in line.events if e.name.startswith("repro.")]
        root = ("", {}, [])
        stack = [(float("inf"), root)]
        for start, end, name, stats in sorted(events, key=lambda e: (e[0], -e[1])):
            while stack[-1][0] <= start:
                stack.pop()
            node = (name, stats, [])
            stack[-1][1][2].append(node)
            stack.append((end, node))
        self.spans = root[2]


def _names(nodes):
    return [n for n, _, _ in nodes]


def _all(nodes):
    for node in nodes:
        yield node
        yield from _all(node[2])


@pytest.fixture(scope="module")
def profiled():
    """Two eager key-value multisplits (the first after the caches are
    cleared, so that it lowers its programs) and one radix sort."""
    keys, values, spec = _inputs()
    jax.clear_caches()
    first = _Profiled(lambda: ops.multisplit_key_value(keys, values, spec))
    second = _Profiled(lambda: ops.multisplit_key_value(keys, values, spec))
    sort = _Profiled(lambda: ops.radix_sort(keys))
    return first, second, sort


def test_multisplit_spans_nest_as_the_call_runs(profiled):
    first, second, _ = profiled
    for run in (first, second):
        (op,) = run.spans
        name, stats, children = op
        assert name == "repro.op"
        assert stats["op"] == "multisplit_key_value"
        assert (stats["n"], stats["m"], stats["key_value"]) == (N, M, 1)
        assert (stats["backend"], stats["auto"]) == ("vmap", 1)
        (dispatch,) = children
        assert dispatch[0] == "repro.dispatch"
        assert dispatch[1]["backend"] == "vmap" and dispatch[1]["attempt"] == 0
        assert _names(dispatch[2]) == ["repro.plan", "repro.trace"] + MULTISPLIT_STAGES
        for _, st, _ in dispatch[2][2:]:
            assert STAGE_STATS <= set(st) and st["backend"] == "vmap"
            assert st["tiles"] * st["tile"] >= N and st["map_batch"] == 0
    assert second.spans[0][2][0][2][0][1]["hit"] == 1        # the cached op


def test_radix_sort_spans_one_pass_per_digit(profiled):
    _, _, sort = profiled
    (op,) = sort.spans
    assert op[0] == "repro.op" and op[1]["op"] == "radix_sort"
    assert (op[1]["n"], op[1]["m"], op[1]["key_value"]) == (N, 256, 0)
    assert (op[1]["backend"], op[1]["auto"]) == ("vmap", 1)
    passes = op[2][1:]
    assert _names(op[2]) == ["repro.stage.layout"] + ["repro.sort.pass"] * 4
    assert [(p[1]["shift"], p[1]["bits"]) for p in passes] == [
        (0, 8), (8, 8), (16, 8), (24, 8)]
    for p in passes:
        assert _names(p[2]) == MULTISPLIT_STAGES[1:]
    np.testing.assert_array_equal(np.asarray(sort.result[0]),
                                  np.sort(np.asarray(_inputs()[0])))


def test_a_first_call_lowers_and_a_cached_one_does_not(profiled):
    first, second, _ = profiled
    lowered = sum(st.get("lowerings", 0) for _, st, _ in _all(first.spans))
    assert lowered >= 1
    assert sum(st.get("compile_ms", 0) for _, st, _ in _all(first.spans)) > 0
    for _, st, _ in _all(second.spans):
        assert st.get("lowerings", 0) == 0 and st.get("compiles", 0) == 0


def test_stage_spans_are_open_while_their_stage_runs(profiled):
    """The eager call traces the plan before it runs it; the programs a
    stage lowers as it runs are counted on that stage's span."""
    first, _, _ = profiled
    stages = [st for n, st, _ in _all(first.spans) if n.startswith("repro.stage.")]
    assert len(stages) == len(MULTISPLIT_STAGES)
    assert sum(st.get("lowerings", 0) for st in stages) >= 1
    (dispatch,) = first.spans[0][2]
    trace = dispatch[2][1]
    assert trace[0] == "repro.trace" and not trace[2]
    assert trace[1].get("traces", 0) >= 1              # the plan, traced


def _staged(x):
    with tracing.span("repro.test", k=3):
        y = x * 2
    return y + 1


def test_run_staged_opens_the_spans_of_the_function_it_runs():
    x = jnp.arange(8)
    run = _Profiled(lambda: tracing.run_staged(_staged, x))
    assert [(n, st.get("k")) for n, st, _ in run.spans] == [
        ("repro.trace", None), ("repro.test", 3)]
    np.testing.assert_array_equal(np.asarray(run.result), np.arange(8) * 2 + 1)
    np.testing.assert_array_equal(np.asarray(tracing.run_staged(_staged, x)),
                                  np.arange(8) * 2 + 1)
    assert not tracing._local.marks and not getattr(tracing._local, "stack", None)


def test_run_staged_closes_its_spans_when_a_stage_fails(tmp_path):
    def fails(x):
        with tracing.span("repro.test"):
            y = jax.pure_callback(_raise, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y

    jax.profiler.start_trace(str(tmp_path))
    try:
        with pytest.raises(Exception, match="stage failed"):
            jax.block_until_ready(tracing.run_staged(fails, jnp.arange(4)))
    finally:
        jax.profiler.stop_trace()
    assert not tracing._local.marks and not tracing._local.stack


def _raise(x):
    raise ValueError("stage failed")


def test_span_is_the_shared_no_op_without_a_profiler():
    assert not tracing.enabled()
    sp = tracing.span("repro.op", op="x", n=1)
    assert sp is tracing.OFF and not sp
    with sp as inner:
        inner.set(hit=True)
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(5))       # lowers and compiles
    assert not getattr(tracing._local, "stack", None)


def test_counts_go_to_the_innermost_open_span(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing.span("repro.outer", skipped=None) as outer:
            assert outer
            with tracing.span("repro.inner") as inner:
                tracing._on_event("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.002)
                tracing._on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
                tracing._on_event("/jax/some/other/event", 1.0)
            tracing._on_event("/jax/core/compile/backend_compile_duration", 0.001)
    finally:
        jax.profiler.stop_trace()
    assert inner.counts == {"lowerings": 1, "compile_ms": pytest.approx(2.0),
                            "cache_reads": 1}
    assert outer.counts == {"compiles": 1, "compile_ms": pytest.approx(1.0)}
    assert not tracing._local.stack


@pytest.mark.parametrize("entry", ["multisplit_key_value", "radix_sort"])
def test_spans_change_no_jaxpr(entry, tmp_path):
    keys, values, spec = _inputs()
    if entry == "radix_sort":
        fn, args = jax.jit(ops.radix_sort), (keys, values)
    else:
        fn, args = jax.jit(ops.multisplit_key_value), (keys, values, spec)
    off = str(jax.make_jaxpr(fn)(*args))
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert tracing.enabled()
        on = str(jax.make_jaxpr(fn)(*args))
    finally:
        jax.profiler.stop_trace()
    assert on == off


def test_eager_calls_run_the_plan_and_match_the_traced_op():
    keys, values, spec = _inputs()
    eager = ops.multisplit_key_value(keys, values, spec)
    traced = jax.jit(ops.multisplit_key_value)(keys, values, spec)
    for a, b in zip(eager, traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    flat = ops.multisplit(keys, spec, mode="positions_only")
    np.testing.assert_array_equal(np.asarray(flat.permutation),
                                  np.asarray(eager.permutation))


@pytest.mark.parametrize("jitted", [False, True])
def test_tile_stages_in_chunks_match_the_reference(jitted, monkeypatch):
    """Past the working-set bound the tile stages run as ``lax.map`` over
    chunks of tiles, eagerly and under ``jit``. Both give the reference's
    result, and the stage spans carry the batch."""
    keys, values, spec = _inputs()
    want = ops.multisplit_key_value(keys, values, spec, backend="reference")
    monkeypatch.setattr(registry, "_VMAP_WORKSET_BYTES", 1 << 20)
    if jitted:
        got = jax.jit(lambda k, v: ops.multisplit_key_value(k, v, spec))(keys, values)
    else:
        run = _Profiled(lambda: ops.multisplit_key_value(keys, values, spec))
        got = run.result
        stages = {n: st for n, st, _ in _all(run.spans) if n.startswith("repro.stage.")}
        assert stages["repro.stage.prescan"]["map_batch"] == 1
        assert stages["repro.stage.postscan"]["map_batch"] == 1
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# The exchange of the sharded multisplit, on four CPU devices
# ---------------------------------------------------------------------------

EXCHANGE_BODY = """
    import json, os, sys
    sys.path.insert(0, {tests!r})
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.core import distributed
    from repro.core.identifiers import delta_buckets
    from repro.core.multisplit import multisplit_ref
    from repro.runtime import tracing
    from test_tracing import _Profiled, _all

    D, n_shard, m = 4, 1024, 16
    mesh = jax.make_mesh((D,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
    keys = jax.random.bits(jax.random.key(1), (D * n_shard,), jnp.uint32)
    vals = jnp.arange(D * n_shard, dtype=jnp.uint32)
    spec = delta_buckets(m, 2**32)

    def sharded():
        return jax.jit(distributed.make_multisplit_sharded(spec, mesh, "x", key_value=True))

    def bucket_sharded():
        return jax.jit(jax.shard_map(
            lambda k, v: distributed.multisplit_bucket_sharded(
                k, spec, v, axis_name="x", capacity=2 * n_shard),
            mesh=mesh, in_specs=(P("x"), P("x")),
            out_specs=distributed.BucketShardedResult(P("x"), P("x"), P("x"), P("x"), P()),
            check_vma=False))

    made = []
    init = tracing._Span.__init__
    tracing._Span.__init__ = lambda self, name, stats: (made.append(name), init(self, name, stats))[1]
    for build in (sharded, bucket_sharded):
        jax.block_until_ready(build()(keys, vals))     # traced with no collector
    print(json.dumps({{"off": made}}))
    want = multisplit_ref(keys, spec, vals)
    for build in (sharded, bucket_sharded):
        run = _Profiled(lambda: build()(keys, vals))
        spans = [(n, st) for n, st, _ in _all(run.spans) if n == "repro.stage.exchange"]
        exact = all(np.array_equal(np.asarray(getattr(run.result, f)), np.asarray(getattr(want, f)))
                    for f in ("keys", "values"))
        print(json.dumps({{"entry": build.__name__, "spans": spans, "exact": exact}}))
"""


@pytest.fixture(scope="module")
def exchange_spans():
    from test_distributed import _run_with_devices

    tests = os.path.dirname(os.path.abspath(__file__))
    out = _run_with_devices(4, EXCHANGE_BODY.format(tests=tests))
    return [json.loads(x) for x in out.splitlines() if x.startswith("{")]


def test_exchange_records_nothing_without_a_collector(exchange_spans):
    assert exchange_spans[0] == {"off": []}


@pytest.mark.parametrize("entry, exact", [("sharded", True), ("bucket_sharded", False)])
def test_exchange_span_and_its_byte_counters(exchange_spans, entry, exact):
    """One ``repro.stage.exchange`` span a trace, with the shipped and
    payload bytes of the dense transport in closed form: per chip and per
    array (keys, values) a (D, n_shard) all-to-all operand of 4-byte
    elements, one run per peer padded to a shard and no positions. The
    profiled call gives the reference's keys and values where the entry's
    result is the exact global multisplit, and not where it is padded to a
    capacity per chip."""
    d, n_shard, m = 4, 1024, 16
    (row,) = [r for r in exchange_spans[1:] if r["entry"] == entry]
    (span,) = row["spans"]
    name, stats = span
    assert {k: stats[k] for k in ("transport", "chips", "n_shard", "m", "backend")} == {
        "transport": "dense", "chips": d, "n_shard": n_shard, "m": m, "backend": "vmap"}
    payload = 2 * n_shard * 4
    assert stats["exchange_payload_bytes"] == payload
    assert stats["exchange_shipped_bytes"] == d * payload
    assert row["exact"] is exact
