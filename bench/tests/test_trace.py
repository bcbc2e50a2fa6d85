"""The reduction from a profiler trace to busy time, collective time and
labelled idle gaps: on small hand-made traces, and on a trace recorded on a
v5e (three key-value multisplits of 2^14 pairs, ``bench/tests/data``)."""

import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace as tr

RECORDED = Path(__file__).parent / "data" / "ms_kv_small.xplane.pb.gz"


def _plane(pid, name, lines):
    """A plane; ``lines`` maps a line name to events (name, start_ns, end_ns)."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    out = []
    for lid, (line, evs) in enumerate(lines.items()):
        body = " ".join(f"events {{ metadata_id: {meta[n]} offset_ps: {s * 1000} "
                        f"duration_ps: {(e - s) * 1000} }}" for n, s, e in evs)
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 {body} }}')
    md = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: '{n}' }} }}"
                  for n, i in meta.items())
    return f'planes {{ id: {pid} name: "{name}" {" ".join(out)} {md} }}'


A2A = "%all-to-all.1 = u32[4,8]{1,0} all-to-all(u32[4,8]{1,0} %x), dimensions={0}"
AG = ("%all-gather-start.2 = (u32[4]{0}, u32[16]{0}) all-gather-start(u32[4]{0} %c),"
      " dimensions={0}")
FUSION = "%fusion.1 = u32[8]{0:T(1024)} fusion(u32[8]{0} %p), kind=kLoop"


def _trace():
    """Two chips and two calls: call 1 [0, 100), call 2 [120, 200) ns."""
    host = _plane(9, "/host:CPU", {"python3": [
        ("bench.call", 0, 100), ("bench.dispatch", 0, 30), ("bench.sync", 30, 100),
        ("bench.call", 120, 200), ("bench.dispatch", 120, 125),
        ("bench.sync", 125, 200), ("PjitFunction(x)", 0, 200)]})
    dev0 = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_a(123)", 40, 90), ("jit_a(123)", 130, 190)],
        "XLA Ops": [(FUSION, 40, 80), (A2A, 70, 90), (FUSION, 130, 190)]})
    dev1 = _plane(2, "/device:TPU:1", {
        "XLA Modules": [("jit_a(123)", 50, 90), ("jit_a(123)", 140, 170)],
        "XLA Ops": [(FUSION, 50, 90), (AG, 140, 150)],
        "Async XLA Ops": [(AG, 140, 170)]})
    return tr.Trace.from_profile(ProfileData.from_text_proto(host + dev0 + dev1))


def test_union_and_gaps():
    assert tr.union([(5, 10), (0, 3), (2, 4), (9, 12)], 1, 11) == [(1, 4), (5, 11)]
    assert tr.covered([(0, 10), (5, 15)], 0, 100) == 15
    assert tr.gaps([(2, 4), (6, 8)], 0, 10) == [(0, 2), (4, 6), (8, 10)]


@pytest.mark.parametrize("text,name,opcode", [
    (A2A, "all-to-all.1", "all-to-all"),
    (AG, "all-gather-start.2", "all-gather-start"),
    (FUSION, "fusion.1", "fusion"),
    ("%while.3 = (s32[]{:T(128)}, /*index=5*/u32[96,4096]{1,0}) while((s32[]) %t),"
     " condition=%c", "while.3", "while"),
    ("collective-permute.12", "collective-permute.12", "collective-permute"),
])
def test_parse_hlo(text, name, opcode):
    assert tr.parse_hlo(text) == (name, opcode)


def test_collective_opcodes():
    for op in ("all-gather-start", "all-to-all", "all-reduce", "collective-permute-done",
               "reduce-scatter"):
        assert tr.is_collective(op)
    for op in ("fusion", "all-to-allx", "copy-start", "while"):
        assert not tr.is_collective(op)


def test_window_busy_and_calls():
    t = _trace()
    assert t.window() == (0, 200) and t.window_s() == 200e-9
    assert [c.start for c in t.calls] == [0, 120]
    # chip 0 busy 40..90 and 130..190 = 110 ns; chip 1 50..90 and 140..150 = 50 ns
    assert t.busy_s() == pytest.approx(80e-9)
    assert t.call_busy_s() == pytest.approx([45e-9, 35e-9])


def test_ops_are_named_after_their_program():
    t = _trace()
    assert [o.name for o in t.ops[0]] == ["jit_a/fusion.1", "jit_a/all-to-all.1",
                                          "jit_a/fusion.1"]


def test_collectives_on_the_slowest_chip_with_their_async_spans():
    # chip 0: 20 ns of all-to-all; chip 1: the all-gather's async span, 30 ns
    assert _trace().collective_s_per_call() == pytest.approx(15e-9)


def test_idle_gaps_are_cut_at_spans_and_labelled():
    gaps = [(lbl, round(s * 1e9)) for lbl, s in _trace().idle_gaps()]
    # chip 0 idles [0, 40), [90, 130) and [190, 200), cut where spans open or close
    assert gaps == [("bench.dispatch", 30), ("between calls", 20),
                    ("bench.sync", 10), ("bench.sync", 10), ("bench.sync", 10),
                    ("bench.dispatch", 5), ("bench.sync", 5)]


def test_top_ops_per_call():
    top = dict(_trace().top_ops())
    assert top["jit_a/fusion.1"] == pytest.approx((40 + 60 + 40) / 4 * 1e-9)


def test_top_ops_leave_out_a_loop_whose_body_is_traced():
    loop = "%while.3 = (s32[], u32[8]{0}) while((s32[], u32[8]{0}) %t), body=%b"
    host = _plane(9, "/host:CPU", {"python3": [("bench.call", 0, 100)]})
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_scan(1)", 0, 90)],
        "XLA Ops": [(loop, 0, 90), (FUSION, 10, 40), (FUSION, 50, 80)]})
    t = tr.Trace.from_profile(ProfileData.from_text_proto(host + dev))
    assert dict(t.top_ops()) == {"jit_scan/fusion.1": pytest.approx(60e-9)}
    assert t.busy_s() == pytest.approx(90e-9)


@pytest.fixture(scope="module")
def recorded():
    return tr.Trace.from_profile(
        ProfileData.from_serialized_xspace(gzip.decompress(RECORDED.read_bytes())))


def test_recorded_trace_has_calls_ops_and_programs(recorded):
    assert len(recorded.calls) == 3
    assert list(recorded.ops) == [0] and len(recorded.ops[0]) > 1000
    assert all(not o.name.startswith("?/") for o in recorded.ops[0])
    assert {o.opcode for o in recorded.ops[0]} >= {"fusion", "copy", "reshape"}
    assert recorded.collective_s_per_call() is None          # one chip


def test_recorded_trace_busy_lies_inside_its_calls(recorded):
    busy = recorded.call_busy_s()
    for call, b in zip(recorded.calls, busy):
        assert 0 < b < (call.end - call.start) / 1e9
    assert 0 < recorded.busy_s() < recorded.window_s()
    assert sum(busy) == pytest.approx(recorded.busy_s(), rel=1e-6)


def test_recorded_trace_gaps_and_top_ops(recorded):
    gaps = recorded.idle_gaps()
    assert len(gaps) == 10
    assert {lbl for lbl, _ in gaps} <= {"bench.dispatch", "bench.sync", "between calls"}
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    top = recorded.top_ops()
    assert len(top) == 10 and top[0][1] >= top[-1][1] > 0


def test_per_layer_readers_on_the_recorded_trace(recorded):
    from bench import harness
    from bench.tests.cases import small_cell

    cell = small_cell("ms_kv_m256", 1 << 14)       # the recorded run's size
    run = harness.Run(cell, {"hbm_bytes_per_s": 819e9}, 1.0, [0.1] * 3, 3, 0.4,
                      recorded)
    read = {m["name"]: harness.load_module(
        harness.BENCH_DIR / "metrics" / f"{m['name']}.py").read(run)
        for m in cell.metrics["per_layer"]}
    assert "collective_ms" not in read                   # a one-chip cell
    assert 0 < read["hbm_roofline_pct"] <= 100
    assert 0 < read["device_idle_pct"] < 100
    assert read["host_gap_ms"] > 0
