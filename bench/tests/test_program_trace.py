"""The program's spans and the device programs they launched: on a small
hand-made trace, on the v5e recording without the program's spans
(``ms_kv_small``) and on one with them (``ms_kv_small_spans``: three
key-value multisplits of 2^14 pairs, recorded with ``bench.tests.record``)."""

import gzip
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import harness
from bench import program_trace as pt
from bench import trace as tr
from bench.tests.cases import small_cell

DATA = Path(__file__).parent / "data"
FUSION = "%fusion.1 = u32[8]{0:T(1024)} fusion(u32[8]{0} %p), kind=kLoop"
LINK = "PJRT_LoadedExecutable_Execute linkage"
EXECUTE = "PJRT_LoadedExecutable_Execute"
NEW_METRICS = ("tile_stages_ms", "scatter_ms", "host_compile_ms")
OLD_METRICS = ("host_gap_ms", "device_idle_pct", "hbm_roofline_pct")


def _stat(sid, value):
    if isinstance(value, str):
        return f'stats {{ metadata_id: {sid} str_value: "{value}" }}'
    if isinstance(value, float):
        return f"stats {{ metadata_id: {sid} double_value: {value} }}"
    return f"stats {{ metadata_id: {sid} int64_value: {value} }}"


def _plane(pid, name, lines):
    """A plane; ``lines`` maps a line name to events
    ``(name, start_ns, end_ns[, stats])``."""
    names = sorted({e[0] for evs in lines.values() for e in evs})
    meta = {n: i + 1 for i, n in enumerate(names)}
    keys = sorted({k for evs in lines.values() for e in evs if len(e) > 3
                   for k in e[3]})
    smeta = {k: i + 1 for i, k in enumerate(keys)}
    out = []
    for lid, (line, evs) in enumerate(lines.items()):
        body = " ".join(
            f"events {{ metadata_id: {meta[e[0]]} offset_ps: {e[1] * 1000} "
            f"duration_ps: {(e[2] - e[1]) * 1000} "
            + " ".join(_stat(smeta[k], v) for k, v in (e[3] if len(e) > 3 else {}).items())
            + " }" for e in evs)
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 {body} }}')
    md = " ".join(f"event_metadata {{ key: {i} value {{ id: {i} name: '{n}' }} }}"
                  for n, i in meta.items())
    sm = " ".join(f"stat_metadata {{ key: {i} value {{ id: {i} name: '{k}' }} }}"
                  for k, i in smeta.items())
    return f'planes {{ id: {pid} name: "{name}" {" ".join(out)} {md} {sm} }}'


def _trace():
    """One chip, two calls: [0, 100) and [120, 200) ns. Call 1 launches a
    run from its prescan span and one from its scatter span, while JAX
    lowers a program; call 2 one from its prescan and one from ``repro.op``
    with no stage open. Runs 1, 3 and 4 are enqueued on the thread that
    launched them, run 2 through a task thread; an event with run 1's id
    under another flow type must not be taken for its link."""
    host = _plane(9, "/host:CPU", {
        "python3": [
            ("bench.call", 0, 100), ("bench.dispatch", 0, 40), ("bench.sync", 40, 100),
            ("repro.op", 2, 38, {"op": "multisplit_key_value", "compile_ms": 1.0}),
            ("repro.stage.prescan", 5, 15, {"lowerings": 1, "compile_ms": 2.5}),
            ("repro.stage.scatter", 20, 30),
            ("lower_sharding_computation", 21, 25),
            (LINK, 8, 9, {"_pt": 14, "_p": 1}), (LINK, 22, 23, {"_pt": 14, "_p": 2}),
            ("bench.call", 120, 200), ("bench.dispatch", 120, 150),
            ("bench.sync", 150, 200), ("repro.op", 121, 149),
            ("repro.stage.prescan", 125, 135),
            (LINK, 126, 127, {"_pt": 14, "_p": 3}), (LINK, 140, 141, {"_pt": 14, "_p": 4})],
        "main": [
            (EXECUTE, 8, 12, {"_ct": 14, "_c": 1}),
            ("DoEnqueueProgram", 9, 10, {"_pt": 12, "_p": 101}),
            (EXECUTE, 22, 26, {"_ct": 14, "_c": 2}),
            ("tpu::System::Execute", 23, 25, {"_pt": 7, "_p": 7}),
            (EXECUTE, 126, 128, {"_ct": 14, "_c": 3}),
            ("DoEnqueueProgram", 126, 127, {"_pt": 12, "_p": 103}),
            (EXECUTE, 140, 142, {"_ct": 14, "_c": 4}),
            ("DoEnqueueProgram", 140, 141, {"_pt": 12, "_p": 104})],
        "pjrt-tpu-tasks": [
            ("tpu::System::Execute=>IssueSequencedEvent", 27, 29, {"_ct": 7, "_c": 7}),
            ("DoEnqueueProgram", 27, 28, {"_pt": 12, "_p": 102}),
            ("tpu::System::TransferToDevice", 60, 61, {"_pt": 7, "_p": 1})]})
    dev = _plane(1, "/device:TPU:0", {
        "XLA Modules": [("jit_scan(1)", 40, 60, {"_ct": 12, "_c": 101}),
                        ("jit_scatter(2)", 60, 90, {"_ct": 12, "_c": 102}),
                        ("jit_scan(1)", 150, 170, {"_ct": 12, "_c": 103}),
                        ("jit_add(3)", 170, 175, {"_ct": 12, "_c": 104})],
        "XLA Ops": [(FUSION, 40, 60), (FUSION, 60, 90), (FUSION, 150, 170),
                    (FUSION, 170, 175)]})
    return pt.ProgramTrace.from_profile(ProfileData.from_text_proto(host + dev))


def test_runs_are_put_down_to_the_span_that_launched_them():
    t = _trace()
    runs = t.launches[0]
    assert [(r.program, r.launched_at, r.span) for r in runs] == [
        ("jit_scan", 8, "repro.stage.prescan"), ("jit_scatter", 22, "repro.stage.scatter"),
        ("jit_scan", 126, "repro.stage.prescan"), ("jit_add", 140, "repro.op")]
    assert [r.busy_ns for r in runs] == [20, 30, 20, 5]
    assert t.stage_device_s(["repro.stage.prescan"]) == pytest.approx([20e-9, 20e-9])
    assert t.stage_device_s(["repro.stage.scatter"]) == pytest.approx([30e-9, 0.0])
    assert t.device_s_by_span() == pytest.approx({
        "repro.stage.prescan": 20e-9, "repro.stage.scatter": 15e-9, "repro.op": 2.5e-9})


def test_span_stats_and_compile_time_per_call():
    t = _trace()
    assert [sp.name for sp in t.program_spans][:3] == [
        "repro.op", "repro.stage.prescan", "repro.stage.scatter"]
    assert t.program_spans[0].stats == {"op": "multisplit_key_value", "compile_ms": 1.0}
    assert t.program_stat("compile_ms") == [3.5, 0.0]
    assert t.program_stat("lowerings") == [1.0, 0.0]
    assert [sp.name for sp in t.compile_spans] == ["lower_sharding_computation"]


def test_idle_gaps_are_named_by_the_innermost_span_of_any_kind():
    gaps = {(lbl, round(s * 1e9)) for lbl, s in _trace().idle_gaps(k=20)}
    assert ("lower_sharding_computation", 4) in gaps
    assert ("repro.stage.scatter", 5) in gaps and ("repro.stage.prescan", 10) in gaps
    assert ("repro.op", 14) in gaps and ("between calls", 20) in gaps
    assert ("bench.sync", 25) in gaps


def test_new_metrics_on_the_hand_made_trace():
    cell = small_cell("ms_kv_m256", 1 << 14)
    read = _read(cell, _trace(), NEW_METRICS)
    assert read["tile_stages_ms"] == pytest.approx(20e-6)
    assert read["scatter_ms"] == pytest.approx(15e-6)
    assert read["host_compile_ms"] == pytest.approx(1.75)


def _load(name):
    data = ProfileData.from_serialized_xspace(gzip.decompress((DATA / name).read_bytes()))
    return tr.Trace.from_profile(data), pt.ProgramTrace.from_profile(data)


def _read(cell, trace, names):
    run = harness.Run(cell, {"hbm_bytes_per_s": 819e9}, 1.0, [0.1] * 3, 3, 0.4, trace)
    return {n: harness.load_module(harness.BENCH_DIR / "metrics" / f"{n}.py").read(run)
            for n in names}


@pytest.fixture(scope="module")
def old():
    return _load("ms_kv_small.xplane.pb.gz")


@pytest.fixture(scope="module")
def spans():
    return _load("ms_kv_small_spans.xplane.pb.gz")


def test_every_recorded_run_has_a_launch(old):
    _, t = old
    runs = [r for rs in t.launches.values() for r in rs]
    assert len(runs) == 612
    assert all(r.launched_at is not None for r in runs)
    assert all(c.start <= r.launched_at < c.end for r in runs
               for c in [max((c for c in t.calls if c.start <= r.launched_at),
                             key=lambda c: c.start)])


@pytest.mark.parametrize("fixture", ["old", "spans"])
def test_the_reducer_leaves_what_it_extends_as_it_was(fixture, request):
    plain, ext = request.getfixturevalue(fixture)
    cell = small_cell("ms_kv_m256", 1 << 14)
    assert _read(cell, ext, OLD_METRICS) == _read(cell, plain, OLD_METRICS)
    assert ext.top_ops() == plain.top_ops()
    assert ext.busy_s() == plain.busy_s() and ext.call_busy_s() == plain.call_busy_s()
    if fixture == "old":                  # no program spans: the same gaps
        assert ext.idle_gaps() == plain.idle_gaps()


def test_new_metrics_are_absent_without_program_spans(old):
    plain, ext = old
    cell = small_cell("ms_kv_m256", 1 << 14)
    assert _read(cell, plain, NEW_METRICS) == dict.fromkeys(NEW_METRICS)
    assert _read(cell, ext, NEW_METRICS) == dict.fromkeys(NEW_METRICS)


def test_recorded_spans_own_the_device_time(spans):
    _, t = spans
    assert len(t.calls) == 3
    runs = [r for rs in t.launches.values() for r in rs]
    assert runs and all(r.launched_at is not None for r in runs)
    stages = t.stage_device_s(pt.STAGE_SPANS)
    for busy, staged in zip(t.call_busy_s(), stages):
        assert 0.95 * busy <= staged <= busy * (1 + 1e-9)
    names = {lbl for lbl, _ in t.idle_gaps()}
    assert names and all(n.startswith("repro.") or n in pt.COMPILE_SPANS
                         or n == "between calls" for n in names)


def test_new_metrics_on_the_recorded_spans(spans):
    _, t = spans
    cell = small_cell("ms_kv_m256", 1 << 14)
    read = _read(cell, t, NEW_METRICS)
    assert read["tile_stages_ms"] > 0 and read["scatter_ms"] > 0
    busy_ms = sum(t.call_busy_s()) / len(t.calls) * 1e3
    assert read["tile_stages_ms"] + read["scatter_ms"] <= busy_ms * (1 + 1e-9)
    assert read["host_compile_ms"] >= 0
    gap_ms = _read(cell, t, ("host_gap_ms",))["host_gap_ms"]
    assert read["host_compile_ms"] < gap_ms
