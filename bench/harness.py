"""One run of one cell: find it by name, make its inputs, warm it up, drive
it for the window, check what it returned, and reduce the timings and the
trace to the metrics ``BENCHMARK.json`` lists for it.

Nothing here names a cell, a configuration, a traffic mix or a metric. A
cell is an entry of ``BENCHMARK.json``'s ``workloads``; its configuration is
the file that the entry of ``configs`` names; its traffic is
``bench/traffic/<traffic>.json``; its entry point is
``bench/drivers/<driver>.py``, where ``driver`` is a key of the
configuration; each metric is read by ``bench/metrics/<metric>.py``.

A driver module provides ``build(cfg, mesh)``, the timed callable
``call(keys, values)``; ``fetch(result)``, the result as numpy arrays;
``expected(cfg, keys, values)``, the reference's arrays; ``control(cfg,
mesh)``, the reference on the device with one stated guarantee broken; and
may provide ``extra_checks(result, mesh)``, further numbers to compare.

A metric module provides ``read(run)``, which returns a number, or ``None``
where the run holds nothing to read; the metric is then left out.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Callable, Dict, List, Mapping, Optional

import jax
import numpy as np

from bench import trace as trace_mod
from bench.reference import mismatches
from bench import traffic

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# Resilience counters that say a call did not run as asked: a demoted call
# ran another backend, a re-run one ran the reference.
FAILURE_COUNTERS = ("degradations", "backend_demotions", "tile_shrinks",
                    "reference_reruns", "verify_mismatches")
# Events that say a program was compiled, or read from the compile cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class BenchError(Exception):
    """The run cannot give a result: no chip, an unknown name, a bad file."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: Dict[str, Any]
    traffic: Dict[str, Any]
    driver: Any
    metrics: Dict[str, List[Dict[str, Any]]]     # "end_to_end" / "per_layer"
    bench_dir: Path = BENCH_DIR                  # where its metric readers are


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""

    cell: Cell
    peak: Mapping[str, Any]
    setup_s: float
    durations_s: List[float]           # every call of the window, host clock
    completed: int                     # calls that returned a result
    window_s: float                    # first call's start to last call's end
    trace: Optional[trace_mod.Trace]   # with --trace 1


def load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _named(entries, name: str, what: str) -> Dict[str, Any]:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def applies(metric: Mapping[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bench: Mapping[str, Any], name: str, root: Path = ROOT,
                 bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration, traffic, driver and metrics."""
    w = _named(bench["workloads"], name, "workload")
    c = _named(bench["configs"], w["config"], "config")
    try:
        cfg = json.loads((root / c["file"]).read_text())
        mix = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    except (OSError, ValueError) as e:
        raise BenchError(f"cannot read the files of {name!r}: {e}") from e
    driver = load_module(bench_dir / "drivers" / f"{cfg['driver']}.py")
    metrics = {kind: [m for m in bench[kind] if applies(m, name)]
               for kind in ("end_to_end", "per_layer")}
    return Cell(name, int(w["chips"]), cfg, mix, driver, metrics, bench_dir)


def require_chips(chips: int, peaks: Mapping[str, Any]):
    """The first ``chips`` TPU devices and their peak table entry, or an error:
    the benchmark never measures another platform."""
    if os.environ.get("REPRO_INTERPRET"):
        raise BenchError("REPRO_INTERPRET is set: the kernels would be "
                         "interpreted, not compiled")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:chips], peaks[kind]


class CompileCounter:
    """Counts programs compiled, and programs read from the compile cache."""

    def __init__(self):
        self.compiled = self.cache_reads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, _secs: float, **_kw) -> None:
        self.compiled += name == COMPILE_EVENT
        self.cache_reads += name == CACHE_READ_EVENT

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def make_mesh(devices):
    """One axis ``"x"`` over ``devices``, sharded by the compiler's choice
    (``Auto``), as the program's own meshes are; ``None`` for one chip."""
    if len(devices) == 1:
        return None
    return jax.make_mesh((len(devices),), ("x",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))


def _placement(devices):
    from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

    mesh = make_mesh(devices)
    if mesh is None:
        return None, SingleDeviceSharding(devices[0])
    return mesh, NamedSharding(mesh, P("x"))


def _failure_counts() -> Dict[str, int]:
    from repro.runtime import resilience

    stats = resilience.stats()
    return {k: stats.get(k, 0) for k in FAILURE_COUNTERS}


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool, devices,
             peak: Mapping[str, Any], t_start: float, log: Callable[[str], None],
             call: Optional[Callable] = None,
             trace_dir: Optional[str] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line's object.

    ``call`` replaces the driver's timed callable (a control, or a fault
    planted by a test); ``t_start`` is when the process started, so that
    ``setup_s`` counts everything before the window."""
    cfg, mix = cell.cfg, cell.traffic
    mesh, sharding = _placement(devices)
    key_sets, values = traffic.make_inputs(mix, cfg, seed, sharding)
    fn = call if call is not None else cell.driver.build(cfg, mesh)
    jax.block_until_ready(fn(key_sets[0], values))      # compiles: set-up
    setup_s = time.perf_counter() - t_start

    compiles = CompileCounter()
    rng = traffic.sample_rng(seed)
    keep = int(mix["checked_calls"])
    kept: List[tuple] = []                              # (call, key set, result)
    durations: List[float] = []
    completed = failed = 0
    trace_path = None
    if trace:
        trace_path = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_path, profiler_options=opts)
    t_first = t_last = time.perf_counter()
    try:
        while not durations or time.perf_counter() - t_first < seconds:
            i, s = len(durations), len(durations) % len(key_sets)
            before = _failure_counts()
            t0 = time.perf_counter()
            result = None
            try:
                with jax.profiler.TraceAnnotation(trace_mod.SPAN_CALL):
                    with jax.profiler.TraceAnnotation(trace_mod.SPAN_DISPATCH):
                        result = fn(key_sets[s], values)
                    with jax.profiler.TraceAnnotation(trace_mod.SPAN_SYNC):
                        jax.block_until_ready(result)
            except Exception:  # noqa: BLE001 - a call that raises is a failed call
                traceback.print_exc(file=sys.stderr)
                result = None
            t_last = time.perf_counter()
            durations.append(t_last - t0)
            if result is None or _failure_counts() != before:
                failed += 1
            if result is None:
                continue
            completed += 1
            if len(kept) < keep:                        # reservoir sample
                kept.append((i, s, result))
            else:
                j = int(rng.integers(0, i + 1))
                if j < keep:
                    kept[j] = (i, s, result)
            del result
    finally:
        if trace:
            jax.profiler.stop_trace()
        compiles.close()
    window_s = t_last - t_first
    mem = memory_peak_bytes(devices)
    log(f"calls={len(durations)} completed={completed} failed={failed} "
        f"window_s={window_s!r} compiled_in_window={compiles.compiled} "
        f"cache_reads_in_window={compiles.cache_reads} "
        f"checked_calls={[k[0] for k in kept]} "
        f"call_ms={[round(d * 1e3, 1) for d in durations]}")

    compared = check(cell, mesh, kept, key_sets, values)
    del kept, key_sets, values, fn

    tr = None
    if trace:
        try:
            tr = trace_mod.Trace.load(trace_mod.find_xplane(trace_path))
        finally:
            if trace_dir is None:
                shutil.rmtree(trace_path, ignore_errors=True)
    run = Run(cell, peak, setup_s, durations, completed, window_s, tr)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics[kind]:
        value = load_module(cell.bench_dir / "metrics" / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": all(c["value"] <= c["limit"] for c in compared.values()),
           "attempted": len(durations), "failed": failed, "metrics": metrics,
           "device": device}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        out["breakdown"] = tr.breakdown()
    out["compared"] = compared
    return out


def check(cell: Cell, mesh, kept, key_sets, values) -> Dict[str, Dict[str, float]]:
    """Every number compared, the worst over the checked calls, each with
    its limit; ``unchecked`` counts the configuration's numbers that no
    checked call gave."""
    limits = dict(cell.cfg["limits"], unchecked=0)
    worst: Dict[str, float] = {}
    wants: Dict[int, Dict[str, np.ndarray]] = {}      # key set -> reference
    host_values = None if values is None else np.asarray(values)
    extra = getattr(cell.driver, "extra_checks", None)
    for _, s, result in kept:
        numbers = {} if extra is None else dict(extra(result, mesh))
        if s not in wants:
            wants[s] = cell.driver.expected(cell.cfg, np.asarray(key_sets[s]),
                                            host_values)
        numbers.update(mismatches(cell.driver.fetch(result), wants[s]))
        for k, v in numbers.items():
            worst[k] = max(worst.get(k, 0), v)
    worst["unchecked"] = sum(k not in worst for k in limits if k != "unchecked")
    return {k: {"value": worst[k], "limit": limits.get(k, 0)} for k in sorted(worst)}
