"""The harness finds cells, configurations, traffic and metrics by name,
and refuses to measure anything but the chip."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench import harness, stats
from bench.reference import necessary_bytes

ROOT = harness.ROOT


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _run_py(cwd, *extra, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ms_kv_m256", "--seed",
         str(2**31 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    return proc.returncode != 0 and not last.startswith("{")


def test_refuses_a_cpu():
    assert _no_result(_run_py(ROOT))


def test_refuses_interpret_mode():
    assert _no_result(_run_py(ROOT, env_extra={"REPRO_INTERPRET": "1"}))


def test_refuses_a_device_kind_missing_from_the_peak_table():
    with pytest.raises(harness.BenchError):
        harness.require_chips(1, {})


def test_refuses_an_unknown_workload():
    with pytest.raises(harness.BenchError):
        harness.resolve_cell(_bench(), "no_such_cell")


def test_refuses_without_the_program(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert _no_result(_run_py(tmp_path))


def test_benchmark_names_every_file():
    bench = _bench()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()
        assert cfg["reduced"] == c["reduced"]
    for w in bench["workloads"]:
        cell = harness.resolve_cell(bench, w["name"])
        assert cell.chips == json.loads(
            (ROOT / next(c["file"] for c in bench["configs"]
                         if c["name"] == w["config"])).read_text())["chips"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()


def test_peak_table_has_the_v5e():
    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    assert "TPU v5e" in peaks["source"]
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_new_cell_and_metric_are_found_by_name(tmp_path):
    """A cell, configuration, traffic mix and metric added as files alone."""
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "drivers", "metrics"):
        (bench_dir / sub).mkdir(parents=True)
    shutil.copy(ROOT / "bench" / "drivers" / "radix_sort.py", bench_dir / "drivers")
    for name in ("keys_per_s", "setup_s"):
        shutil.copy(ROOT / "bench" / "metrics" / f"{name}.py", bench_dir / "metrics")
    (bench_dir / "metrics" / "calls_seen.py").write_text(
        "def read(run):\n    return float(len(run.durations_s))\n")
    (bench_dir / "metrics" / "nothing_here.py").write_text(
        "def read(run):\n    return None\n")
    cfg = {"driver": "radix_sort", "chips": 1, "n": 1024, "key_dtype": "uint32",
           "value_dtype": None, "limits": {"keys_mismatch": 0}, "reduced": []}
    (bench_dir / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "once.json").write_text(json.dumps(
        {"keys": "uniform", "values": "arange", "input_sets": 1, "checked_calls": 1}))
    bench = {
        "configs": [{"name": "tiny", "file": "bench/configs/tiny.json", "reduced": []}],
        "workloads": [{"name": "tiny_sort", "config": "tiny", "traffic": "once",
                       "chips": 1}],
        "end_to_end": [{"name": "keys_per_s", "unit": "Gkeys/s"},
                       {"name": "setup_s", "unit": "s"},
                       {"name": "calls_seen", "unit": "calls"},
                       {"name": "nothing_here", "unit": "s"}],
        "per_layer": [],
    }
    cell = harness.resolve_cell(bench, "tiny_sort", root=tmp_path, bench_dir=bench_dir)
    out = harness.run_cell(cell, seed=3, seconds=0.1, trace=False,
                           devices=jax.devices()[:1], peak={},
                           t_start=time.perf_counter(), log=lambda m: None)
    assert out["correct"]
    assert set(out["metrics"]) == {"keys_per_s", "setup_s", "calls_seen"}
    assert out["metrics"]["calls_seen"]["value"] == out["attempted"]
    assert list(out)[-1] == "compared"


def test_percentiles_are_nearest_rank():
    xs = list(range(1, 21))
    p = stats.percentiles(xs, (50.0, 95.0, 100.0))
    assert p == {50.0: 10.0, 95.0: 19.0, 100.0: 20.0}
    assert stats.percentiles([3.0], (95.0,))[95.0] == 3.0


def test_necessary_bytes_depend_on_the_problem_alone():
    assert necessary_bytes(1 << 25, 4, 4) == 1 << 29
    assert necessary_bytes(1 << 25, 4, 0) == 1 << 28
