"""``correct`` is true for the timed path as it is and false for its
control and for every fault a cell can have, at a size a test can hold."""

import json
import os
import subprocess
import sys

import pytest

from bench.tests import faults
from bench.tests.cases import run_case, small_cell

# cell -> its configuration, for a cell that BENCHMARK.json does not list
ONE_CHIP = {"ms_kv_m256": "paper-multisplit-kv-2p25",
            "sort_keys_r8": "paper-radix-sort-keys-2p25"}
BROKEN = ("control",) + tuple(faults.FAULTS)
SHARDED_CASES = ("program", "control") + tuple(faults.SHARDED_FAULTS)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_program_is_correct(workload):
    out = run_case(small_cell(workload, config=ONE_CHIP[workload]), "program",
                   trace=False)
    assert out["correct"], out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"keys_per_s", "call_p95_ms", "setup_s"}


@pytest.mark.parametrize("case", BROKEN)
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_broken_path_is_not_correct(workload, case):
    out = run_case(small_cell(workload, config=ONE_CHIP[workload]), case, trace=False)
    assert not out["correct"], out["compared"]


@pytest.fixture(scope="module")
def sharded_results():
    """Every four-chip case in one child process with four CPU devices. The
    four-chip cell is not in BENCHMARK.json yet (it has not run on four
    chips); its configuration and driver are, and are checked here."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join([root, os.path.join(root, "src")])
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.cases", "ms_kv_m256_4chip",
         "--config", "paper-multisplit-kv-2p25-4chip", "--chips", "4",
         "--cases", *SHARDED_CASES],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    return {r["case"]: r["correct"] for r in lines}


@pytest.mark.parametrize("case", SHARDED_CASES)
def test_sharded_cell(sharded_results, case):
    assert sharded_results[case] is (case == "program")
