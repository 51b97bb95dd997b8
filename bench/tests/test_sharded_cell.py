"""The four-chip cell's path on four virtual CPU devices: a tiny GBT cell
served by ``ShardedDeviceExecutor`` is correct, and each fault planted
underneath its timed path makes ``correct`` false.

A process fixes its device count when JAX starts, so the cell runs in a
child process with ``--xla_force_host_platform_device_count=4``: this file
run as a script prints one JSON line per case.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

FAULTS = ["answer_altered", "half_left_out", "state_unchanged", "exchange_left_out"]
BENCH = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def cases(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               BENCH_TEST_CACHE=str(tmp_path_factory.mktemp("bench-cache-4")))
    proc = subprocess.run([sys.executable, __file__], env=env, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return {c["case"]: c for c in map(json.loads, proc.stdout.splitlines())}


def test_sharded_cell_is_correct(cases):
    c = cases["sound"]
    assert c["correct"], c["checks"]
    assert c["devices"] == 4 and c["checks"]["wrong_executor"]["value"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_sharded_planted_fault_is_not_correct(cases, fault):
    c = cases[fault]
    assert not c["correct"]
    assert c["checks"]["mismatched_rows"]["value"] > 0


def main() -> None:
    sys.path[:0] = [str(BENCH / "tests"), str(BENCH), str(BENCH.parent / "src")]
    import harness
    import test_harness
    from repro.kernels.sharded_executor import ShardedDeviceExecutor

    harness.CACHE = Path(os.environ["BENCH_TEST_CACHE"])
    cell = dataclasses.replace(
        test_harness.tiny_cell("gbt500_adult", "closed", backend="auto"), chips=4)
    inner = ShardedDeviceExecutor.run
    for case in ["sound"] + FAULTS:
        ShardedDeviceExecutor.run = inner
        if case != "sound":
            ShardedDeviceExecutor.run = test_harness._faulty(case, ShardedDeviceExecutor)
        out = harness.run_cell(cell, 2**35 + 11, 0.5, False, time.time(), log=lambda s: None)
        print(json.dumps({"case": case, "correct": out["correct"], "checks": out["checks"],
                          "devices": out["device"]["count"]}), flush=True)


if __name__ == "__main__":
    main()
