"""Every cell's driver through the harness on the CPU, at a tiny size.

The harness's look for a chip is skipped here; the last test checks that
the benchmark itself, given no TPU, exits non-zero and prints no result.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(Path(__file__).parent)]

from bench.lib.harness import run  # noqa: E402
from cells import cells, shrink  # noqa: E402


def last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", cells())
def test_cell_runs_and_checks_on_cpu(cell, capsys):
    rc = run(cell, 2**33 + 17, 0.2, False, t_start=time.perf_counter(),
             require_chip=False, shrink=shrink(cell))
    out, err = capsys.readouterr()
    assert rc == 0, err
    line = last_json(out)
    assert line["correct"] is True, line["check"]
    assert list(line)[-1] == "check"
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert "sim_ttis_per_s" in line["metrics"]
    assert err.strip().splitlines()[-1].startswith("check ")


def test_without_a_tpu_the_benchmark_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         cells()[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "TPU" in p.stderr
