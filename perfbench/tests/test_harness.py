"""The harness end to end: at the measurement gate a machine without the
chip is refused and nothing is printed; a rehearsal drives the whole run
of each driver at a tiny size and prints no device metric."""

import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench.tests.cells import DP4

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _run(cwd, *extra):
    return subprocess.run(
        [sys.executable, "-m", "perfbench", "--workload", CELLS[0], "--seed", "3000000011",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300,
    )


def test_no_chip_no_result():
    r = _run(ROOT)
    assert r.returncode != 0 and r.stdout.strip() == ""
    assert "TPU" in r.stderr and "nothing was measured" in r.stderr


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path, "--rehearse")
    assert r.returncode != 0 and r.stdout.strip() == ""


@pytest.mark.parametrize("cell", CELLS + [DP4], ids=lambda c: c if isinstance(c, str) else c["name"])
def test_rehearsal_runs_the_whole_cell(rehearse, cell):
    if isinstance(cell, dict) and cell["name"] in CELLS:
        pytest.skip("in BENCHMARK.json by now, and rehearsed from there")
    rc, line, out = rehearse(cell)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    # A number from the CPU is never written under a device metric's name.
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "check" and all(n["ok"] for n in line["check"].values())
    series = json.loads(out[-2].removeprefix("perfbench series "))
    assert series["compiles_in_window"] == 0 and series["n"] >= 1
    assert {"gap_ms_median", "gap_ms_p99", "gap_ms_max", "max_at"} <= set(series)
