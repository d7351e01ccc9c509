"""``BENCHMARK.json`` and the data files it names keep to the contract:
names, units, every ``moves`` an end-to-end metric that each cell of the
metric's ``workloads`` reports, every metric file matching its entry."""

import importlib
import json
import re
from pathlib import Path

import pytest

from perfbench import harness

ROOT = Path(__file__).resolve().parents[2]
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in M["workloads"]]


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(line(c) for c in M["command"])
    assert M["paths"] == ["perfbench"]
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    # 2 + 14 runs a cell, each run_seconds + 60 s, 180 s a cell to compile, 1200 s spare
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in M["configs"]]
    assert len(set(names)) == len(names) and 1 <= len(names) <= 24
    used = {w["config"] for w in M["workloads"]}
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line(c["source"]) and line(c["why"]) and len(c["reduced"]) <= 16
        assert c["file"].startswith("perfbench/") and (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank)$|hidden|intermediate|n_embd|n_inner|head", key)
    assert len({c["file"] for c in M["configs"]}) == len(names)


def test_workloads():
    assert len(set(CELLS)) == len(CELLS) and 1 <= len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"], w["chips"]) for w in M["workloads"]]
    assert len({(c, t) for c, t, _ in pairs}) == len(pairs), "a pair of configuration and traffic appears once"
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert line(w["why"]) and len(w["why"]) > 40
        traffic = harness.traffic_of(w)
        assert importlib.import_module(f"perfbench.drivers.{traffic['driver']}").run


def test_end_to_end():
    names = [m["name"] for m in M["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for cell in CELLS:
        reported = [m["name"] for m in M["end_to_end"] if cell in m.get("workloads", CELLS)]
        assert "setup_s" in reported and len(reported) >= 2


def test_per_layer_entries_and_their_files():
    e2e = {m["name"]: m for m in M["end_to_end"]}
    files = harness.metric_files()
    entries = {m["name"]: m for m in M["per_layer"]}
    assert set(entries) == set(files) and 1 <= len(entries) <= 128
    assert not set(entries) & set(e2e)
    layers = {}
    for name, m in entries.items():
        f = files[name]
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert {k: f[k] for k in m} == m, f"{name}: BENCHMARK.json and perfbench/metrics/{name}.json differ"
        assert NAME.match(name) and UNIT.match(m["unit"]) and m["source"] in SOURCES and line(m["layer"])
        assert m["moves"] in e2e
        for cell in harness.cells_of(m, M):
            assert cell in e2e[m["moves"]].get("workloads", CELLS), f"{name}: {cell} does not report {m['moves']}"
        assert importlib.import_module(f"perfbench.readers.{f['reader']}").read
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
        if name.endswith("_roofline") or "mfu" in re.split(r"[._]", name):
            assert m["unit"] == "%" and m["better"] == "higher"
    assert all(len(v) == 1 for v in layers.values()), "one layer, one spelling"
    for cell in CELLS:
        assert any(cell in harness.cells_of(m, M) for m in entries.values())


def test_rooflines_have_a_whole_step_share_beside_them():
    entries = {m["name"]: m for m in M["per_layer"]}
    for name, m in entries.items():
        if not name.endswith("_roofline"):
            continue
        beside = [
            o for n, o in entries.items()
            if "mfu" in re.split(r"[._]", n) and o["moves"] == m["moves"]
            and set(harness.cells_of(m, M)) <= set(harness.cells_of(o, M))
        ]
        assert beside, f"{name} has no mfu metric moving {m['moves']} in its cells"


def test_files_under_paths_are_named_from_a_name_s_characters():
    import subprocess

    listed = subprocess.run(
        ["git", "ls-files", "--cached", "--others", "--exclude-standard", "perfbench"],
        cwd=ROOT, capture_output=True, text=True,
    )
    if listed.returncode != 0:
        pytest.skip("not a git checkout")
    for path in listed.stdout.split():
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", path), path


def test_every_cell_has_limits_that_its_driver_compares():
    from perfbench import check

    for w in M["workloads"]:
        limits = check.load_limits(w["name"])
        assert limits and all(isinstance(v, (int, float)) for v in limits.values())
