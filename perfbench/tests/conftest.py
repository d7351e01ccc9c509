"""The benchmark's own tests: on the CPU, a few minutes in all. Run with
``python -m pytest perfbench/tests`` (tier-1 collects ``tests/`` only)."""

import json
import os

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Four CPU devices, so that the four-chip cell rehearses its exchange.
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


@pytest.fixture
def rehearse(capsys, monkeypatch):
    """Drives one rehearsal of a cell in this process (the harness's whole
    run but its look for a chip) and returns (exit code, result line). A
    cell given as an entry (a dict) is added to the manifest the harness
    reads, for that run."""

    def go(workload, seed: int = 3000000011, seconds: float = 1.0, probe: bool = False):
        from perfbench import harness

        if isinstance(workload, dict):
            manifest = harness.load_manifest()
            manifest["workloads"] = [w for w in manifest["workloads"] if w["name"] != workload["name"]] + [workload]
            for m in manifest["end_to_end"]:
                if m["name"] == "train_samples_per_s_per_chip" and workload["name"] not in m["workloads"]:
                    m["workloads"] = m["workloads"] + [workload["name"]]
            monkeypatch.setattr(harness, "load_manifest", lambda: manifest)
            workload = workload["name"]
        monkeypatch.setenv("PERFBENCH_PROBE", "1" if probe else "")
        rc = harness.main(
            ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
             "--trace", "0", "--rehearse"]
        )
        out = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(out[-1]), out

    return go
