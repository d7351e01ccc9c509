"""benchmarks/regress.py — the perf-regression gate's pass/fail contract.

The gate is pure (``evaluate(baseline_records, current_records)``); the
CLI is I/O around it. These tests pin the contract the CI perf-smoke
job depends on: exit 0 on parity, exit 1 on a seeded >10%% regression,
exit 2 when either side has no usable values — a gate that can't find
its numbers must fail loudly, not pass vacuously.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "benchmarks"))

from regress import (  # noqa: E402
    MISSING,
    PASS,
    REGRESSION,
    evaluate,
    load_records,
    main,
    metric_values,
)
import metrics_summary  # noqa: E402

METRIC = "cifar10_resnet18_train_samples_per_sec_per_chip"


def _bench(value, **extra):
    return {"kind": "bench", "metric": METRIC, "value": value, **extra}


def test_pass_within_tolerance():
    base = [_bench(100.0)]
    code, verdict = evaluate(base, [_bench(95.0)], metric=METRIC,
                             tolerance=0.10)
    assert code == PASS
    assert verdict["throughput_ok"]
    assert verdict["baseline"] == 100.0 and verdict["current"] == 95.0


def test_seeded_regression_fails():
    """A 15% drop against a 10% tolerance must exit nonzero."""
    base = [_bench(100.0)]
    code, verdict = evaluate(base, [_bench(85.0)], metric=METRIC,
                             tolerance=0.10)
    assert code == REGRESSION
    assert not verdict["throughput_ok"]
    assert verdict["floor"] == pytest.approx(90.0)


def test_missing_metric_exits_2():
    code, verdict = evaluate([_bench(100.0)], [], metric=METRIC)
    assert code == MISSING and "error" in verdict
    code, verdict = evaluate([], [_bench(100.0)], metric=METRIC)
    assert code == MISSING and "error" in verdict


def test_baseline_is_window_median():
    """One noisy baseline run must not move the bar: the gate uses the
    median of the last ``window`` values, in stream order."""
    base = [_bench(v) for v in (500.0, 100.0, 102.0, 98.0, 101.0, 99.0)]
    code, verdict = evaluate(base, [_bench(95.0)], metric=METRIC,
                             tolerance=0.10, window=5)
    assert verdict["baseline"] == 100.0  # median of last 5, 500 aged out
    assert code == PASS


def test_no_default_baseline_exits_2(tmp_path, capsys):
    """No BENCH_r*.json is checked in: without --baseline the gate has
    nothing to compare against and must say so, not pass."""
    current = tmp_path / "metrics.jsonl"
    current.write_text(json.dumps(_bench(100.0)) + "\n")
    assert main(["--current", str(current)]) == MISSING
    assert "nothing to compare against" in capsys.readouterr().err


def test_bench_envelope_parsing(tmp_path):
    """BENCH_rNN.json driver envelopes (headline record under "parsed")
    read the same as JSONL streams."""
    envelope = {
        "n": 5, "cmd": "python3 -m perfbench", "rc": 0, "tail": "...",
        "parsed": {"metric": METRIC, "value": 35330.5, "unit": "s/s/chip"},
    }
    p = tmp_path / "BENCH_r05.json"
    p.write_text(json.dumps(envelope))
    records = load_records(str(p))
    assert metric_values(records, METRIC) == [35330.5]

    jsonl = tmp_path / "metrics.jsonl"
    jsonl.write_text(
        json.dumps(_bench(34000.0)) + "\n" + json.dumps(_bench(35000.0)) + "\n"
    )
    assert metric_values(load_records(str(jsonl)), METRIC) == [
        34000.0, 35000.0,
    ]


def test_phase_gate_on_sync_exposed():
    """When both sides carry phase_summary records and a phase tolerance
    is set, a blown sync_exposed_ms fails even if throughput passes."""
    summary = {"kind": "phase_summary", "sync_exposed_ms": 2.0}
    base = [_bench(100.0), summary]
    good = [_bench(100.0), {"kind": "phase_summary", "sync_exposed_ms": 2.1}]
    bad = [_bench(100.0), {"kind": "phase_summary", "sync_exposed_ms": 9.0}]
    code, verdict = evaluate(base, good, metric=METRIC, phase_tolerance=0.5)
    assert code == PASS and verdict["sync_exposed_ok"]
    code, verdict = evaluate(base, bad, metric=METRIC, phase_tolerance=0.5)
    assert code == REGRESSION
    assert verdict["throughput_ok"] and not verdict["sync_exposed_ok"]
    # without the flag the phase records are ignored
    code, verdict = evaluate(base, bad, metric=METRIC)
    assert code == PASS and "sync_exposed_ok" not in verdict


def test_metrics_summary_phase_rows():
    """metrics_summary.summarize picks up graftscope phase records next
    to the step records it already reduces."""
    records = [
        {"kind": "step", "step": 1, "loss": 2.5, "step_time_s": 0.5},
        {"kind": "step", "step": 2, "loss": 2.0, "step_time_s": 0.1},
        {
            "kind": "phase", "phase": "grad_sync", "device_ms": 1.25,
            "wall_ms": 30.0, "clock": "device", "flops": 1e6,
            "bytes_accessed": 2e6, "comm_bytes": 8e4, "mfu": 0.1,
            "roofline": "comms",
        },
        {"kind": "phase_summary", "sync_exposed_ms": 0.75},
    ]
    s = metrics_summary.summarize(records)
    assert s["phases"]["grad_sync"]["ms"] == 1.25  # device clock wins
    assert s["phases"]["grad_sync"]["roofline"] == "comms"
    assert s["sync_exposed_ms"] == 0.75
    assert s["final_loss"] == 2.0  # step reduction unaffected


def test_metrics_summary_memory_ledger_rows():
    """metrics_summary renders graftmem memory_report.json ledgers as
    one hbm row per entrypoint, latest record per entry winning."""
    ledger = {
        "kind": "memory_ledger", "entry": "cifar", "devices": 4,
        "argument_bytes": 118332, "output_bytes": 93964,
        "temp_bytes": 2558400, "total_bytes": 2676980,
        "alias_saved_bytes": 93716, "dropped_donation_bytes": 0,
        "replicated_leaves": 0,
    }
    stale = dict(ledger, total_bytes=1)
    s = metrics_summary.summarize([stale, ledger])
    assert s["memory"]["cifar"]["total_bytes"] == 2676980
    assert s["memory"]["cifar"]["devices"] == 4
    # a replicated leaf count survives into the summary for the renderer
    leaky = dict(ledger, entry="lm", replicated_leaves=2)
    s = metrics_summary.summarize([leaky])
    assert s["memory"]["lm"]["replicated_leaves"] == 2
