"""Perf-regression gate: compare bench output against a baseline window.

Throughput history comes as bench envelopes (``BENCH_r*.json``, each
holding a run's parsed headline record) or as ``kind="bench"`` records
on telemetry JSONL streams (``serve_cli --metrics-dir``). No envelope is
checked in on this installation, so ``--baseline`` must name the
history to compare against; without one the gate exits 2. This gate
reads EITHER format on either side, takes the **median of the last ``--window`` baseline
values** (median, not mean: one noisy CI run must not move the bar),
and fails when the current value drops more than ``--tolerance`` below
it. When BOTH sides carry graftscope ``phase_summary`` records, the
``sync_exposed_ms`` metric is gated too (higher-is-worse, its own
tolerance) — so a sync-overlap win (ROADMAP item 2), once landed,
cannot silently regress. Independently, any baseline record carrying
``sync_exposed_budget_ms`` arms an ABSOLUTE ceiling on the current
stream's sync_exposed_ms. Nothing in the tree emits ``phase_summary``
records since the segmented profiler went (ROADMAP D4b): that half is
held by ``tests/test_regress.py`` alone.

Exit codes: 0 pass, 1 regression, 2 missing/unusable data (a gate that
can't find its numbers must fail loudly, not pass vacuously).

CLI::

    python benchmarks/regress.py --current run/metrics.jsonl \\
        [--baseline BENCH_r0*.json] [--metric NAME] [--tolerance 0.10]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import statistics
import sys
from typing import Any

DEFAULT_METRIC = "cifar10_resnet18_train_samples_per_sec_per_chip"
DEFAULT_TOLERANCE = 0.10
DEFAULT_WINDOW = 5

PASS, REGRESSION, MISSING = 0, 1, 2


def load_records(path: str) -> list[dict[str, Any]]:
    """Records from one file, either format:

    - JSONL telemetry stream: one record per line (non-dict lines skipped)
    - bench envelope (``BENCH_rNN.json``): a single JSON object whose
      ``parsed`` field is the headline record (driver format) — or any
      single JSON object/array of records
    """
    with open(path) as f:
        text = f.read()
    records: list[dict[str, Any]] = []
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if obj is not None:
        if isinstance(obj, list):
            records = [r for r in obj if isinstance(r, dict)]
        elif isinstance(obj, dict):
            # Driver envelope: the record of interest rides in "parsed".
            parsed = obj.get("parsed")
            records = [parsed] if isinstance(parsed, dict) else [obj]
        return records
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(rec, dict):
            records.append(rec)
    return records


def metric_values(records: list[dict[str, Any]], metric: str) -> list[float]:
    """Values of ``metric`` in stream order. A record counts if its
    ``metric`` field matches and it carries a numeric ``value`` —
    ``kind`` is not required, so bare envelope records qualify too."""
    vals = []
    for r in records:
        if r.get("metric") == metric and isinstance(
            r.get("value"), (int, float)
        ):
            vals.append(float(r["value"]))
    return vals


def sync_exposed_values(records: list[dict[str, Any]]) -> list[float]:
    vals = []
    for r in records:
        if r.get("kind") == "phase_summary" and isinstance(
            r.get("sync_exposed_ms"), (int, float)
        ):
            vals.append(float(r["sync_exposed_ms"]))
    return vals


def generic_budgets(records: list[dict[str, Any]]) -> list[dict[str, Any]]:
    """Generic absolute gates armed by baseline records of the form
    ``{"metric": NAME, "budget": V, "direction": "max"|"min"}`` (the
    checked-in ``benchmarks/serve_smoke_budget.json`` idiom). Direction
    "max" (default) means the current value must stay <= budget (a
    latency ceiling, e.g. serve p99 TTFT); "min" means >= budget (a
    throughput floor). Last record per metric wins."""
    budgets: dict[str, dict[str, Any]] = {}
    for r in records:
        if isinstance(r.get("metric"), str) and isinstance(
            r.get("budget"), (int, float)
        ):
            budgets[r["metric"]] = {
                "metric": r["metric"],
                "budget": float(r["budget"]),
                "direction": r.get("direction", "max"),
            }
    return list(budgets.values())


def sync_exposed_budget(records: list[dict[str, Any]]) -> float | None:
    """Absolute sync_exposed_ms ceiling carried by the baseline side.

    A budget envelope carries ``sync_exposed_budget_ms``; its presence
    among the baseline records ARMS the budget gate — no extra CLI flag
    needed. Last value wins."""
    budget = None
    for r in records:
        if isinstance(r.get("sync_exposed_budget_ms"), (int, float)):
            budget = float(r["sync_exposed_budget_ms"])
    return budget


def evaluate(
    baseline_records: list[dict[str, Any]],
    current_records: list[dict[str, Any]],
    *,
    metric: str = DEFAULT_METRIC,
    tolerance: float = DEFAULT_TOLERANCE,
    window: int = DEFAULT_WINDOW,
    phase_tolerance: float | None = None,
) -> tuple[int, dict[str, Any]]:
    """(exit_code, verdict). Pure — the CLI is I/O around this.

    Throughput gate: current >= median(last ``window`` baseline values)
    * (1 - tolerance). Phase gate (only when BOTH sides have
    ``phase_summary`` records and ``phase_tolerance`` is not None):
    current sync_exposed_ms <= baseline * (1 + phase_tolerance), with a
    0.5 ms absolute grace so a ~0 baseline doesn't make noise a failure.
    """
    base_vals = metric_values(baseline_records, metric)
    cur_vals = metric_values(current_records, metric)
    verdict: dict[str, Any] = {"metric": metric, "tolerance": tolerance}
    if not base_vals:
        verdict["error"] = f"no baseline values for metric {metric!r}"
        return MISSING, verdict
    if not cur_vals:
        verdict["error"] = f"no current values for metric {metric!r}"
        return MISSING, verdict
    base = statistics.median(base_vals[-window:])
    cur = cur_vals[-1]
    floor = base * (1.0 - tolerance)
    verdict.update(
        baseline=base,
        baseline_n=len(base_vals[-window:]),
        current=cur,
        floor=floor,
        ratio=cur / base if base else None,
        throughput_ok=cur >= floor,
    )
    code = PASS if verdict["throughput_ok"] else REGRESSION

    if phase_tolerance is not None:
        base_sync = sync_exposed_values(baseline_records)
        cur_sync = sync_exposed_values(current_records)
        if base_sync and cur_sync:
            b = statistics.median(base_sync[-window:])
            c = cur_sync[-1]
            ceil = b * (1.0 + phase_tolerance) + 0.5
            verdict.update(
                sync_exposed_baseline_ms=b,
                sync_exposed_current_ms=c,
                sync_exposed_ceiling_ms=ceil,
                sync_exposed_ok=c <= ceil,
            )
            if not verdict["sync_exposed_ok"]:
                code = REGRESSION

    budget = sync_exposed_budget(baseline_records)
    if budget is not None:
        cur_sync = sync_exposed_values(current_records)
        if not cur_sync:
            # An armed budget with nothing to gate is missing data, not
            # a pass — the CI stream must carry phase_summary records.
            verdict["error"] = (
                "sync_exposed_budget_ms armed but the current stream has "
                "no phase_summary records"
            )
            return MISSING, verdict
        c = cur_sync[-1]
        verdict.update(
            sync_exposed_budget_ms=budget,
            sync_exposed_current_ms=c,
            sync_budget_ok=c <= budget,
        )
        if not verdict["sync_budget_ok"]:
            code = REGRESSION

    checks = []
    for bgt in generic_budgets(baseline_records):
        vals = metric_values(current_records, bgt["metric"])
        if not vals:
            verdict["error"] = (
                f"budget armed for metric {bgt['metric']!r} but the "
                "current stream has no values for it"
            )
            return MISSING, verdict
        cur_v = vals[-1]
        ok = (
            cur_v >= bgt["budget"]
            if bgt["direction"] == "min"
            else cur_v <= bgt["budget"]
        )
        checks.append({**bgt, "current": cur_v, "ok": ok})
        if not ok:
            code = REGRESSION
    if checks:
        verdict["budgets"] = checks
    return code, verdict


def _default_baselines() -> list[str]:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return sorted(glob.glob(os.path.join(root, "BENCH_r*.json")))


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--current", required=True,
        help="bench output to gate: a metrics.jsonl stream or envelope JSON",
    )
    p.add_argument(
        "--baseline", nargs="*", default=None,
        help="baseline file(s); default: BENCH_r*.json envelopes at the "
        "repo root, if any",
    )
    p.add_argument("--metric", default=DEFAULT_METRIC)
    p.add_argument("--tolerance", type=float, default=DEFAULT_TOLERANCE)
    p.add_argument(
        "--window", type=int, default=DEFAULT_WINDOW,
        help="median over the last N baseline values (default %(default)s)",
    )
    p.add_argument(
        "--phase-tolerance", type=float, default=None,
        help="also gate sync_exposed_ms (phase_summary records) within "
        "this relative headroom; off by default",
    )
    p.add_argument("--json", action="store_true", help="print the verdict as JSON")
    args = p.parse_args(argv)

    baseline_paths = (
        args.baseline if args.baseline else _default_baselines()
    )
    if not baseline_paths:
        print(
            "regress: no --baseline given and no BENCH_r*.json at the repo "
            "root; nothing to compare against",
            file=sys.stderr,
        )
        return MISSING
    baseline_records: list[dict[str, Any]] = []
    for path in baseline_paths:
        baseline_records.extend(load_records(path))
    current_records = load_records(args.current)

    code, verdict = evaluate(
        baseline_records,
        current_records,
        metric=args.metric,
        tolerance=args.tolerance,
        window=args.window,
        phase_tolerance=args.phase_tolerance,
    )
    if args.json:
        print(json.dumps(verdict, indent=1))
    elif "error" in verdict:
        print(f"regress: {verdict['error']}", file=sys.stderr)
    else:
        status = "PASS" if code == PASS else "FAIL"
        print(
            f"regress [{status}] {verdict['metric']}: current "
            f"{verdict['current']:.1f} vs baseline {verdict['baseline']:.1f} "
            f"(floor {verdict['floor']:.1f}, ratio {verdict['ratio']:.3f})"
        )
        if "sync_exposed_ok" in verdict:
            print(
                f"regress [{'PASS' if verdict['sync_exposed_ok'] else 'FAIL'}] "
                f"sync_exposed_ms: current "
                f"{verdict['sync_exposed_current_ms']:.3f} vs baseline "
                f"{verdict['sync_exposed_baseline_ms']:.3f} (ceiling "
                f"{verdict['sync_exposed_ceiling_ms']:.3f})"
            )
        if "sync_budget_ok" in verdict:
            print(
                f"regress [{'PASS' if verdict['sync_budget_ok'] else 'FAIL'}] "
                f"sync_exposed_ms budget: current "
                f"{verdict['sync_exposed_current_ms']:.3f} vs budget "
                f"{verdict['sync_exposed_budget_ms']:.3f}"
            )
        for bgt in verdict.get("budgets", []):
            cmp_ = ">=" if bgt["direction"] == "min" else "<="
            print(
                f"regress [{'PASS' if bgt['ok'] else 'FAIL'}] "
                f"{bgt['metric']} budget: current {bgt['current']:.3f} "
                f"{cmp_} {bgt['budget']:.3f}"
            )
    return code


if __name__ == "__main__":
    sys.exit(main())
