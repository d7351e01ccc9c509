"""Tabulate a telemetry JSONL (obs/) for eyeballing a run.

    python benchmarks/metrics_summary.py /tmp/run/metrics.jsonl

Reads the stream the engines write with ``--metrics-dir`` (or a file
``serve_cli --metrics-dir`` appended to), filters the ``kind == "step"``
records, and prints a one-screen summary: steps covered, mean step time
(first emission excluded — it amortizes compile), final/best loss, mean
MFU where recorded, and total gradient bytes on the wire. Stdlib only —
usable on any machine the JSONL lands on.

Also accepts the graftfleet ``fleet_report.json`` artifact (a single
pretty-printed object; its ``records`` list flattens into the stream)
and summarizes its ``fleet_skew`` / ``fleet_incident`` /
``fleet_summary`` rows: per-step collective-skew attribution with a
straggler histogram, incident counts, and the run-level audit line.
The graftmem ``memory_report.json`` artifact flattens the same way:
its ``kind:"memory_ledger"`` rows render one ``hbm <entry>`` line per
registered entrypoint — per-device HBM bytes, donation-alias savings,
and any replicated-leaf count TA008 found.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any


def load_records(path: str) -> list[dict[str, Any]]:
    with open(path, encoding="utf-8") as f:
        text = f.read()
    # Whole-file JSON first: a pretty-printed object carrying "records"
    # (the fleet_report.json artifact obs/fleet.py writes) flattens
    # into its row list; a bare object/array is taken as-is. Anything
    # that isn't one JSON document falls through to JSONL.
    try:
        obj = json.loads(text)
    except json.JSONDecodeError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("records"), list):
            return [r for r in obj["records"] if isinstance(r, dict)]
        return [obj]
    if isinstance(obj, list):
        return [r for r in obj if isinstance(r, dict)]
    records: list[dict[str, Any]] = []
    for i, line in enumerate(text.splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError as e:
            print(f"{path}:{i + 1}: skipping bad line ({e})",
                  file=sys.stderr)
            continue
        if isinstance(rec, dict):
            records.append(rec)
    return records


def _mean(vals: list[float]) -> float | None:
    return sum(vals) / len(vals) if vals else None


def summarize(records: list[dict[str, Any]]) -> dict[str, Any]:
    """Reduce a record stream to the table rows. Pure — tested directly."""
    steps = [r for r in records if r.get("kind") == "step"]
    losses = [r["loss"] for r in steps
              if isinstance(r.get("loss"), (int, float))]
    # Drop the first recorded step time: it amortizes XLA compilation
    # and would dominate short runs.
    times = [r["step_time_s"] for r in steps
             if isinstance(r.get("step_time_s"), (int, float))][1:]
    mfus = [r["mfu"] for r in steps if isinstance(r.get("mfu"), (int, float))]
    wire = [r["grad_sync_bytes"] for r in steps
            if isinstance(r.get("grad_sync_bytes"), (int, float))]
    events = [r for r in records if r.get("kind") == "event"]
    # Chaos/recovery attribution (docs/reliability.md): kind:"event"
    # records are stamped with process_id/generation, so a merged
    # multi-process stream (e.g. a rendezvous store's events.jsonl)
    # summarizes into per-rank/per-generation "pN/gM" tags — which rank
    # died, who re-elected, who restored, in which generation.
    chaos_events: dict[str, dict[str, Any]] = {}
    for r in events:
        name = r.get("event")
        if not isinstance(name, str):
            continue
        if not (
            name.startswith("recovery_")
            or name
            in (
                "chaos_inject",
                "process_loss",
                "worker_death",
                "worker_exit",
                "reelection",
                "generation_start",
                "run_complete",
            )
        ):
            continue
        row = chaos_events.setdefault(name, {"count": 0, "by": []})
        row["count"] += 1
        pid, gen = r.get("process_id"), r.get("generation")
        if pid is not None or gen is not None:
            tag = f"p{'-' if pid is None else pid}/g{'-' if gen is None else gen}"
            if tag not in row["by"]:
                row["by"].append(tag)
        # recovery_giveup carries the full traceback of the fatal
        # failure (utils/failure.py, serve/guard.py); surface the last
        # non-empty line — the exception itself — as the row's tail.
        tb = r.get("traceback")
        if isinstance(tb, str) and tb.strip():
            row["traceback_tail"] = tb.strip().splitlines()[-1].strip()
    # per-phase records (no producer since ROADMAP D4b) plus the
    # serve-side kind:"serve_phase" twins (serve_cli --trace-dir): one
    # row per phase, keyed by name, latest record wins on repeat runs.
    phases: dict[str, dict[str, Any]] = {}
    for r in records:
        kind = r.get("kind")
        if kind in ("phase", "serve_phase") and isinstance(
            r.get("phase"), str
        ):
            row = {
                k: r.get(k)
                for k in ("clock", "flops", "bytes_accessed",
                          "comm_bytes", "mfu", "roofline")
            }
            row["ms"] = (
                r.get("device_ms")
                if r.get("clock") == "device"
                else r.get("wall_ms")
            )
            name = r["phase"]
            phases[f"serve {name}" if kind == "serve_phase" else name] = row
    sync_exposed = [
        float(r["sync_exposed_ms"]) for r in records
        if r.get("kind") == "phase_summary"
        and isinstance(r.get("sync_exposed_ms"), (int, float))
    ]
    # Fused-vs-overlapped sync comparison rows (no producer, ROADMAP D4b):
    # one row per wire format, latest record wins on repeat runs.
    sync_compare: dict[str, dict[str, Any]] = {}
    for r in records:
        if r.get("kind") == "sync_compare" and isinstance(
            r.get("wire"), str
        ):
            sync_compare[r["wire"]] = {
                k: r.get(k)
                for k in ("sync_overlap", "fused_step_ms", "overlap_step_ms",
                          "sync_exposed_ms_fused", "sync_exposed_ms_overlap",
                          "parity_ok")
            }
    # Serving rows (serve/loadgen.py): one row per engine label
    # ("continuous" / "batch"), latest serve_summary record wins.
    serve: dict[str, dict[str, Any]] = {}
    for r in records:
        if r.get("kind") == "serve_summary" and isinstance(
            r.get("engine"), str
        ):
            serve[r["engine"]] = {
                k: r.get(k)
                for k in ("requests", "ttft_p50_ms", "ttft_p99_ms",
                          "itl_p50_ms", "itl_p99_ms",
                          "tokens_per_sec", "page_high_water",
                          "slot_occupancy", "preemptions",
                          "recovered_requests",
                          "completed", "rejected", "timed_out",
                          "recovered", "restarts")
            }
    # graftguard overload shedding (serve/guard.py): kind:"serve_shed"
    # records aggregated by machine-readable reason; terminal sheds
    # (rejections) counted apart from non-terminal ones (degrade trims).
    serve_shed: dict[str, int] = {}
    shed_terminal = 0
    for r in records:
        if r.get("kind") == "serve_shed":
            reason = r.get("reason")
            if isinstance(reason, str):
                serve_shed[reason] = serve_shed.get(reason, 0) + 1
            if r.get("terminal"):
                shed_terminal += 1
    # graftserve windowed SLO telemetry (obs/serve_trace.py): one
    # aggregate row over every kind:"serve_window" record — TTFT/ITL
    # p99 trajectory (last + worst window), peak pool occupancy, queue
    # depth, preemption rate.
    windows = [r for r in records if r.get("kind") == "serve_window"]
    serve_windows: dict[str, Any] | None = None
    if windows:
        def _col(key: str) -> list[float]:
            return [w[key] for w in windows
                    if isinstance(w.get(key), (int, float))]

        ttft = _col("ttft_p99_ms")
        itl = _col("itl_p99_ms")
        serve_windows = {
            "count": len(windows),
            "span_s": windows[-1].get("t_s"),
            "ttft_p99_ms_last": ttft[-1] if ttft else None,
            "ttft_p99_ms_max": max(ttft) if ttft else None,
            "itl_p99_ms_last": itl[-1] if itl else None,
            "itl_p99_ms_max": max(itl) if itl else None,
            "live_pages_peak": max(_col("live_pages"), default=None),
            "queue_depth_max": max(_col("queue_depth_max"), default=None),
            "preempt_rate_per_s_max": max(
                _col("preempt_rate_per_s"), default=None
            ),
        }
    # decode_host_exposed_ms (kind:"serve_phase_summary"): host
    # scheduling overhead per live decode step — the serving analog of
    # sync_exposed_ms.
    host_exposed = [
        float(r["decode_host_exposed_ms"]) for r in records
        if r.get("kind") == "serve_phase_summary"
        and isinstance(r.get("decode_host_exposed_ms"), (int, float))
    ]
    # graftfleet rows (obs/fleet.py fleet_report.json, flattened by
    # load_records): skew attribution aggregated over post-warmup steps
    # (straggler histogram + worst skew), incidents counted by event
    # name, and the run-level summary (latest record wins).
    fleet_skew_rows = [
        r for r in records
        if r.get("kind") == "fleet_skew" and not r.get("warmup")
    ]
    fleet_skew: dict[str, Any] | None = None
    if fleet_skew_rows:
        skews = [float(r["skew_ms"]) for r in fleet_skew_rows
                 if isinstance(r.get("skew_ms"), (int, float))]
        stragglers: dict[str, int] = {}
        for r in fleet_skew_rows:
            s = r.get("straggler")
            if s is not None:
                stragglers[f"r{s}"] = stragglers.get(f"r{s}", 0) + 1
        fleet_skew = {
            "steps": len(fleet_skew_rows),
            "max_skew_ms": max(skews) if skews else None,
            "mean_skew_ms": _mean(skews),
            "stragglers": stragglers,
        }
    fleet_incidents: dict[str, int] = {}
    for r in records:
        if r.get("kind") == "fleet_incident" and isinstance(
            r.get("event"), str
        ):
            fleet_incidents[r["event"]] = (
                fleet_incidents.get(r["event"], 0) + 1
            )
    # graftmem rows (analysis/trace/memory.py memory_report.json,
    # flattened by load_records): the compiled per-device HBM ledger of
    # each registered entrypoint, latest record per entry wins.
    memory: dict[str, dict[str, Any]] = {}
    for r in records:
        if r.get("kind") == "memory_ledger" and isinstance(
            r.get("entry"), str
        ):
            memory[r["entry"]] = {
                k: r.get(k)
                for k in ("devices", "argument_bytes", "output_bytes",
                          "temp_bytes", "total_bytes", "alias_saved_bytes",
                          "dropped_donation_bytes", "replicated_leaves")
            }
    fleet_summaries = [r for r in records if r.get("kind") == "fleet_summary"]
    fleet_summary = (
        {
            k: fleet_summaries[-1].get(k)
            for k in ("generations", "ranks", "steps_attributed",
                      "max_skew_ms", "problems", "torn_lines")
        }
        if fleet_summaries
        else None
    )
    # Chaos visibility (docs/reliability.md): per-request kind:"serve"
    # lifecycle events — preemption replays and kill/resume recoveries
    # (serve/engine.py emits one record per transition).
    serve_events = [r for r in records if r.get("kind") == "serve"]
    preempt_replays = sum(
        1 for r in serve_events if r.get("event") == "preempt"
    )
    recovered = sum(
        1 for r in serve_events if r.get("event") == "recovered"
    )
    return {
        "records": len(records),
        "step_records": len(steps),
        "step_range": (
            (steps[0].get("step"), steps[-1].get("step")) if steps else None
        ),
        "mean_step_time_s": _mean(times),
        "final_loss": losses[-1] if losses else None,
        "best_loss": min(losses) if losses else None,
        "mean_mfu": _mean(mfus),
        "total_grad_sync_bytes": sum(wire) if wire else None,
        "events": sorted({e.get("event") for e in events}),
        "chaos_events": chaos_events,
        "phases": phases,
        "sync_exposed_ms": sync_exposed[-1] if sync_exposed else None,
        "sync_compare": sync_compare,
        "serve": serve,
        "serve_shed": serve_shed,
        "serve_shed_terminal": shed_terminal,
        "serve_windows": serve_windows,
        "serve_decode_host_exposed_ms": (
            host_exposed[-1] if host_exposed else None
        ),
        "serve_preempt_replays": preempt_replays,
        "serve_recovered": recovered,
        "fleet_skew": fleet_skew,
        "fleet_incidents": fleet_incidents,
        "fleet_summary": fleet_summary,
        "memory": memory,
    }


def _fmt(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("jsonl", help="path to a metrics.jsonl")
    p.add_argument("--json", action="store_true",
                   help="print the summary as one JSON object instead")
    args = p.parse_args(argv)
    summary = summarize(load_records(args.jsonl))
    if args.json:
        print(json.dumps(summary))
        return 0
    rows = [
        ("records", summary["records"]),
        ("step records", summary["step_records"]),
        ("step range", summary["step_range"]),
        ("mean step time (s)", summary["mean_step_time_s"]),
        ("final loss", summary["final_loss"]),
        ("best loss", summary["best_loss"]),
        ("mean MFU", summary["mean_mfu"]),
        ("grad sync bytes (total)", summary["total_grad_sync_bytes"]),
        ("events", ", ".join(summary["events"]) or None),
    ]
    for name, row in summary["chaos_events"].items():
        by = f" ({', '.join(row['by'])})" if row["by"] else ""
        tail = row.get("traceback_tail")
        tail = f" — {tail}" if tail else ""
        rows.append((f"chaos {name}", f"{row['count']}{by}{tail}"))
    for name, row in summary["phases"].items():
        rows.append((
            f"phase {name}",
            f"{_fmt(row['ms'])} ms ({_fmt(row['clock'])}), "
            f"{_fmt(row['flops'])} flops, {_fmt(row['comm_bytes'])} comm B, "
            f"{_fmt(row['roofline'])}",
        ))
    if summary["sync_exposed_ms"] is not None:
        rows.append(("sync exposed (ms)", summary["sync_exposed_ms"]))
    for label, row in summary["serve"].items():
        occ = row.get("slot_occupancy")
        recovered = row.get("recovered_requests")
        # Terminal-status accounting (serve/guard.py): shown whenever
        # any request ended other than plain-completed.
        statuses = ""
        if row.get("rejected") or row.get("timed_out") or row.get("recovered"):
            statuses = (
                f", done/shed/expired/recovered "
                f"{_fmt(row.get('completed'))}/{_fmt(row.get('rejected'))}/"
                f"{_fmt(row.get('timed_out'))}/{_fmt(row.get('recovered'))}"
            )
        restarts = row.get("restarts")
        rows.append((
            f"serve {label}",
            f"{_fmt(row['requests'])} reqs, TTFT p50/p99 "
            f"{_fmt(row['ttft_p50_ms'])}/{_fmt(row['ttft_p99_ms'])} ms, "
            f"ITL p50/p99 "
            f"{_fmt(row.get('itl_p50_ms'))}/{_fmt(row.get('itl_p99_ms'))} ms, "
            f"{_fmt(row['tokens_per_sec'])} tok/s, pages hw "
            f"{_fmt(row.get('page_high_water'))}, occupancy "
            f"{_fmt(round(occ, 3) if isinstance(occ, float) else occ)}"
            + (f", recovered {_fmt(recovered)}" if recovered else "")
            + statuses
            + (f", restarts {_fmt(restarts)}" if restarts else ""),
        ))
    if summary["serve_shed"]:
        by_reason = ", ".join(
            f"{k}={v}" for k, v in sorted(summary["serve_shed"].items())
        )
        rows.append((
            "serve shed",
            f"{by_reason} ({summary['serve_shed_terminal']} terminal)",
        ))
    sw = summary["serve_windows"]
    if sw:
        rows.append((
            "serve windows",
            f"{_fmt(sw['count'])} over {_fmt(sw['span_s'])} s, TTFT p99 "
            f"last/max {_fmt(sw['ttft_p99_ms_last'])}/"
            f"{_fmt(sw['ttft_p99_ms_max'])} ms, ITL p99 last/max "
            f"{_fmt(sw['itl_p99_ms_last'])}/{_fmt(sw['itl_p99_ms_max'])} ms, "
            f"pages peak {_fmt(sw['live_pages_peak'])}, queue max "
            f"{_fmt(sw['queue_depth_max'])}, preempt/s max "
            f"{_fmt(sw['preempt_rate_per_s_max'])}",
        ))
    if summary["serve_decode_host_exposed_ms"] is not None:
        rows.append((
            "serve decode host exposed (ms)",
            summary["serve_decode_host_exposed_ms"],
        ))
    if summary["serve_preempt_replays"] or summary["serve_recovered"]:
        rows.append((
            "serve chaos",
            f"{summary['serve_preempt_replays']} preemption replays, "
            f"{summary['serve_recovered']} recovered requests",
        ))
    fs = summary["fleet_summary"]
    if fs:
        rows.append((
            "fleet",
            f"generations {', '.join(f'g{g}' for g in fs['generations'] or [])}"
            f", ranks {', '.join(f'r{r}' for r in fs['ranks'] or [])}, "
            f"{_fmt(fs['steps_attributed'])} steps attributed, max skew "
            f"{_fmt(fs['max_skew_ms'])} ms, {_fmt(fs['problems'])} audit "
            f"problem(s), {_fmt(fs['torn_lines'])} torn line(s)",
        ))
    fsk = summary["fleet_skew"]
    if fsk:
        hist = ", ".join(
            f"{k}={v}" for k, v in sorted(fsk["stragglers"].items())
        )
        rows.append((
            "fleet skew",
            f"{_fmt(fsk['steps'])} post-warmup steps, mean/max "
            f"{_fmt(fsk['mean_skew_ms'])}/{_fmt(fsk['max_skew_ms'])} ms, "
            f"stragglers {hist or '-'}",
        ))
    if summary["fleet_incidents"]:
        by_event = ", ".join(
            f"{k}={v}" for k, v in sorted(summary["fleet_incidents"].items())
        )
        rows.append(("fleet incidents", by_event))
    for entry, row in summary["memory"].items():
        repl = row.get("replicated_leaves")
        rows.append((
            f"hbm {entry}",
            f"{_fmt(row['total_bytes'])} B/device "
            f"(arg {_fmt(row['argument_bytes'])}, out "
            f"{_fmt(row['output_bytes'])}, temp {_fmt(row['temp_bytes'])}) "
            f"on {_fmt(row['devices'])} dev, alias saved "
            f"{_fmt(row['alias_saved_bytes'])} B, dropped donation "
            f"{_fmt(row['dropped_donation_bytes'])} B"
            + (f", {repl} REPLICATED leaf(s)" if repl else ""),
        ))
    for wire, row in summary["sync_compare"].items():
        rows.append((
            f"overlap {wire}",
            f"step {_fmt(row['fused_step_ms'])} -> "
            f"{_fmt(row['overlap_step_ms'])} ms, sync exposed "
            f"{_fmt(row['sync_exposed_ms_fused'])} -> "
            f"{_fmt(row['sync_exposed_ms_overlap'])} ms "
            f"({_fmt(row['sync_overlap'])})",
        ))
    width = max(len(name) for name, _ in rows)
    for name, val in rows:
        print(f"{name:<{width}}  {_fmt(val)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
