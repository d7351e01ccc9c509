"""Render telemetry artifacts from the command line.

    python -m cs744_pytorch_distributed_tutorial_tpu.obs serve-report \\
        <trace_dir> [--check]
    python -m cs744_pytorch_distributed_tutorial_tpu.obs fleet-report \\
        <store_dir> [--check] [--no-artifacts]

``serve-report`` summarizes a graftserve trace dir (``serve_cli.py
--trace-dir``: span/window/request JSONL + the Perfetto trace);
``--check`` additionally runs the span-consistency audit (no orphan,
unclosed, or overlapping spans; span sums reconcile with recorded
TTFT) and exits 1 on any problem — the CI serve-smoke gate.

``fleet-report`` merges everything a multi-process elastic run left in
its rendezvous store (per-rank stamp/metrics streams, events.jsonl,
heartbeat/death-note/world files) into one clock-aligned view: it
prints the graftfleet report (generations, incident timeline,
collective-skew attribution), writes ``fleet_trace.json`` (merged
Perfetto timeline) + ``fleet_report.json`` beside the store, and with
``--check`` runs the incident-consistency audit (deaths pair with
re-election + re-exec, no orphan generations, no span crosses a
generation seal), exiting 1 on any problem — the CI multihost-smoke
gate.
"""

from __future__ import annotations

import argparse
import sys


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m cs744_pytorch_distributed_tutorial_tpu.obs",
        description=__doc__,
    )
    sub = p.add_subparsers(dest="cmd", required=True)
    srv = sub.add_parser(
        "serve-report", help="summarize a graftserve trace dir"
    )
    srv.add_argument(
        "path",
        help="trace dir written by serve_cli --trace-dir, or a "
             "serve_spans.jsonl",
    )
    srv.add_argument(
        "--check",
        action="store_true",
        help="exit 1 on orphan/unclosed/overlapping spans or TTFT "
             "reconciliation drift",
    )
    flt = sub.add_parser(
        "fleet-report",
        help="merge a multi-process run dir into one timeline + audit",
    )
    flt.add_argument(
        "path",
        help="rendezvous store dir (launch.py --store / "
             "GRAFT_ELASTIC_TEST_STORE run dir)",
    )
    flt.add_argument(
        "--check",
        action="store_true",
        help="exit 1 on incident-consistency problems (unpaired deaths, "
             "orphan generations, seal-crossing spans)",
    )
    flt.add_argument(
        "--no-artifacts",
        action="store_true",
        help="print the report only; skip writing fleet_trace.json / "
             "fleet_report.json",
    )
    args = p.parse_args(argv)

    if args.cmd == "fleet-report":
        from .fleet import (
            ClockAligner,
            collective_skew,
            fleet_check,
            load_fleet_dir,
            render_fleet_report,
            write_fleet_artifacts,
        )

        if args.no_artifacts:
            data = load_fleet_dir(args.path)
            aligner = ClockAligner(data.barrier_stamps)
            skew = collective_skew(data, aligner)
            problems = fleet_check(data, aligner)
            print(render_fleet_report(data, skew, problems, aligner))
        else:
            result = write_fleet_artifacts(args.path)
            problems = result["problems"]
            print(result["text"])
            print(f"fleet-report: wrote {result['trace']}")
        if args.check:
            if problems:
                for prob in problems:
                    print(f"fleet check: {prob}", file=sys.stderr)
                return 1
            print("fleet check: OK")
        return 0

    from .serve_trace import (
        check_spans,
        load_trace_dir,
        reconcile,
        render_serve_report,
    )

    data = load_trace_dir(args.path)
    print(render_serve_report(data))
    if args.check:
        problems = check_spans(data["spans"])
        problems += reconcile(data["spans"], data["requests"])
        if problems:
            for prob in problems:
                print(f"serve-trace check: {prob}", file=sys.stderr)
            return 1
        print(
            f"serve-trace check: OK ({len(data['spans'])} spans, "
            f"{len(data['requests'])} requests)"
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
