"""Medians and spreads of the runs `runs.sh` left under chiprun_out/TAG...

    python3 perfbench/tools/spread.py WORKLOAD chiprun_out/setA chiprun_out/setB

A spread is the distance between the first and third quartile, as
``statistics.quantiles(values, n=4)`` gives them, over the median; the
bound of a metric is about five times the wider of the two sets' spreads
(never under 1%). ``setup_s`` is shown without each set's first run when
that one compiled (over twice the median).
"""

import json
import statistics
import sys
from pathlib import Path


def read(directory: str, workload: str):
    runs = []
    for p in sorted(Path(directory).glob(f"{workload}.*.t0.out")):
        lines = p.read_text().strip().splitlines()
        if not lines or not lines[-1].startswith("{"):
            print(f"  {p.name}: no result line")
            continue
        line = json.loads(lines[-1])
        series = json.loads(lines[-2].removeprefix("perfbench series ")) if len(lines) > 1 else {}
        runs.append((p.name, line, series))
    return runs


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    workload, sets = argv[0], argv[1:]
    per_set = {}
    for d in sets:
        runs = read(d, workload)
        print(f"{d}: {len(runs)} runs, correct {[r[1]['correct'] for r in runs]}")
        for name, line, series in runs:
            m = {k: round(v["value"], 3) for k, v in line["metrics"].items()}
            print(f"  {name}: {m} max gap {series.get('gap_ms_max', 0):.1f} ms at {series.get('max_at')}"
                  f" (median {series.get('gap_ms_median', 0):.2f}), compiles in window {series.get('compiles_in_window')}")
        for metric in runs[0][1]["metrics"] if runs else []:
            values = [r[1]["metrics"][metric]["value"] for r in runs]
            if metric == "setup_s" and values[0] > 2 * statistics.median(values):
                values = values[1:]
            per_set.setdefault(metric, []).append(values)
    for metric, groups in per_set.items():
        spreads = [spread(v) for v in groups if len(v) >= 2]
        medians = [statistics.median(v) for v in groups]
        both = [x for v in groups for x in v]
        print(f"{metric}: medians {medians}; spreads {[f'{100 * s:.3f}%' for s in spreads]}; "
              f"all runs {100 * spread(both):.3f}%, min {min(both):.4f} max {max(both):.4f}; "
              f"5 x widest {100 * 5 * max(spreads):.2f}%")


if __name__ == "__main__":
    main(sys.argv[1:])
