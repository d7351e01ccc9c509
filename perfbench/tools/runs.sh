#!/bin/bash
# Several runs of one cell, one after another, in one chip call:
#   bash perfbench/tools/runs.sh TAG WORKLOAD SECONDS TRACE SEED...
# Each run's output and errors go to chiprun_out/TAG/; the series line,
# the result line and the harness's own log lines are echoed.
# PERFBENCH_PROBE=1 in the environment adds the control's and the faults'
# readings to every run (how the limits in perfbench/limits/ were set).
set -u
tag=$1; w=$2; secs=$3; trace=$4; shift 4
mkdir -p chiprun_out/$tag
for s in "$@"; do
  out=chiprun_out/$tag/$w.$s.t$trace
  python3 -m perfbench --workload $w --seed $s --seconds $secs --trace $trace > $out.out 2> $out.err
  echo "== $w seed=$s seconds=$secs trace=$trace rc=$?"
  tail -n 2 $out.out | cut -c1-6000
  grep "^perfbench" $out.err | tail -n 40 | cut -c1-1200
done
