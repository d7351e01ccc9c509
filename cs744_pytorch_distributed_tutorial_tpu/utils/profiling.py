"""Tracing/profiling — the subsystem the reference lacks (SURVEY §5.1).

The reference's only instrumentation is wall-clock deltas between
``datetime.now()`` calls printed at batch 10 (``master/part1/part1.py:39-44``),
which on an async-dispatch device measures dispatch, not compute. Here:
real profiler traces (XLA/TPU timeline viewable in TensorBoard /
Perfetto) plus named annotations that show up on the trace, layered over
``jax.profiler``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import json
import os
import shutil
import tempfile
import time
from typing import Any, Callable, Iterator

import jax


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device trace for the enclosed region.

    Usage::

        with profiling.trace("/tmp/trace"):
            state, _ = trainer.train_step(state, x, y, key)
            jax.block_until_ready(state.params)

    View with TensorBoard's profile plugin or ui.perfetto.dev. Python
    frames are left out of the capture: the ``annotate`` spans and the
    device lines are what it is read for, and a record per Python call
    slows the host loop under measurement.
    """
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **fields):
    """Label a host-side region so it appears on the profiler timeline::

        with profiling.annotate("epoch-0-input"):
            batch = next(loader)

    Keyword ``fields`` (ints, floats, strings) ride on the event and come
    back through ``jax.profiler.ProfileData`` as its ``stats``; a field
    known only when the region ends is added with the returned object's
    ``set_metadata(**fields)`` before the ``with`` block closes. With no
    capture running the object records nothing (about a microsecond).
    """
    return jax.profiler.TraceAnnotation(name, **fields)


def step_annotation(name: str, step: int):
    """Step marker used by TensorBoard's per-step analysis."""
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


@dataclasses.dataclass
class DeviceProfile:
    """One timed region: device time (trace interval union), fenced host
    wall time, and the top op rows — all per iteration."""

    device_ms: float  # 0.0 when the trace shows no device lanes (CPU)
    wall_ms: float
    op_rows: list  # [(ms_per_iter, op_name), ...] descending
    iters: int

    @property
    def clock(self) -> str:
        """Which clock ``best_ms`` reports: ``"device"`` when the trace
        yielded device lanes, else the fenced ``"wall"`` fallback."""
        return "device" if self.device_ms > 0.0 else "wall"

    def best_ms(self) -> float:
        return self.device_ms if self.device_ms > 0.0 else self.wall_ms


def _parse_trace(trace_dir: str, iters: int, top: int):
    """Newest Perfetto trace under ``trace_dir`` -> (device_ms_per_iter,
    top op rows). Device total is the per-PID interval UNION of device-
    lane events: trace rows nest (a jit_ program contains its op rows)
    and XLA puts the module event and its ops on different threads of
    the same device process, so neither a flat sum nor per-(pid, tid)
    lanes would be correct."""
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz"))
    )
    if not paths:
        raise RuntimeError(f"no trace produced under {trace_dir}")
    with gzip.open(paths[-1]) as f:
        events = json.load(f)["traceEvents"]
    pids: dict[Any, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            pids[e["pid"]] = e["args"].get("name", "")
    durs: collections.Counter = collections.Counter()
    by_lane: dict = collections.defaultdict(list)
    for e in events:
        pname = pids.get(e.get("pid"), "")
        device_lane = (
            "TPU" in pname or "device" in pname.lower() or "/gpu" in pname
        )
        if e.get("ph") == "X" and e.get("dur") and device_lane:
            durs[e["name"]] += e["dur"]
            by_lane[e.get("pid")].append((e.get("ts", 0.0), e["dur"]))
    rows = sorted(
        ((v / iters / 1e3, k) for k, v in durs.items()), reverse=True
    )
    total_us = 0.0
    for lane in by_lane.values():
        # Ties sort by -dur so a parent sharing its first child's start
        # timestamp wins the top-level slot.
        lane.sort(key=lambda td: (td[0], -td[1]))
        end = float("-inf")
        for ts, dur in lane:
            if ts >= end:
                total_us += dur
                end = ts + dur
            elif ts + dur > end:
                # Overlapping but not nested (a DMA straddling a module
                # boundary): count only the tail — a true interval union.
                total_us += ts + dur - end
                end = ts + dur
    return total_us / iters / 1e3, rows[:top]


def capture_device_profile(
    fn: Callable,
    *args: Any,
    iters: int = 3,
    top: int = 20,
    trace_dir: str | None = None,
) -> DeviceProfile:
    """Run ``fn(*args)`` ``iters`` times under a profiler trace; return
    per-iteration device time, fenced host wall time, and the top op
    rows. Compiles (first call) OUTSIDE the trace; completion is fenced
    by ``jax.block_until_ready``. The one trace-capture path:
    ``device_op_breakdown`` and ``obs.serve_trace`` both call it."""
    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    jax.block_until_ready(fn(*args))  # compile + warm outside the trace
    owns_dir = trace_dir is None
    d = trace_dir or tempfile.mkdtemp(prefix="device_profile_")
    try:
        with trace(d):
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            wall_ms = (time.perf_counter() - t0) * 1e3 / iters
        device_ms, rows = _parse_trace(d, iters, top)
        return DeviceProfile(
            device_ms=device_ms, wall_ms=wall_ms, op_rows=rows, iters=iters
        )
    finally:
        if owns_dir:
            shutil.rmtree(d, ignore_errors=True)


def compiled_costs(compiled: Any) -> dict[str, float | None]:
    """``{'flops': F, 'bytes_accessed': B}`` from a compiled
    executable's ``cost_analysis()`` (per-device module costs). Handles
    both the list-of-dicts (jax 0.4.x) and plain-dict returns; absent
    keys map to None — never fabricated."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {"flops": None, "bytes_accessed": None}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {"flops": None, "bytes_accessed": None}
    flops = ca.get("flops")
    bytes_accessed = ca.get("bytes accessed", ca.get("bytes_accessed"))
    return {
        "flops": float(flops) if flops is not None else None,
        "bytes_accessed": (
            float(bytes_accessed) if bytes_accessed is not None else None
        ),
    }


def device_op_breakdown(
    fn,
    *args,
    iters: int = 3,
    top: int = 20,
    trace_dir: str | None = None,
):
    """Run ``fn(*args)`` ``iters`` times under a profiler trace and return
    per-op DEVICE time — the instrument that found the round-2 conv
    bottlenecks (``docs/kernels.md``).

    Why it exists: a host timer around a sub-millisecond op measures
    dispatch, not the op; the device trace is ground truth. Works on
    CPU traces too (tests).

    Returns ``(total_ms, [(ms_per_iter, op_name), ...])`` — device-lane
    durations aggregated by op name, averaged over ``iters``, sorted
    descending: ``capture_device_profile`` without the wall clock.
    """
    prof = capture_device_profile(
        fn, *args, iters=iters, top=top, trace_dir=trace_dir
    )
    return prof.device_ms, prof.op_rows
