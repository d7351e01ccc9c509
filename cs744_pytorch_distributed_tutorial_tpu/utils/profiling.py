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
import logging
import os
import re
import shutil
import tempfile
import time
from typing import Any, Callable, Iterator, Sequence

import jax

log = logging.getLogger(__name__)


def _start_quiet(log_dir: str) -> None:
    """Start the profiler with the Python tracer off: the ``annotate``
    spans and the device lines are what a capture is read for, and a
    record per Python call slows the host loop under measurement."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)


@contextlib.contextmanager
def trace(log_dir: str) -> Iterator[None]:
    """Capture a device trace for the enclosed region.

    Usage::

        with profiling.trace("/tmp/trace"):
            state, _ = trainer.train_step(state, x, y, key)
            jax.block_until_ready(state.params)

    View with TensorBoard's profile plugin or ui.perfetto.dev. Python
    frames are left out of the capture (``_start_quiet``).
    """
    _start_quiet(log_dir)
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


# ---- the train step's phases ------------------------------------------
#
# The trace names each device op by its instruction in the compiled
# module and carries no ``op_name``, so the step's ``graftscope/*``
# named scopes never reach it. The compiled module's text does carry
# them (``metadata={op_name=...}``): one map from instruction name to
# phase, built from the step's own executable, joins the two.

PHASES = ("augment", "sync", "optimizer", "telemetry", "bwd", "fwd", "unscoped")
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|collective-broadcast"
    r"|all-to-all|ragged-all-to-all)(-start|-done)?$"
)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OPCODE = re.compile(r"\s*([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_ARRAY = re.compile(r"\b([a-z]+\d*(?:e\d+m\d+\w*)?)\[([\d,]*)\]")
_STEP_PHASES: dict[str, dict[str, str]] = {}


def phase_of(op_name: str, opcode: str = "") -> str:
    """The phase of one instruction: the first rule that matches its
    ``op_name`` (or, for ``sync``, its opcode) in this order."""
    if "graftscope/input_augment" in op_name:
        return "augment"
    if "graftscope/sync" in op_name or _COLLECTIVE.match(opcode):
        return "sync"
    if "graftscope/optimizer" in op_name:
        return "optimizer"
    if "graftscope/telemetry" in op_name:
        return "telemetry"
    if "graftscope/fwd_bwd" in op_name:
        # Autodiff names the backward pass ``transpose(...)``: the
        # cotangent of every forward op, a custom_vjp's bwd rule (the
        # flash kernels' dq and dk/dv) and rematerialised forward ops.
        return "bwd" if "transpose(" in op_name else "fwd"
    return "unscoped"


def _shape_and_opcode(rest: str) -> tuple[str, str]:
    """An instruction's result shape and opcode from what follows its
    ``=``: the shape (a tuple's nested parentheses skipped), then
    ``opcode(``."""
    i = 0
    if rest.startswith("("):
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = max(rest.find(" "), 0)
    m = _OPCODE.match(rest, i)
    return rest[:i], (m.group(1) if m else "")


def _bytes(shape: str) -> int:
    """Bytes of the arrays in a result shape (``f32[3,8]{1,0}``: 96)."""
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        bits = re.search(r"\d+", dtype)
        total += n * max(int(bits.group()) // 8 if bits else 1, 1)
    return total


def phase_map(
    hlo_text: str,
    classify: Callable[[str, str], str] = phase_of,
    order: Sequence[str] = PHASES,
) -> tuple[str, dict[str, str]]:
    """(module name, {instruction name: phase}) of a compiled module's
    text (``Compiled.as_text()``), each phase ``classify(op_name,
    opcode)`` (``phase_of``, of the labels ``order``; a serving
    program's scopes use their own): every instruction of every
    computation that runs as ops of its own (the entry, while bodies and
    conditions, branches, async computations); the bodies of fusions and
    of reductions' ``to_apply`` run inside their caller and are left
    out. An async wrapper (``async-start``) takes its wrapped root's
    opcode.

    A fusion holds work of several scopes, and XLA records one of them
    on it. It takes the phase of what dominates its time: its largest
    matmul (``convolution``, ``dot``), where it holds one (the weight
    gradient into which XLA fuses the optimizer's update and the
    telemetry norm); else its root's ``op_name`` as XLA records it, and
    where the root is a tuple (a multi-output fusion: the norm fused into
    the update) its largest output's; ties go to the earlier rule."""
    module = ""
    # computation -> {instruction: (opcode, op_name, called, bytes, tuple operands)}
    comps: dict[str, dict[str, tuple[str, str, str, int, list[str]]]] = {}
    roots: dict[str, str] = {}
    inlined: set[str] = set()
    current = None
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTRUCTION.match(line)
        if m and current is not None:
            name, rest = m.groups()
            shape, opcode = _shape_and_opcode(rest)
            op = _OP_NAME.search(rest)
            calls = _CALLS.search(rest)
            called = calls.group(1) if calls else ""
            operands = _OPERAND.findall(rest.split("(", 1)[1]) if opcode == "tuple" else []
            comps[current][name] = (opcode, op.group(1) if op else "", called, _bytes(shape), operands)
            if line.lstrip().startswith("ROOT "):
                roots[current] = name
            if opcode == "fusion" and called:
                inlined.add(called)
            inlined.update(_TO_APPLY.findall(rest))
            continue
        m = _COMPUTATION.match(line)
        if m:
            current = m.group(1)
            comps[current] = {}
        elif line.strip() == "}":
            current = None

    def dominant(records) -> tuple[str, str] | None:
        """(opcode, op_name) of the largest record that has an op_name."""
        named = [r for r in records if r[1]]
        if not named:
            return None
        top = max(r[3] for r in named)
        best = min((r for r in named if r[3] == top), key=lambda r: order.index(classify(r[1], r[0])))
        return best[0], best[1]

    phases: dict[str, str] = {}
    for comp, instructions in comps.items():
        if comp in inlined:
            continue
        for name, (opcode, op_name, called, _, _) in instructions.items():
            body = comps.get(called, {})
            root = body.get(roots.get(called, ""), ("", "", "", 0, []))
            if opcode.startswith("async-") and root[0]:
                opcode = root[0]
            if opcode == "fusion":
                matmuls = [r for r in body.values() if r[0] in ("convolution", "dot")]
                members = [body[n] for n in root[4] if n in body]
                pick = dominant(matmuls) or (dominant(members) if root[0] == "tuple" else None)
                op_name = pick[1] if pick else (op_name or root[1])
            phases[name] = classify(op_name or root[1], opcode)
    return module, phases


def record_step_phases(step_fn: Callable, *args: Any) -> str:
    """Compile the jitted ``step_fn`` for ``args`` ahead of time (for a
    step the loop has run, JAX's in-memory cache answers: no backend
    compile), keep its phase map under its module's name for
    ``step_phases()``, and return that name. Lowering reads the
    arguments' types and shardings only: nothing runs, nothing is
    donated."""
    t0 = time.perf_counter()
    module, phases = phase_map(step_fn.lower(*args).compile().as_text())
    _STEP_PHASES[module] = phases
    log.info(
        "step phase map of %s: %d instructions in %.2f s",
        module, len(phases), time.perf_counter() - t0,
    )
    return module


def step_phases() -> dict[str, dict[str, str]]:
    """{module name: {instruction name: phase}} of every step a fit
    loop's capture has mapped in this process (``StepCapture``)."""
    return _STEP_PHASES


class StepCapture:
    """The profiler capture of a fit loop: steps ``[start, start + num)``
    traced to ``log_dir``, each step inside a step span named ``span``
    and each batch fetch inside ``graftscope/input_fetch``.

    Before the capture first opens, the step's phase map is built from
    its compiled module (outside the capture) and written beside it as
    ``step_phases.json`` ({module: {instruction: phase}}), so the same
    trace can be split into forward, backward, optimizer, ... A loop
    with no ``profile_dir`` makes none of this: no map, no compile.
    """

    def __init__(self, log_dir: str, start: int, num: int, span: str):
        self.log_dir, self.start, self.end, self.span = log_dir, start, start + num, span
        self.active = False
        self._mapped = False

    def open_if_due(self, step: int, step_fn: Callable, *args: Any) -> None:
        """Open the capture if ``step`` lies in its range (a resume that
        lands inside it traces the rest; ``num=0`` never opens).
        ``step_fn`` and ``args`` are the step the loop is about to run."""
        if self.active or not self.start <= step < self.end:
            return
        if not self._mapped:
            module = record_step_phases(step_fn, *args)
            os.makedirs(self.log_dir, exist_ok=True)
            with open(os.path.join(self.log_dir, "step_phases.json"), "w") as f:
                json.dump({module: _STEP_PHASES[module]}, f)
            self._mapped = True
        _start_quiet(self.log_dir)
        self.active = True

    def fetch(self):
        return annotate("graftscope/input_fetch") if self.active else contextlib.nullcontext()

    def step(self, step: int):
        return step_annotation(self.span, step) if self.active else contextlib.nullcontext()

    def close_if_done(self, step: int, fence: Any = None) -> None:
        if self.active and step + 1 >= self.end:
            self.close(fence)

    def close(self, fence: Any = None) -> None:
        """Close an open capture; ``fence`` (an output of the last traced
        step) is waited for first, so the trace holds its device work."""
        if not self.active:
            return
        if fence is not None:
            jax.block_until_ready(fence)
        jax.profiler.stop_trace()
        self.active = False


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
