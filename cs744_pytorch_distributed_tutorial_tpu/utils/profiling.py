"""Tracing/profiling — the subsystem the reference lacks (SURVEY §5.1).

The reference's only instrumentation is wall-clock deltas between
``datetime.now()`` calls printed at batch 10 (``master/part1/part1.py:39-44``),
which on an async-dispatch device measures dispatch, not compute. Here:
real profiler traces (XLA/TPU timeline viewable in TensorBoard /
Perfetto) plus named annotations that show up on the trace, layered over
``jax.profiler``.
"""

from __future__ import annotations

import contextlib
from typing import Iterator

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


def device_op_breakdown(
    fn,
    *args,
    iters: int = 3,
    top: int = 20,
    trace_dir: str | None = None,
):
    """Run ``fn(*args)`` ``iters`` times under a profiler trace and return
    per-op DEVICE time — the instrument that found the round-2 bench
    bottlenecks (``benchmarks/ablate.py``).

    Why it exists: a host timer around a sub-millisecond op measures
    dispatch, not the op; the device trace is ground truth. Works on
    CPU traces too (tests).

    Returns ``(total_ms, [(ms_per_iter, op_name), ...])`` — device-lane
    durations aggregated by op name, averaged over ``iters``, sorted
    descending.

    Thin shim over ``obs.phases.capture_device_profile`` — graftscope's
    phase profiler and this breakdown share ONE warm-up/fence/trace-parse
    path (the interval-union nesting logic lives there).
    """
    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        capture_device_profile,
    )

    prof = capture_device_profile(
        fn, *args, iters=iters, top=top, trace_dir=trace_dir
    )
    return prof.device_ms, prof.op_rows
