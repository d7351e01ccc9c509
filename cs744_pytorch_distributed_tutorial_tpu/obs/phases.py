"""graftscope: per-phase step attribution for the training engines.

The fused train step is one XLA program — great for throughput (the
latency-hiding scheduler overlaps collectives with compute), useless
for attribution: nothing in a wall-clock number says how many ms/step
are forward, backward, gradient sync, or optimizer. This module builds
the missing instrument:

1. **Segmented step**: forward, forward+backward, grad-sync, and
   optimizer-apply compiled as SEPARATE jitted ``shard_map`` programs
   over the trainer's own mesh/specs, each timed under a device trace
   closed by ``block_until_ready`` (``capture_device_profile`` — the one
   trace-capture path; ``utils.profiling.device_op_breakdown`` is now a
   shim over it). Backward time is ``t(fwd+bwd) - t(fwd)``.
2. **Parity**: the segmented composition must reproduce the fused
   step's loss and post-step params within the ``test_sync_parity``
   tolerance discipline — attribution of a step that computes something
   else is worthless.
3. **Cost accounting**: per-phase flops / bytes-accessed via
   ``compiled.cost_analysis()``, per-phase MFU against the chip peak
   (``obs/flops.py``), analytic comm bytes for the sync phase
   (``parallel.sync.sync_wire_bytes`` — the TA003-audited model), and a
   compute/memory/comms roofline classification.
4. **``sync_exposed_ms``**: ``max(0, fused - (fwd+bwd + opt))`` — the
   sync time the fused step's scheduler did NOT hide behind compute.
   This is the explicit optimization target for the overlap work
   (ROADMAP item 2): overlap succeeds exactly when this goes to ~0
   while the isolated sync-segment time stays constant.

Restrictions (raise ``ValueError``, not wrong answers): segmentation
needs a separable explicit sync pass, so ``accum_steps == 1``, no
fsdp (its gradient reduction is the AD transpose of the parameter
all_gather, inserted inside backward), no fused_optimizer. zero1 IS
segmentable (fused or overlapped): the grad-sync segment runs the
per-bucket ``psum_scatter`` (or the int8+EF quantized wire) and the
optimizer segment runs the chunk updates PLUS the per-bucket delta
all_gathers — the gather is deliberately counted as optimizer time,
so ``sync_exposed_ms`` reports the unhidden scatter wire, the part
backward can hide. The LM engine additionally requires a pure
data-parallel, unsharded-optimizer layout (seq/tensor collectives
live inside the forward and cannot be carved out).
``'auto'``/``'none'`` reroute through the numerically-identical
explicit allreduce, exactly as the engine itself does under legacy
shard_map.

Segments compile with ``check_vma=False``: without the replication
analysis there are no AD-inserted collectives, so differentiating the
local loss yields purely local grads and the explicit sync segment is
the ONLY cross-device communication — which is the point.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Callable

import numpy as np

from . import flops as _flops

__all__ = [
    "PARITY_RTOL",
    "PARITY_ATOL",
    "PARITY_LOSS_RTOL",
    "DeviceProfile",
    "capture_device_profile",
    "compiled_costs",
    "roofline_classify",
    "PhaseStat",
    "PhaseReport",
    "build_cifar_segments",
    "build_lm_segments",
    "profile_phases",
    "profile_lm_phases",
    "render_phase_table",
    "phase_records_from_stream",
]

# The test_sync_parity tolerance discipline (tests/test_sync_parity.py):
# strategies must agree to float32 noise, and so must the segmented
# composition. Callers loosen these ONLY for sub-f32 compute dtypes.
PARITY_RTOL = 1e-5
PARITY_ATOL = 1e-6
PARITY_LOSS_RTOL = 1e-6

PHASE_NAMES = ("forward", "backward", "grad_sync", "optimizer")

# Ridge point (flops/byte) used by the roofline classifier when the
# device kind has no known peak pair: v5e's 197e12 / 819e9 ~= 240.
DEFAULT_RIDGE_FLOPS_PER_BYTE = 240.0


# ---------------------------------------------------------------------------
# Trace capture — THE shared path (device_op_breakdown shims onto this)
# ---------------------------------------------------------------------------




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
    import collections
    import glob
    import gzip
    import os

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
    by ``jax.block_until_ready``. The one trace-capture path shared by
    graftscope and ``utils.profiling.device_op_breakdown``."""
    import shutil
    import tempfile

    import jax

    if iters < 1:
        raise ValueError(f"iters must be >= 1, got {iters}")
    jax.block_until_ready(fn(*args))  # compile + warm outside the trace
    owns_dir = trace_dir is None
    d = trace_dir or tempfile.mkdtemp(prefix="graftscope_trace_")
    try:
        jax.profiler.start_trace(d)
        try:
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
            wall_ms = (time.perf_counter() - t0) * 1e3 / iters
        finally:
            jax.profiler.stop_trace()
        device_ms, rows = _parse_trace(d, iters, top)
        return DeviceProfile(
            device_ms=device_ms, wall_ms=wall_ms, op_rows=rows, iters=iters
        )
    finally:
        if owns_dir:
            shutil.rmtree(d, ignore_errors=True)


# ---------------------------------------------------------------------------
# Cost analysis + roofline
# ---------------------------------------------------------------------------


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


def roofline_classify(
    flops: float | None,
    bytes_accessed: float | None,
    device_kind: str | None,
    *,
    comm_bytes: float = 0.0,
) -> str:
    """'comms' | 'compute' | 'memory' | 'unknown'.

    A phase that puts bytes on the wire is comms-bound by construction
    (its time scales with the interconnect, not the roofline). Otherwise
    classify by arithmetic intensity against the chip's ridge point
    (peak_flops / peak_hbm_bw) when both peaks are known, else the
    documented v5e default ridge."""
    if comm_bytes and comm_bytes > 0:
        return "comms"
    if not flops or not bytes_accessed:
        return "unknown"
    peak_f = _flops.peak_flops_per_chip(device_kind or "")
    peak_b = _flops.peak_hbm_bytes_per_sec(device_kind or "")
    ridge = (
        peak_f / peak_b if (peak_f and peak_b) else DEFAULT_RIDGE_FLOPS_PER_BYTE
    )
    return "compute" if flops / bytes_accessed >= ridge else "memory"


# ---------------------------------------------------------------------------
# Report types
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PhaseStat:
    name: str
    device_ms: float
    wall_ms: float
    clock: str
    flops: float | None
    bytes_accessed: float | None
    comm_bytes: float
    mfu: float | None
    roofline: str

    def best_ms(self) -> float:
        return self.device_ms if self.device_ms > 0.0 else self.wall_ms


@dataclasses.dataclass
class PhaseReport:
    """The graftscope deliverable: per-phase stats + the fused-vs-
    segmented comparison, serializable as flat telemetry records."""

    phases: list[PhaseStat]
    fused_ms: float
    fused_clock: str
    segmented_total_ms: float
    sync_exposed_ms: float
    parity_ok: bool
    loss_fused: float
    loss_segmented: float
    max_param_abs_diff: float
    n_chips: int
    device_kind: str
    batch: int | None
    iters: int

    def phase(self, name: str) -> PhaseStat:
        for p in self.phases:
            if p.name == name:
                return p
        raise KeyError(name)

    def records(self, run: str = "phase") -> list[dict[str, Any]]:
        """Flat sink-ready records: one ``kind="phase"`` per phase plus
        one ``kind="phase_summary"``."""
        recs: list[dict[str, Any]] = []
        for p in self.phases:
            recs.append(
                {
                    "kind": "phase",
                    "run": run,
                    "phase": p.name,
                    "device_ms": round(p.device_ms, 4),
                    "wall_ms": round(p.wall_ms, 4),
                    "clock": p.clock,
                    "flops": p.flops,
                    "bytes_accessed": p.bytes_accessed,
                    "comm_bytes": p.comm_bytes,
                    "mfu": p.mfu,
                    "roofline": p.roofline,
                    "iters": self.iters,
                }
            )
        recs.append(
            {
                "kind": "phase_summary",
                "run": run,
                "fused_step_ms": round(self.fused_ms, 4),
                "fused_clock": self.fused_clock,
                "segmented_total_ms": round(self.segmented_total_ms, 4),
                "sync_exposed_ms": round(self.sync_exposed_ms, 4),
                "parity_ok": self.parity_ok,
                "loss_fused": self.loss_fused,
                "loss_segmented": self.loss_segmented,
                "max_param_abs_diff": self.max_param_abs_diff,
                "n_chips": self.n_chips,
                "device_kind": self.device_kind,
                "batch": self.batch,
                "iters": self.iters,
            }
        )
        return recs

    def table(self) -> str:
        return render_phase_table(self.records())


def _fmt_num(v: Any) -> str:
    if v is None:
        return "-"
    if isinstance(v, bool):
        return str(v)
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


def render_phase_table(records: list[dict[str, Any]]) -> str:
    """Render ``kind="phase"``/``kind="phase_summary"`` records (any
    mixed stream; other kinds are ignored) into the phase table — shared
    by ``python -m ...obs report``, ``bench.py --phase-breakdown`` and
    ``benchmarks/metrics_summary.py``."""
    phases = [r for r in records if r.get("kind") == "phase"]
    summaries = [r for r in records if r.get("kind") == "phase_summary"]
    if not phases and not summaries:
        return "(no phase records)"
    cols = ("phase", "ms", "clock", "flops", "bytes", "comm B", "MFU", "roofline")
    rows = [cols]
    for r in phases:
        ms = r.get("device_ms") if r.get("clock") == "device" else r.get("wall_ms")
        rows.append(
            (
                str(r.get("phase")),
                _fmt_num(ms),
                str(r.get("clock", "-")),
                _fmt_num(r.get("flops")),
                _fmt_num(r.get("bytes_accessed")),
                _fmt_num(r.get("comm_bytes")),
                _fmt_num(r.get("mfu")),
                str(r.get("roofline", "-")),
            )
        )
    widths = [max(len(row[i]) for row in rows) for i in range(len(cols))]
    lines = [
        "  ".join(cell.ljust(widths[i]) for i, cell in enumerate(row)).rstrip()
        for row in rows
    ]
    for s in summaries:
        lines.append("")
        lines.append(
            f"fused step: {_fmt_num(s.get('fused_step_ms'))} ms "
            f"({s.get('fused_clock', '-')})   segmented total: "
            f"{_fmt_num(s.get('segmented_total_ms'))} ms"
        )
        lines.append(
            f"sync_exposed_ms: {_fmt_num(s.get('sync_exposed_ms'))}   "
            f"parity_ok: {s.get('parity_ok')}   "
            f"loss fused/segmented: {_fmt_num(s.get('loss_fused'))}/"
            f"{_fmt_num(s.get('loss_segmented'))}"
        )
    return "\n".join(lines)


def phase_records_from_stream(
    records: list[dict[str, Any]],
) -> list[dict[str, Any]]:
    """Filter a telemetry stream down to the graftscope records."""
    return [
        r for r in records if r.get("kind") in ("phase", "phase_summary")
    ]


# ---------------------------------------------------------------------------
# Parity
# ---------------------------------------------------------------------------


def _check_parity(
    loss_fused: float,
    loss_segmented: float,
    params_fused: Any,
    params_segmented: Any,
    *,
    rtol: float,
    atol: float,
    loss_rtol: float,
) -> tuple[bool, float]:
    """(parity_ok, max param abs diff) under the sync-parity discipline."""
    import jax

    ok = abs(loss_fused - loss_segmented) <= max(
        loss_rtol * abs(loss_fused), 1e-12
    )
    max_diff = 0.0
    lf = jax.tree.leaves(params_fused)
    ls = jax.tree.leaves(params_segmented)
    for a, b in zip(lf, ls):
        a = np.asarray(jax.device_get(a), dtype=np.float64)
        b = np.asarray(jax.device_get(b), dtype=np.float64)
        if a.size:
            max_diff = max(max_diff, float(np.max(np.abs(a - b))))
        if not np.allclose(a, b, rtol=rtol, atol=atol):
            ok = False
    return ok, max_diff


def _parity_tols(compute_dtype: str) -> tuple[float, float, float]:
    """(rtol, atol, loss_rtol): the f32 sync-parity tolerances, loosened
    when the compute dtype rounds harder than f32 — fused and segmented
    programs fuse differently, so bf16 accumulation order differs."""
    if compute_dtype in ("float32", "f32"):
        return PARITY_RTOL, PARITY_ATOL, PARITY_LOSS_RTOL
    return 1e-2, 1e-3, 1e-2


# ---------------------------------------------------------------------------
# CIFAR engine segments
# ---------------------------------------------------------------------------


class CifarSegments:
    """The four phase programs of one CIFAR train step, plus a
    non-donating clone of the fused step for honest same-inputs timing
    (the engine's ``train_step`` donates its state and would delete the
    timing inputs on the first call)."""

    def __init__(self, trainer: Any):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        import optax

        from cs744_pytorch_distributed_tutorial_tpu.data.augment import (
            augment_train_batch,
            eval_batch,
        )
        from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
            DATA_AXIS,
        )
        from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
            sync_grads,
            sync_grads_compressed,
        )
        from cs744_pytorch_distributed_tutorial_tpu.train.engine import (
            _smoothed_xent,
        )
        from cs744_pytorch_distributed_tutorial_tpu.train.state import (
            TrainState,
        )

        from cs744_pytorch_distributed_tutorial_tpu.parallel import (
            buckets as _B,
            overlap as _OV,
        )

        cfg = trainer.cfg
        if cfg.accum_steps != 1:
            raise ValueError(
                "graftscope segmentation requires accum_steps=1: with "
                "accumulation the sync runs inside the microbatch scan and "
                "cannot be carved into its own program"
            )
        if trainer._fsdp or cfg.fused_optimizer:
            raise ValueError(
                f"graftscope segmentation does not support sync={cfg.sync!r}/"
                f"fused_optimizer={cfg.fused_optimizer}: fsdp's gradient "
                "reduction is the AD transpose of its parameter all_gather "
                "(inserted inside backward) and the fused kernel is one "
                "whole-tree Pallas call — neither has a separable sync "
                "phase. allreduce/ring/zero1 (fused or overlapped) are "
                "segmentable"
            )
        if trainer._zero1 and not (
            trainer._bucket_bytes and trainer.axis_size > 1
        ):
            raise ValueError(
                "graftscope zero1 segmentation requires the bucketed "
                "multi-device path (sync_bucket_mb > 0, num_devices > 1): "
                "the per-leaf fallback has no bucket lanes to carve"
            )
        self.trainer = trainer
        self.compress = trainer._compress
        self.overlap = getattr(trainer, "_overlap", False)
        self.zero1 = trainer._zero1
        axis_size = trainer.axis_size
        model, tx = trainer.model, trainer.tx
        bucket_bytes = trainer._bucket_bytes
        # 'auto'/'none' have no hand-traced sync pass; the explicit
        # allreduce is numerically identical (the engine itself reroutes
        # them this way under legacy shard_map).
        explicit_sync = (
            "allreduce" if cfg.sync in ("auto", "none") else cfg.sync
        )
        wire_name = (
            "int8_ring" if trainer._compress_ring else "int8_allreduce"
        )
        state_specs = trainer._state_specs()

        def local_loss_fn(state, images, labels, base_key):
            """The engine's exact key/augment/loss recipe, closed over a
            single microbatch — KEEP IN SYNC with engine.local_train_step."""
            key = jax.random.fold_in(base_key, state.step)
            key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
            x = (
                augment_train_batch(key, images)
                if cfg.augment
                else eval_batch(images)
            )
            drop_key = jax.random.fold_in(key, 7)
            local_stats = jax.tree.map(lambda a: a[0], state.batch_stats)

            def loss_fn(p):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": local_stats},
                    x,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": drop_key},
                )
                loss = _smoothed_xent(logits, labels, cfg.label_smoothing)
                return loss, mutated["batch_stats"]

            return loss_fn

        def seg_forward(state, images, labels, base_key):
            loss_fn = local_loss_fn(state, images, labels, base_key)
            local, _ = loss_fn(state.params)
            return lax.pmean(local, DATA_AXIS)

        def seg_grads(state, images, labels, base_key):
            # check_vma=False: no replication analysis, so grads come out
            # purely LOCAL (no AD-inserted psum) — the state after the
            # reference's loss.backward() and before its sync loop.
            loss_fn = local_loss_fn(state, images, labels, base_key)
            (local, new_stats), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
            return (
                lax.pmean(local, DATA_AXIS),
                jax.tree.map(lambda g: g[None], grads),
                jax.tree.map(lambda s: s[None], new_stats),
            )

        def seg_sync(grads_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            return sync_grads(
                g,
                explicit_sync,
                DATA_AXIS,
                axis_size,
                bucket_bytes=bucket_bytes,
            )

        def seg_sync_compressed(grads_stacked, ef_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            e = jax.tree.map(lambda a: a[0], ef_stacked)
            synced, ef_out = sync_grads_compressed(
                g,
                e,
                wire_name,
                DATA_AXIS,
                axis_size,
                bucket_bytes=bucket_bytes,
            )
            return synced, jax.tree.map(lambda a: a[None], ef_out)

        def seg_opt(state, synced, stats_stacked, ef_stacked):
            updates, new_opt = tx.update(
                synced, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            return TrainState(
                step=state.step + 1,
                params=new_params,
                batch_stats=stats_stacked,
                opt_state=new_opt,
                ef=ef_stacked,
            )

        # Overlapped-schedule segments (parallel/overlap.py): the same
        # reverse-order bucket layout and per-bucket kernels the fused
        # overlapped step runs, split at the sync/apply boundary. Buckets
        # are independent, so all-syncs-then-all-applies is bitwise the
        # fused interleaving; the per-bucket named scopes give the sync
        # segment's trace the same bucketNN lanes as the fused program.
        ov_name = wire_name if self.compress else explicit_sync

        def ov_layout(tree):
            return _OV.overlap_layout(
                tree,
                explicit_sync,
                axis_size,
                bucket_bytes,
                compressed=self.compress,
            )

        def seg_sync_overlap(grads_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            layout = ov_layout(g)
            bufs = _B.flatten_for_sync(g, layout)
            synced = []
            for k, buf in enumerate(bufs):
                with jax.named_scope(
                    f"graftscope/sync/overlap/{ov_name}/bucket{k:02d}"
                ):
                    synced.append(
                        _OV.sync_bucket(buf, explicit_sync, DATA_AXIS, axis_size)
                    )
            return _B.unflatten(synced, layout)

        def seg_sync_overlap_compressed(grads_stacked, ef_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            e = jax.tree.map(lambda a: a[0], ef_stacked)
            layout = ov_layout(g)
            g_bufs = _B.flatten_for_sync(g, layout)
            e_bufs = _B.flatten_for_sync(e, layout)
            synced, new_e = [], []
            for k, (gbuf, ebuf) in enumerate(zip(g_bufs, e_bufs)):
                with jax.named_scope(
                    f"graftscope/sync/overlap/{ov_name}/bucket{k:02d}"
                ):
                    s, resid = _OV.sync_bucket_compressed(
                        gbuf, ebuf, ov_name, DATA_AXIS, axis_size
                    )
                synced.append(s)
                new_e.append(resid)
            ef_out = _B.unflatten(new_e, layout)
            return (
                _B.unflatten(synced, layout),
                jax.tree.map(lambda a: a[None], ef_out),
            )

        def seg_opt_overlap(state, synced, stats_stacked, ef_stacked):
            trace, rebuild = _OV.split_momentum(state.opt_state)
            layout = ov_layout(synced)
            p_bufs = _B.flatten_for_sync(state.params, layout)
            t_bufs = _B.flatten_for_sync(trace, layout)
            s_bufs = _B.flatten_for_sync(synced, layout)
            new_p, new_t = [], []
            for k, (p, t, s) in enumerate(zip(p_bufs, t_bufs, s_bufs)):
                with jax.named_scope(
                    f"graftscope/optimizer/overlap/bucket{k:02d}"
                ):
                    pn, tn = _OV.apply_bucket(
                        p,
                        t,
                        s,
                        lr=cfg.learning_rate,
                        momentum=cfg.momentum,
                        weight_decay=cfg.weight_decay,
                    )
                new_p.append(pn)
                new_t.append(tn)
            return TrainState(
                step=state.step + 1,
                params=_B.unflatten(new_p, layout),
                batch_stats=stats_stacked,
                opt_state=rebuild(_B.unflatten(new_t, layout)),
                ef=ef_stacked,
            )

        # ZeRO-1 segments: the sharded optimizer's step carved at the
        # scatter boundary — KEEP IN SYNC with parallel/zero.py
        # Zero1SGD._apply_bucketed (same bucket layout, same chunk rule,
        # same lane names). seg_sync_zero1 runs each bucket's
        # psum_scatter (or the int8+EF quantized wire) and returns the
        # device-owned mean-gradient rows; seg_opt_zero1 runs the chunk
        # updates AND the per-bucket delta all_gathers. The gather is
        # deliberately counted as optimizer time: the scatter wire is
        # what the overlapped schedule hides under backward, so
        # sync_exposed_ms reports the UNHIDDEN scatter.
        def zero1_layout(tree):
            return _B.bucket_layout(
                tree, bucket_bytes, rows=axis_size, reverse=self.overlap
            )

        def seg_sync_zero1(grads_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            layout = zero1_layout(g)
            bufs = _B.flatten_for_sync(g, layout)
            rows = []
            for k, buf in enumerate(bufs):
                with jax.named_scope(
                    f"graftscope/sync/overlap_rs/zero1/bucket{k:02d}"
                ):
                    rows.append(
                        (
                            lax.psum_scatter(
                                buf, DATA_AXIS, scatter_dimension=0
                            )
                            / axis_size
                        )[None]
                    )
            return tuple(rows)

        def seg_sync_zero1_compressed(grads_stacked, ef_stacked):
            from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
                _int8_allreduce_flat,
            )

            g = jax.tree.map(lambda a: a[0], grads_stacked)
            e = jax.tree.map(lambda a: a[0], ef_stacked)
            layout = zero1_layout(g)
            g_bufs = _B.flatten_for_sync(g, layout)
            e_bufs = _B.flatten_for_sync(e, layout)
            idx = lax.axis_index(DATA_AXIS)
            rows, new_e = [], []
            for k, (gbuf, ebuf) in enumerate(zip(g_bufs, e_bufs)):
                cols = gbuf.shape[-1]
                with jax.named_scope(
                    f"graftscope/sync/overlap_rs/zero1/bucket{k:02d}"
                ):
                    b = gbuf.reshape(-1).astype(jnp.float32) + ebuf.reshape(
                        -1
                    ).astype(jnp.float32)
                    mean, resid = _int8_allreduce_flat(
                        b, DATA_AXIS, axis_size
                    )
                new_e.append(resid.reshape(axis_size, cols))
                rows.append(
                    lax.dynamic_index_in_dim(
                        mean.reshape(axis_size, cols).astype(gbuf.dtype),
                        idx,
                        0,
                        keepdims=True,
                    )
                )
            ef_out = _B.unflatten(new_e, layout)
            return tuple(rows), jax.tree.map(lambda a: a[None], ef_out)

        def seg_opt_zero1(state, scattered, stats_stacked, ef_stacked):
            idx = lax.axis_index(DATA_AXIS)
            leaves_p, treedef = jax.tree.flatten(state.params)
            leaves_m = jax.tree.leaves(state.opt_state)
            layout = zero1_layout(state.params)
            by_bucket = [[] for _ in layout.bucket_cols]
            for i, slot in enumerate(layout.slots):
                by_bucket[slot.bucket].append((slot.offset, i, slot))
            new_p = [None] * len(leaves_p)
            new_m = [None] * len(leaves_p)
            for k, group in enumerate(by_bucket):
                group.sort(key=lambda t: t[0])
                g_mine = scattered[k][0]
                deltas = []
                with jax.named_scope(
                    f"graftscope/optimizer/overlap/bucket{k:02d}"
                ):
                    for off, i, slot in group:
                        chunk = slot.size
                        p = leaves_p[i]
                        pad = axis_size * chunk - p.size
                        p2d = jnp.pad(p.ravel(), (0, pad)).reshape(
                            axis_size, chunk
                        )
                        p_mine = lax.dynamic_index_in_dim(
                            p2d, idx, 0, keepdims=False
                        )
                        m_new, delta_mine = tx._sgd_chunk_update(
                            p_mine,
                            leaves_m[i].reshape(chunk),
                            g_mine[off : off + chunk],
                        )
                        deltas.append(delta_mine)
                        new_m[i] = m_new.reshape(1, chunk)
                with jax.named_scope(
                    f"graftscope/sync/overlap_ag/zero1/bucket{k:02d}"
                ):
                    delta_buf = lax.all_gather(
                        jnp.concatenate(deltas), DATA_AXIS, axis=0
                    )
                for off, i, slot in group:
                    chunk = slot.size
                    p = leaves_p[i]
                    delta_flat = delta_buf[:, off : off + chunk].reshape(
                        axis_size * chunk
                    )[: p.size]
                    new_p[i] = p + delta_flat.reshape(p.shape)
            return TrainState(
                step=state.step + 1,
                params=jax.tree.unflatten(treedef, new_p),
                batch_stats=stats_stacked,
                opt_state=jax.tree.unflatten(treedef, new_m),
                ef=ef_stacked,
            )

        if self.zero1:
            seg_sync = seg_sync_zero1
            seg_sync_compressed = seg_sync_zero1_compressed
            seg_opt = seg_opt_zero1
        elif self.overlap:
            seg_sync = seg_sync_overlap
            seg_sync_compressed = seg_sync_overlap_compressed
            seg_opt = seg_opt_overlap

        def sm(f, in_specs, out_specs):
            return jax.jit(
                jax.shard_map(
                    f,
                    mesh=trainer.mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False,
                )
            )

        batch_in = (state_specs, P(DATA_AXIS), P(DATA_AXIS), P())
        self.forward = sm(seg_forward, batch_in, P())
        self.grads = sm(seg_grads, batch_in, (P(), P(DATA_AXIS), P(DATA_AXIS)))
        # zero1's sync segment yields device-OWNED rows (one [1, cols]
        # shard per bucket), not a replicated mean tree — spec them
        # sharded over data; the prefix P(DATA_AXIS) covers the whole
        # per-bucket tuple.
        synced_spec = P(DATA_AXIS) if self.zero1 else P()
        if self.compress:
            self.sync = sm(
                seg_sync_compressed,
                (P(DATA_AXIS), P(DATA_AXIS)),
                (synced_spec, P(DATA_AXIS)),
            )
        else:
            self.sync = sm(seg_sync, (P(DATA_AXIS),), synced_spec)
        self.opt = sm(
            seg_opt,
            (state_specs, synced_spec, P(DATA_AXIS), state_specs.ef),
            state_specs,
        )
        # Non-donating fused step over the SAME mapped function the
        # engine jits (train/engine.py exposes it as mapped_train).
        self.fused = jax.jit(trainer.mapped_train)

    def segmented_step(self, state, x, y, key):
        """Compose the segments into one full step: (new_state, loss)."""
        loss, g_st, stats = self.grads(state, x, y, key)
        if self.compress:
            synced, ef = self.sync(g_st, state.ef)
        else:
            synced = self.sync(g_st)
            ef = state.ef
        return self.opt(state, synced, stats, ef), loss


def build_cifar_segments(trainer: Any) -> CifarSegments:
    return CifarSegments(trainer)


# ---------------------------------------------------------------------------
# LM engine segments
# ---------------------------------------------------------------------------


class LMSegments:
    """Phase programs for the LM engine, pure data-parallel layouts
    only: seq/tensor collectives live inside the forward (ring hops,
    Megatron f/g boundaries) and cannot be carved into a sync phase."""

    def __init__(self, trainer: Any):
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        import optax

        from cs744_pytorch_distributed_tutorial_tpu.parallel import (
            buckets as _B,
            overlap as _OV,
        )
        from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
            DATA_AXIS,
        )
        from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
            sync_grads_compressed,
        )
        from cs744_pytorch_distributed_tutorial_tpu.train.lm import (
            SEQ_AXIS,
        )

        cfg = trainer.cfg
        if cfg.accum_steps != 1:
            raise ValueError(
                "graftscope segmentation requires accum_steps=1"
            )
        if trainer._zero1_opt is not None or cfg.fsdp:
            raise ValueError(
                "graftscope LM segmentation does not support zero1/fsdp: "
                "the DP reduction is fused into the sharded update (and "
                "for fsdp it is the AD transpose of the parameter "
                "all_gather). Time those schedules with the CIFAR engine's "
                "zero1 segments, or from a profile_dir trace — the "
                "overlapped schedule labels per-bucket lanes "
                "(graftscope/sync/overlap_rs/*, graftscope/optimizer/"
                "overlap/*, graftscope/sync/overlap_ag/*)"
            )
        if (
            trainer.seq_size > 1
            or getattr(trainer, "tensor_size", 1) > 1
            or getattr(trainer, "expert_parallel", False)
        ):
            raise ValueError(
                "graftscope LM segmentation requires a pure data-parallel "
                "layout (seq_parallel=1, no tensor axis, no expert "
                "parallelism): other axes' collectives run inside the "
                "forward and cannot be separated into a sync phase"
            )
        self.trainer = trainer
        self.compress = trainer._compress
        self.overlap = getattr(trainer, "_overlap", False)
        model, tx = trainer.model, trainer.tx
        data_size = trainer.data_size
        bucket_bytes = trainer._bucket_bytes
        param_specs = trainer.param_specs
        batch_spec = P(DATA_AXIS, SEQ_AXIS)
        if self.compress:
            tx_opt_specs, _ef_spec = trainer.opt_specs
        else:
            tx_opt_specs = trainer.opt_specs

        fused_xent = cfg.fused_xent
        xent_interpret = trainer._flash_interpret
        smoothing = cfg.label_smoothing
        dropout = cfg.dropout_rate
        seed = cfg.seed
        aux_coef = cfg.moe_aux_coef

        def loss_fn(p, toks, tgts, drop_key):
            """The LM engine's exact local loss — KEEP IN SYNC with
            lm._build_steps.loss_fn (same smoothing/fused-xent/MoE-aux
            objective; the monitoring-only sown metrics are dropped)."""
            apply_kw = (
                dict(rngs={"dropout": drop_key}, deterministic=False)
                if dropout > 0.0
                else {}
            )
            logits, mut = model.apply(
                {"params": p}, toks, mutable=["losses", "metrics"], **apply_kw
            )
            if fused_xent:
                from cs744_pytorch_distributed_tutorial_tpu.ops.fused_xent import (
                    fused_cross_entropy,
                )

                v = logits.shape[-1]
                ce = fused_cross_entropy(
                    logits.reshape(-1, v),
                    tgts.reshape(-1),
                    interpret=xent_interpret,
                ).mean()
            else:
                from cs744_pytorch_distributed_tutorial_tpu.train.engine import (
                    _smoothed_xent,
                )

                ce = _smoothed_xent(logits, tgts, smoothing)
            from cs744_pytorch_distributed_tutorial_tpu.models.moe import (
                moe_aux_loss,
            )

            return ce + aux_coef * moe_aux_loss(mut)

        def drop_key_for(step):
            k = jax.random.fold_in(jax.random.key(seed), step)
            k = jax.random.fold_in(k, lax.axis_index(DATA_AXIS))
            return jax.random.fold_in(k, lax.axis_index(SEQ_AXIS))

        def mean_over_replicas(x):
            return lax.pmean(lax.pmean(x, DATA_AXIS), SEQ_AXIS)

        def seg_forward(params, tokens, targets, step):
            local = loss_fn(params, tokens, targets, drop_key_for(step))
            return mean_over_replicas(local)

        def seg_grads(params, tokens, targets, step):
            local, grads = jax.value_and_grad(loss_fn)(
                params, tokens, targets, drop_key_for(step)
            )
            return (
                mean_over_replicas(local),
                jax.tree.map(lambda g: g[None], grads),
            )

        def seg_sync(grads_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            # Pure DP: sync_grad reduces to the data/seq pmean pair
            # (seq axis is 1-sized here, so that pmean is identity —
            # kept for exact numerical equivalence with the fused step).
            return jax.tree.map(
                lambda g: lax.pmean(lax.pmean(g, DATA_AXIS), SEQ_AXIS), g
            )

        def seg_sync_compressed(grads_stacked, ef_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            e = jax.tree.map(lambda a: a[0], ef_stacked)
            synced, ef_out = sync_grads_compressed(
                g,
                e,
                "int8_allreduce",
                DATA_AXIS,
                data_size,
                bucket_bytes=bucket_bytes,
            )
            return synced, jax.tree.map(lambda a: a[None], ef_out)

        def seg_opt(params, opt_state, synced):
            updates, new_opt = tx.update(synced, opt_state, params)
            return optax.apply_updates(params, updates), new_opt

        # Overlapped-schedule segments — see CifarSegments for the
        # bitwise argument (independent buckets: all-syncs-then-all-
        # applies equals the fused interleaving).
        ov_name = "int8_allreduce" if self.compress else "allreduce"

        def ov_layout(tree):
            return _OV.overlap_layout(
                tree,
                "allreduce",
                data_size,
                bucket_bytes,
                compressed=self.compress,
            )

        def seg_sync_overlap(grads_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            layout = ov_layout(g)
            bufs = _B.flatten_for_sync(g, layout)
            synced = []
            for k, buf in enumerate(bufs):
                with jax.named_scope(
                    f"graftscope/sync/overlap/{ov_name}/bucket{k:02d}"
                ):
                    synced.append(
                        _OV.sync_bucket(buf, "allreduce", DATA_AXIS, data_size)
                    )
            return _B.unflatten(synced, layout)

        def seg_sync_overlap_compressed(grads_stacked, ef_stacked):
            g = jax.tree.map(lambda a: a[0], grads_stacked)
            e = jax.tree.map(lambda a: a[0], ef_stacked)
            layout = ov_layout(g)
            g_bufs = _B.flatten_for_sync(g, layout)
            e_bufs = _B.flatten_for_sync(e, layout)
            synced, new_e = [], []
            for k, (gbuf, ebuf) in enumerate(zip(g_bufs, e_bufs)):
                with jax.named_scope(
                    f"graftscope/sync/overlap/{ov_name}/bucket{k:02d}"
                ):
                    s, resid = _OV.sync_bucket_compressed(
                        gbuf, ebuf, ov_name, DATA_AXIS, data_size
                    )
                synced.append(s)
                new_e.append(resid)
            ef_out = _B.unflatten(new_e, layout)
            return (
                _B.unflatten(synced, layout),
                jax.tree.map(lambda a: a[None], ef_out),
            )

        def seg_opt_overlap(params, opt_state, synced):
            trace, rebuild = _OV.split_momentum(opt_state)
            layout = ov_layout(synced)
            p_bufs = _B.flatten_for_sync(params, layout)
            t_bufs = _B.flatten_for_sync(trace, layout)
            s_bufs = _B.flatten_for_sync(synced, layout)
            new_p, new_t = [], []
            for k, (p, t, s) in enumerate(zip(p_bufs, t_bufs, s_bufs)):
                with jax.named_scope(
                    f"graftscope/optimizer/overlap/bucket{k:02d}"
                ):
                    pn, tn = _OV.apply_bucket(
                        p,
                        t,
                        s,
                        lr=cfg.learning_rate,
                        momentum=cfg.momentum,
                        weight_decay=cfg.weight_decay,
                    )
                new_p.append(pn)
                new_t.append(tn)
            return _B.unflatten(new_p, layout), rebuild(
                _B.unflatten(new_t, layout)
            )

        if self.overlap:
            seg_sync = seg_sync_overlap
            seg_sync_compressed = seg_sync_overlap_compressed
            seg_opt = seg_opt_overlap

        def sm(f, in_specs, out_specs):
            return jax.jit(
                jax.shard_map(
                    f,
                    mesh=trainer.mesh,
                    in_specs=in_specs,
                    out_specs=out_specs,
                    check_vma=False,
                )
            )

        batch_in = (param_specs, batch_spec, batch_spec, P())
        self.forward = sm(seg_forward, batch_in, P())
        self.grads = sm(seg_grads, batch_in, (P(), P(DATA_AXIS)))
        if self.compress:
            self.sync = sm(
                seg_sync_compressed,
                (P(DATA_AXIS), P(DATA_AXIS)),
                (P(), P(DATA_AXIS)),
            )
        else:
            self.sync = sm(seg_sync, (P(DATA_AXIS),), P())
        self.opt = sm(
            seg_opt,
            (param_specs, tx_opt_specs, P()),
            (param_specs, tx_opt_specs),
        )
        self.fused = jax.jit(trainer.mapped_train)

    def segmented_step(self, params, opt_state, x, y, step):
        """((new_params, new_opt_state), loss) — ``opt_state`` in the
        engine's own layout ((tx_state, ef) when compressed)."""
        loss, g_st = self.grads(params, x, y, step)
        if self.compress:
            tx_state, ef = opt_state
            synced, new_ef = self.sync(g_st, ef)
            new_params, new_tx = self.opt(params, tx_state, synced)
            return (new_params, (new_tx, new_ef)), loss
        synced = self.sync(g_st)
        new_params, new_tx = self.opt(params, opt_state, synced)
        return (new_params, new_tx), loss


def build_lm_segments(trainer: Any) -> LMSegments:
    return LMSegments(trainer)


# ---------------------------------------------------------------------------
# The profiler
# ---------------------------------------------------------------------------


def _aot(seg: Any, *args: Any):
    """Lower+compile a jitted segment ONCE; the compiled object serves
    both the timed executions and the cost analysis (no double compile)."""
    compiled = seg.lower(*args).compile()
    return compiled, compiled_costs(compiled)


def _sub(a: float | None, b: float | None) -> float | None:
    if a is None or b is None:
        return None
    return max(0.0, a - b)


def _phase_stat(
    name: str,
    prof: DeviceProfile,
    costs: dict[str, float | None],
    device_kind: str,
    *,
    comm_bytes: float = 0.0,
) -> PhaseStat:
    ms = prof.best_ms()
    mfu = None
    peak = _flops.peak_flops_per_chip(device_kind)
    if peak and costs["flops"] and ms > 0:
        mfu = costs["flops"] / (ms / 1e3) / peak
    return PhaseStat(
        name=name,
        device_ms=prof.device_ms,
        wall_ms=prof.wall_ms,
        clock=prof.clock,
        flops=costs["flops"],
        bytes_accessed=costs["bytes_accessed"],
        comm_bytes=comm_bytes,
        mfu=mfu,
        roofline=roofline_classify(
            costs["flops"],
            costs["bytes_accessed"],
            device_kind,
            comm_bytes=comm_bytes,
        ),
    )


def _derived_backward(
    grads_prof: DeviceProfile,
    fwd_prof: DeviceProfile,
    grads_costs: dict[str, float | None],
    fwd_costs: dict[str, float | None],
    device_kind: str,
) -> PhaseStat:
    """backward = (fwd+bwd) - fwd, per clock and per cost counter."""
    device_ms = max(0.0, grads_prof.device_ms - fwd_prof.device_ms)
    wall_ms = max(0.0, grads_prof.wall_ms - fwd_prof.wall_ms)
    costs = {
        "flops": _sub(grads_costs["flops"], fwd_costs["flops"]),
        "bytes_accessed": _sub(
            grads_costs["bytes_accessed"], fwd_costs["bytes_accessed"]
        ),
    }
    prof = DeviceProfile(
        device_ms=device_ms,
        wall_ms=wall_ms,
        op_rows=[],
        iters=grads_prof.iters,
    )
    return _phase_stat("backward", prof, costs, device_kind)


def _assemble_report(
    *,
    fwd,
    grads,
    sync,
    opt,
    fused,
    comm_bytes: float,
    parity_ok: bool,
    loss_fused: float,
    loss_segmented: float,
    max_param_abs_diff: float,
    n_chips: int,
    device_kind: str,
    batch: int | None,
    iters: int,
) -> PhaseReport:
    """(prof, costs) pairs per segment -> the PhaseReport."""
    fwd_prof, fwd_costs = fwd
    grads_prof, grads_costs = grads
    sync_prof, sync_costs = sync
    opt_prof, opt_costs = opt
    fused_prof = fused
    phases = [
        _phase_stat("forward", fwd_prof, fwd_costs, device_kind),
        _derived_backward(
            grads_prof, fwd_prof, grads_costs, fwd_costs, device_kind
        ),
        _phase_stat(
            "grad_sync",
            sync_prof,
            sync_costs,
            device_kind,
            comm_bytes=comm_bytes,
        ),
        _phase_stat("optimizer", opt_prof, opt_costs, device_kind),
    ]
    fused_ms = fused_prof.best_ms()
    segmented_total = (
        grads_prof.best_ms() + sync_prof.best_ms() + opt_prof.best_ms()
    )
    # Sync time the fused step's scheduler did NOT hide: what the fused
    # step costs beyond its comm-free work (fwd+bwd + opt). The isolated
    # sync-segment time bounds it from above on a quiet machine.
    sync_exposed = max(
        0.0, fused_ms - (grads_prof.best_ms() + opt_prof.best_ms())
    )
    return PhaseReport(
        phases=phases,
        fused_ms=fused_ms,
        fused_clock=fused_prof.clock,
        segmented_total_ms=segmented_total,
        sync_exposed_ms=sync_exposed,
        parity_ok=parity_ok,
        loss_fused=loss_fused,
        loss_segmented=loss_segmented,
        max_param_abs_diff=max_param_abs_diff,
        n_chips=n_chips,
        device_kind=device_kind,
        batch=batch,
        iters=iters,
    )


def profile_phases(
    trainer: Any,
    state: Any,
    x: Any,
    y: Any,
    key: Any,
    *,
    iters: int = 3,
    top: int = 10,
) -> PhaseReport:
    """Segment, parity-check, and time one CIFAR train step.

    ``state`` is never donated (all segment programs and the fused
    clone compile without donation), so the caller's state remains
    valid. The parity check runs first on the same inputs the timed
    iterations use."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
        sync_wire_bytes,
    )

    segs = build_cifar_segments(trainer)
    cfg = trainer.cfg
    rtol, atol, loss_rtol = _parity_tols(cfg.compute_dtype)

    new_f, m_f = segs.fused(state, x, y, key)
    new_s, loss_s = segs.segmented_step(state, x, y, key)
    loss_fused = float(m_f["loss"])
    loss_segmented = float(loss_s)
    parity_ok, max_diff = _check_parity(
        loss_fused,
        loss_segmented,
        new_f.params,
        new_s.params,
        rtol=rtol,
        atol=atol,
        loss_rtol=loss_rtol,
    )

    # Same strategy resolution the segments use, so the bytes describe
    # the sync program actually timed.
    sync_name = "allreduce" if cfg.sync in ("auto", "none") else cfg.sync
    comm_bytes = float(
        sync_wire_bytes(
            state.params,
            sync_name,
            trainer.axis_size,
            cfg.grad_compress,
            bucket_bytes=trainer._bucket_bytes,
            overlap=segs.overlap,
        )
    )
    device_kind = jax.devices()[0].device_kind
    n_chips = int(trainer.mesh.devices.size)

    fwd_c, fwd_costs = _aot(segs.forward, state, x, y, key)
    grads_c, grads_costs = _aot(segs.grads, state, x, y, key)
    loss0, g_st, stats = grads_c(state, x, y, key)
    if segs.compress:
        sync_c, sync_costs = _aot(segs.sync, g_st, state.ef)
        synced, ef = sync_c(g_st, state.ef)
        sync_args = (g_st, state.ef)
    else:
        sync_c, sync_costs = _aot(segs.sync, g_st)
        synced = sync_c(g_st)
        ef = state.ef
        sync_args = (g_st,)
    opt_c, opt_costs = _aot(segs.opt, state, synced, stats, ef)

    cap = lambda fn, *a: capture_device_profile(fn, *a, iters=iters, top=top)
    return _assemble_report(
        fwd=(cap(fwd_c, state, x, y, key), fwd_costs),
        grads=(cap(grads_c, state, x, y, key), grads_costs),
        sync=(cap(sync_c, *sync_args), sync_costs),
        opt=(cap(opt_c, state, synced, stats, ef), opt_costs),
        fused=cap(segs.fused, state, x, y, key),
        comm_bytes=comm_bytes,
        parity_ok=parity_ok,
        loss_fused=loss_fused,
        loss_segmented=loss_segmented,
        max_param_abs_diff=max_diff,
        n_chips=n_chips,
        device_kind=device_kind,
        batch=cfg.global_batch_size,
        iters=iters,
    )


def profile_lm_phases(
    trainer: Any,
    params: Any,
    opt_state: Any,
    x: Any,
    y: Any,
    *,
    iters: int = 3,
    top: int = 10,
) -> PhaseReport:
    """LM counterpart of :func:`profile_phases` (pure-DP layouts)."""
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
        sync_wire_bytes,
    )

    segs = build_lm_segments(trainer)
    cfg = trainer.cfg
    rtol, atol, loss_rtol = _parity_tols(cfg.compute_dtype)
    with jax.transfer_guard("allow"):
        step = jnp.int32(0)

    new_p, _new_o, m_f = segs.fused(params, opt_state, x, y, step)
    (p_s, _o_s), loss_s = segs.segmented_step(params, opt_state, x, y, step)
    loss_fused = float(m_f["loss"])
    loss_segmented = float(loss_s)
    parity_ok, max_diff = _check_parity(
        loss_fused,
        loss_segmented,
        new_p,
        p_s,
        rtol=rtol,
        atol=atol,
        loss_rtol=loss_rtol,
    )

    dp_strategy = "int8_allreduce" if segs.compress else "allreduce"
    comm_bytes = float(
        sync_wire_bytes(
            params,
            dp_strategy,
            trainer.data_size,
            bucket_bytes=trainer._bucket_bytes,
            overlap=segs.overlap,
        )
    )
    device_kind = jax.devices()[0].device_kind
    n_chips = int(trainer.mesh.devices.size)

    fwd_c, fwd_costs = _aot(segs.forward, params, x, y, step)
    grads_c, grads_costs = _aot(segs.grads, params, x, y, step)
    loss0, g_st = grads_c(params, x, y, step)
    if segs.compress:
        tx_state, ef = opt_state
        sync_c, sync_costs = _aot(segs.sync, g_st, ef)
        synced, _new_ef = sync_c(g_st, ef)
        sync_args = (g_st, ef)
    else:
        tx_state = opt_state
        sync_c, sync_costs = _aot(segs.sync, g_st)
        synced = sync_c(g_st)
        sync_args = (g_st,)
    opt_c, opt_costs = _aot(segs.opt, params, tx_state, synced)

    cap = lambda fn, *a: capture_device_profile(fn, *a, iters=iters, top=top)
    return _assemble_report(
        fwd=(cap(fwd_c, params, x, y, step), fwd_costs),
        grads=(cap(grads_c, params, x, y, step), grads_costs),
        sync=(cap(sync_c, *sync_args), sync_costs),
        opt=(cap(opt_c, params, tx_state, synced), opt_costs),
        fused=cap(segs.fused, params, opt_state, x, y, step),
        comm_bytes=comm_bytes,
        parity_ok=parity_ok,
        loss_fused=loss_fused,
        loss_segmented=loss_segmented,
        max_param_abs_diff=max_diff,
        n_chips=n_chips,
        device_kind=device_kind,
        batch=cfg.global_batch_size,
        iters=iters,
    )
