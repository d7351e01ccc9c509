"""Headline benchmark: CIFAR-10 ResNet-18 training samples/sec/chip.

The driver's scored metric (BASELINE.json): ResNet-18 on CIFAR-10,
data-parallel training step, samples per second per chip. The reference
publishes no numbers (SURVEY §6) — it only *instruments* avg per-batch
wall-clock on 4-thread CPU ranks (``master/part1/part1.py:42-44``) — so
the baseline here is the value this repo established in round 1 on one
TPU v5e chip; ``vs_baseline`` tracks improvement against it.

- the step is compiled with ``xla_tpu_scoped_vmem_limit_kib=65536``
  (v5e has far more physical VMEM than the 16 MiB scoped default; the
  larger budget lets XLA pick deeper fusions), so this mode needs a TPU:
  on any other backend the compile option is refused and the run fails;
- the headline batch stays 4096 (round 1's scored point), and the
  JSON line *also* reports the batch-1024 operating point (round 1's
  baseline batch) so ``vs_baseline_b1024`` measures code, not batch.

Prints ONE JSON line, stamped with the device it ran on:
    {"metric": ..., "value": N, "unit": "samples/sec/chip", "vs_baseline": N,
     "platform": ..., "device_kind": ..., "device_count": N, ...}
"""

from __future__ import annotations

import argparse
import sys
import time

import jax

from cs744_pytorch_distributed_tutorial_tpu.obs.flops import (
    peak_flops_per_chip,
    resnet18_cifar_train_flops_per_sample,
)
from cs744_pytorch_distributed_tutorial_tpu.obs.sinks import (
    JsonlSink,
    MultiSink,
    StreamSink,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.compile_cache import (
    configure_compile_cache,
)

# Round-1 measured values on one TPU v5e chip (bf16, sync='auto'):
# 32,954.6 sps at the scored batch 4096; ~32.2k at batch 1024.
ROUND1_BASELINE_SPS = 21_700.0  # the driver's original baseline
GLOBAL_BATCH = 4096
BATCH_SMALL = 1024
WARMUP_STEPS = 10
MEASURE_STEPS = 30

# v5e: 128 MiB physical VMEM/core vs the 16 MiB scoped-allocation
# default; a 64 MiB budget admits deeper fusions for the conv+BN step.
COMPILER_OPTIONS = {"xla_tpu_scoped_vmem_limit_kib": "65536"}


def _make_sink(metrics_dir: str | None):
    """Stdout always (the driver scrapes it); a JSONL file too when
    ``--metrics-dir`` is set — bench results land in the same stream
    format as training telemetry (``obs/``)."""
    sinks = [StreamSink(sys.stdout)]
    if metrics_dir:
        import os

        os.makedirs(metrics_dir, exist_ok=True)
        sinks.append(JsonlSink(os.path.join(metrics_dir, "metrics.jsonl")))
    return MultiSink(sinks)


def _measure(trainer, state, x, y, key, steps: int) -> float:
    """Steps/sec of the compiled per-step path; each timing region is
    closed by ``jax.block_until_ready`` on the last step's state."""
    fn = trainer.train_step.lower(state, x, y, key).compile(
        compiler_options=COMPILER_OPTIONS
    )
    for _ in range(WARMUP_STEPS):
        state, _ = fn(state, x, y, key)
    jax.block_until_ready(state)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, _ = fn(state, x, y, key)
    jax.block_until_ready(state)
    return steps / (time.perf_counter() - t0)


def _bench_at(
    batch: int,
    steps: int = MEASURE_STEPS,
    sync: str = "auto",
    grad_compress: str = "none",
    sync_overlap: str = "off",
) -> tuple[float, int]:
    """(samples/sec/chip, analytic gradient-sync payload bytes sent per
    device per step) for the given sync strategy/compression/overlap."""
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.buckets import (
        sync_bytes_per_step,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    n_chips = len(jax.devices())
    cfg = TrainConfig(
        model="resnet18",
        sync=sync,
        grad_compress=grad_compress,
        sync_overlap=sync_overlap,
        num_devices=n_chips,
        global_batch_size=batch,
        compute_dtype="bfloat16",
        synthetic_data=True,
    )
    mesh = make_mesh({"data": n_chips})
    trainer = Trainer(cfg, mesh=mesh)
    state = trainer.init()
    wire = sync_bytes_per_step(
        state.params,
        "int8_allreduce" if trainer._compress else sync,
        n_chips,
        reverse=trainer._overlap,
    )
    ds = synthetic_cifar10(batch, 16, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    sps = _measure(trainer, state, x, y, key, steps) * batch
    return sps / n_chips, wire


def sync_compare(
    sink,
    batch: int = BATCH_SMALL,
    steps: int = MEASURE_STEPS,
    *,
    phase_iters: int = 3,
) -> None:
    """Bytes-on-wire mode: samples/sec/chip AND analytic gradient payload
    bytes sent per device per step, one JSON line per sync setting —
    f32 per-leaf ('auto', the DDP analog), f32 bucketed flat allreduce,
    the int8-quantized bucket allreduce with error feedback, and the
    zero1 reduce-scatter schedule (parallel/zero.py). The bucketed rows
    also carry their OVERLAPPED throughput (``--sync-overlap``,
    parallel/overlap.py / parallel/zero.py), and each overlapped wire
    gets one ``kind="sync_compare"`` record comparing fused vs
    overlapped step wall and the sync_exposed_ms each leaves on the
    table (graftscope's attribution, obs/phases.py) — so
    metrics_summary.py renders an ``overlap <wire>`` row per sharded
    strategy alongside the pure-DP ones."""
    rows = (
        ("f32_per_leaf_auto", "auto", "none", None),
        ("f32_bucketed_allreduce", "allreduce", "none", "bucket"),
        ("int8_bucketed_allreduce", "allreduce", "int8", "bucket+int8"),
        ("f32_zero1_scatter", "zero1", "none", "bucket"),
    )
    for label, sync, compress, ov in rows:
        sps, wire = _bench_at(batch, steps, sync=sync, grad_compress=compress)
        rec = {
            "kind": "bench",
            "time": time.time(),
            "metric": "cifar10_resnet18_grad_sync",
            "sync": label,
            "batch": batch,
            "samples_per_sec_per_chip": round(sps, 1),
            "grad_sync_bytes_per_step": wire,
        }
        if ov is not None:
            sps_ov, _ = _bench_at(
                batch, steps, sync=sync, grad_compress=compress,
                sync_overlap=ov,
            )
            rec["sync_overlap"] = ov
            rec["samples_per_sec_per_chip_overlap"] = round(sps_ov, 1)
        sink.emit(rec)
    for label, sync, compress, ov in rows:
        if ov is None:
            continue
        rep_f, _ = _phase_report(
            batch, model="resnet18", sync=sync, grad_compress=compress,
            compute_dtype="bfloat16", iters=phase_iters,
        )
        rep_o, _ = _phase_report(
            batch, model="resnet18", sync=sync, grad_compress=compress,
            compute_dtype="bfloat16", sync_overlap=ov, iters=phase_iters,
        )
        sink.emit(
            {
                "kind": "sync_compare",
                "time": time.time(),
                "metric": "cifar10_resnet18_sync_overlap",
                "wire": label,
                "sync_overlap": ov,
                "batch": batch,
                "fused_step_ms": round(rep_f.fused_ms, 4),
                "overlap_step_ms": round(rep_o.fused_ms, 4),
                "sync_exposed_ms_fused": round(rep_f.sync_exposed_ms, 4),
                "sync_exposed_ms_overlap": round(rep_o.sync_exposed_ms, 4),
                "parity_ok": bool(rep_f.parity_ok and rep_o.parity_ok),
            }
        )


def _phase_report(
    batch: int,
    *,
    model: str = "resnet18",
    sync: str = "auto",
    grad_compress: str = "none",
    compute_dtype: str = "bfloat16",
    sync_overlap: str = "off",
    iters: int = 3,
):
    """Build a trainer for the given sync configuration and run the
    graftscope segmented profile (obs/phases.py). Returns
    ``(PhaseReport, n_chips)``; shared by ``--phase-breakdown`` and the
    overlap comparison inside ``--sync-compare``."""
    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        profile_phases,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    n_chips = len(jax.devices())
    cfg = TrainConfig(
        model=model,
        sync=sync,
        grad_compress=grad_compress,
        sync_overlap=sync_overlap,
        num_devices=n_chips,
        global_batch_size=batch,
        compute_dtype=compute_dtype,
        synthetic_data=True,
    )
    mesh = make_mesh({"data": n_chips})
    trainer = Trainer(cfg, mesh=mesh)
    state = trainer.init()
    ds = synthetic_cifar10(batch, 16, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    return profile_phases(trainer, state, x, y, key, iters=iters), n_chips


def phase_breakdown(
    sink,
    batch: int = GLOBAL_BATCH,
    *,
    model: str = "resnet18",
    sync: str = "auto",
    grad_compress: str = "none",
    compute_dtype: str = "bfloat16",
    sync_overlap: str = "off",
    iters: int = 3,
    metrics_dir: str | None = None,
) -> bool:
    """graftscope mode (obs/phases.py): compile forward / backward /
    grad-sync / optimizer as separate fenced segments, parity-check the
    segmented step against the fused fast path, and emit per-phase
    device time, flops, bytes, MFU, roofline class, and
    ``sync_exposed_ms`` — the optimization target for the sync-overlap
    work (ROADMAP item 2). Returns parity_ok (the caller exits nonzero
    on False: attribution of a step that computes something else is
    not a benchmark)."""
    report, n_chips = _phase_report(
        batch,
        model=model,
        sync=sync,
        grad_compress=grad_compress,
        compute_dtype=compute_dtype,
        sync_overlap=sync_overlap,
        iters=iters,
    )
    now = time.time()
    for rec in report.records(run=f"bench_{model}"):
        sink.emit({**rec, "time": now})
    sink.emit(
        {
            "kind": "bench",
            "time": now,
            "metric": f"cifar10_{model}_phase_breakdown",
            # Throughput derived from the fused-step time so regress.py
            # can gate this mode with the same tolerance arithmetic as
            # the headline metric.
            "value": round(batch / (report.fused_ms / 1e3) / n_chips, 1),
            "unit": "samples/sec/chip",
            "batch": batch,
            "sync_overlap": sync_overlap,
            "sync_exposed_ms": round(report.sync_exposed_ms, 4),
            "parity_ok": report.parity_ok,
        }
    )
    print(report.table(), file=sys.stderr)
    if metrics_dir:
        import json
        import os

        with open(os.path.join(metrics_dir, "phase_report.json"), "w") as f:
            json.dump(report.records(run=f"bench_{model}"), f, indent=1)
    return report.parity_ok


def _parse_args() -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--sync-compare",
        action="store_true",
        help="report samples/sec/chip and gradient bytes-on-wire per "
        "step for f32 per-leaf / f32 bucketed / int8 bucketed sync "
        "instead of the headline benchmark",
    )
    p.add_argument(
        "--phase-breakdown",
        action="store_true",
        help="graftscope mode: per-phase (forward/backward/grad-sync/"
        "optimizer) device time, flops, bytes, MFU, roofline class, and "
        "sync_exposed_ms, with segmented-vs-fused parity checking",
    )
    p.add_argument(
        "--batch", type=int, default=GLOBAL_BATCH,
        help="global batch size for --phase-breakdown (default %(default)s)",
    )
    p.add_argument(
        "--model", default="resnet18",
        help="model for --phase-breakdown (default %(default)s)",
    )
    p.add_argument(
        "--sync", default="auto",
        help="sync strategy for --phase-breakdown (default %(default)s)",
    )
    p.add_argument(
        "--grad-compress", default="none", choices=("none", "int8"),
        help="gradient compression for --phase-breakdown",
    )
    p.add_argument(
        "--sync-overlap", default="off",
        choices=("off", "bucket", "bucket+int8"),
        help="overlapped bucket sync schedule for --phase-breakdown "
        "(parallel/overlap.py; 'bucket' needs --grad-compress none, "
        "'bucket+int8' needs --grad-compress int8)",
    )
    p.add_argument(
        "--compute-dtype", default="bfloat16",
        help="compute dtype for --phase-breakdown (default %(default)s; "
        "float32 keeps the parity check at the strict f32 tolerance)",
    )
    p.add_argument(
        "--phase-iters", type=int, default=3,
        help="timed iterations per segment for --phase-breakdown",
    )
    p.add_argument(
        "--metrics-dir",
        default=None,
        help="also append the result records to METRICS_DIR/metrics.jsonl "
        "(the training-telemetry stream format)",
    )
    p.add_argument(
        "--serve",
        nargs=argparse.REMAINDER,
        default=None,
        help="delegate to the continuous-batching serving benchmark "
        "(serve_cli, docs/serving.md): every argument AFTER --serve "
        "passes through, e.g. bench.py --serve --requests 32 --gate "
        "or bench.py --serve --trace-dir /tmp/trace --window-every "
        "0.25 (graftserve spans + SLO windows, docs/observability.md). "
        "A --metrics-dir given before --serve is forwarded.",
    )
    return p.parse_args()


def main() -> None:
    args = _parse_args()
    configure_compile_cache()
    if args.serve is not None:
        from cs744_pytorch_distributed_tutorial_tpu.serve_cli import (
            main as serve_main,
        )

        argv = list(args.serve)
        if args.metrics_dir and "--metrics-dir" not in argv:
            argv += ["--metrics-dir", args.metrics_dir]
        serve_main(argv)
        return
    sink = _make_sink(args.metrics_dir)
    try:
        if args.phase_breakdown:
            ok = phase_breakdown(
                sink,
                args.batch,
                model=args.model,
                sync=args.sync,
                grad_compress=args.grad_compress,
                compute_dtype=args.compute_dtype,
                sync_overlap=args.sync_overlap,
                iters=args.phase_iters,
                metrics_dir=args.metrics_dir,
            )
            if not ok:
                sys.exit(1)
            return
        if args.sync_compare:
            sync_compare(sink)
            return
        device = jax.devices()[0]
        peak = peak_flops_per_chip(device.device_kind)
        if peak is None:
            raise SystemExit(
                f"no peak FLOP/s on record for device_kind "
                f"{device.device_kind!r} (platform {device.platform!r}); "
                "add it to obs/flops.py with its source before scoring on it"
            )
        sps_big, wire = _bench_at(GLOBAL_BATCH)
        # Shorter steps: a longer window for the same wall time.
        sps_small, _ = _bench_at(BATCH_SMALL, steps=90)
        flops = resnet18_cifar_train_flops_per_sample()
        sink.emit(
            {
                "kind": "bench",
                "time": time.time(),
                "metric": "cifar10_resnet18_train_samples_per_sec_per_chip",
                "value": round(sps_big, 1),
                "unit": "samples/sec/chip",
                "vs_baseline": round(sps_big / ROUND1_BASELINE_SPS, 3),
                "batch": GLOBAL_BATCH,
                "value_b1024": round(sps_small, 1),
                "vs_baseline_b1024": round(sps_small / ROUND1_BASELINE_SPS, 3),
                # Model FLOPs (2*MACs, 3x-forward train convention,
                # resnet18_cifar_train_flops_per_sample) against the
                # bf16 peak of this device_kind (obs/flops.py).
                "flops_per_sample": flops,
                # Analytic gradient-sync payload bytes SENT per device
                # per step under the configured sync (0 for 'auto' on
                # one chip; parallel/buckets.py::sync_bytes_per_step).
                "grad_sync_bytes_per_step": wire,
                "mfu": round(sps_big * flops / peak, 4),
                "platform": device.platform,
                "device_kind": device.device_kind,
                "device_count": len(jax.devices()),
            }
        )
    finally:
        sink.close()


if __name__ == "__main__":
    main()
