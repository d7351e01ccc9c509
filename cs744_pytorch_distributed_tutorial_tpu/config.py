"""Single dataclass config for the whole framework.

Replaces the reference's scattered module-level constants and 3-flag
argparse (``--master-ip``/``--num-nodes``/``--rank`` at
``master/part2a/part2a.py:136-143``; ``batch_size`` at ``:20``; SGD
hyperparameters at ``:127-128``; seed 5000 at ``:89``; hardcoded ports
29501/29508 at ``part2a.py:83`` / ``part3.py:72``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

# compute_dtype values accepted by both trainers (engine.py, lm.py).
# Resolved lazily so importing config stays jax-free.
COMPUTE_DTYPES = ("float32", "bfloat16")


def resolve_dtype(name: str):
    import jax.numpy as jnp

    table = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
    try:
        return table[name]
    except KeyError:
        raise ValueError(
            f"unknown compute_dtype {name!r}; choose from {COMPUTE_DTYPES}"
        ) from None


@dataclasses.dataclass
class TrainConfig:
    """Everything needed to reproduce a training run.

    Defaults reproduce the reference workload: VGG-11 on CIFAR-10,
    global batch 256, SGD lr=0.1 momentum=0.9 wd=1e-4, 1 epoch,
    seed 5000 (``master/part1/part1.py:17,98-101,107``).
    """

    # Model / data
    model: str = "vgg11"
    num_classes: int = 10
    image_size: int = 32
    # ResNet stem selection: None = auto (CIFAR 3x3 stem at image_size
    # <= 64, ImageNet 7x7/stride-2 + maxpool above); True/False forces.
    # Ignored by non-ResNet models.
    imagenet_stem: bool | None = None
    # SyncBN: compute BatchNorm batch statistics ACROSS data-parallel
    # replicas (one psum per BN layer). False reproduces the reference's
    # per-replica BN (DDP default; SURVEY §7 hard part b).
    sync_bn: bool = False
    # Dropout for models that support it (the ViT family); conv models
    # follow the reference and have none.
    dropout_rate: float = 0.0
    data_root: str = "./data"
    synthetic_data: bool | None = None  # None = auto (synthetic if no local CIFAR-10)
    synthetic_train_size: int = 50_000
    synthetic_test_size: int = 10_000

    # Optimization (reference: master/part1/part1.py:98-101). The
    # reference's only recipe is fixed-LR SGD(momentum); optimizer and
    # lr_schedule are capability additions resolved by
    # train/state.py::make_optimizer. Cosine schedules need total_steps
    # (the horizon); warmup_steps linearly ramps from 0 first.
    global_batch_size: int = 256
    learning_rate: float = 0.1
    momentum: float = 0.9
    weight_decay: float = 1e-4
    epochs: int = 1
    seed: int = 5000
    optimizer: str = "sgd"  # "sgd" | "adamw" | "lion"
    lr_schedule: str = "constant"  # "constant" | "cosine" | "warmup_cosine"
    warmup_steps: int = 0
    total_steps: int | None = None  # required by cosine schedules
    # Clip the GLOBAL gradient norm (across all params, after sync) to
    # this value before the optimizer sees it; None disables. Capability
    # addition — the reference never clips.
    grad_clip_norm: float | None = None
    # Label smoothing: target distribution (1-s) one-hot + s/num_classes.
    # 0.0 reproduces the reference's plain CE (master/part1/part1.py:94).
    label_smoothing: float = 0.0
    # Train-time crop/flip augmentation (the reference's transform_train,
    # master/part1/part1.py:68-73). False trains on normalize-only inputs
    # — needed for deterministic cross-framework trajectory comparison
    # (tests/test_torch_parity.py pins the torch loss curve this way).
    augment: bool = True
    # Gradient accumulation: split each device's batch shard into this
    # many sequential microbatches (lax.scan) — one microbatch's
    # activations live at a time. BN statistics update per microbatch.
    accum_steps: int = 1

    # Parallelism
    # none|gather_scatter|p2p_star|allreduce|ring|auto|zero1|fsdp
    # |int8_allreduce|int8_ring (quantized wire formats — see grad_compress)
    sync: str = "allreduce"
    num_devices: int | None = None  # None = all visible devices
    mesh_axes: dict[str, int] | None = None  # overrides num_devices; e.g. {"data": 4}
    # Gradient compression on the sync wire (parallel/sync.py):
    # "none" ships f32; "int8" quantizes each bucket per-chunk to int8 +
    # f32 scales (~3.9x fewer gradient bytes) and carries the
    # quantization residual as per-device error feedback so compression
    # error does not bias SGD. "int8" requires sync in
    # {allreduce, ring, int8_allreduce, int8_ring}; naming an int8_*
    # sync strategy implies grad_compress="int8".
    grad_compress: str = "none"  # "none" | "int8"
    # Bucket size (MiB) for coalesced gradient sync (parallel/buckets.py):
    # allreduce/ring/zero1/fsdp issue one collective per ~this many
    # megabytes instead of one per parameter leaf (DDP's bucketing
    # reducer). 0 disables bucketing (per-leaf collectives).
    sync_bucket_mb: float = 4.0
    # Overlapped gradient sync (parallel/overlap.py, parallel/zero.py):
    # reverse-layer-order buckets whose collectives dispatch as backward
    # produces each bucket's gradients, with the optimizer applied per
    # bucket as its sync completes — DDP's reducer schedule as dataflow.
    # "bucket" overlaps the float wire: sync in {allreduce, ring} runs
    # per-bucket mean + torch-SGD apply, sync in {zero1, fsdp} runs the
    # per-bucket psum_scatter -> per-shard apply -> all_gather schedule
    # inside the sharded optimizer. "bucket+int8" overlaps the int8+EF
    # compressed wire (allreduce/ring, or zero1 where the quantization
    # chunks live on bucket boundaries; fsdp has no separate grad wire
    # to quantize). accum_steps>1 composes: only the final micro-step's
    # sync overlaps. Requires the fixed-LR SGD recipe (this engine's
    # sharded strategies already do) and no fused_optimizer.
    sync_overlap: str = "off"  # "off" | "bucket" | "bucket+int8"

    # Numerics: params/BN stats stay float32; compute dtype is the MXU knob.
    compute_dtype: str = "float32"  # "bfloat16" on real TPU runs

    # Use the Pallas fused SGD kernel (ops/fused_sgd.py) instead of the
    # optax chain; runs in interpret mode off-TPU.
    fused_optimizer: bool = False

    # Route wide stride-1 3x3 ResNet convs through the Pallas wgrad
    # kernel (ops/fused_conv.py). Off by default: in-graph measurement
    # on the v5e showed XLA's batch-minor activation layouts force
    # relayout copies around the custom call that outweigh the kernel's
    # isolated win (docs/kernels.md, layout lessons); the flag
    # exists for shapes/layouts where the kernel wins and for tests.
    fast_conv: bool = False

    # Attention implementation for the ViT family ("dense" model
    # default, or "flash" for the Pallas kernel); rejected for the conv
    # families, which have no attention.
    vit_attention: str | None = None

    # Input-pipeline prefetch depth: batches staged ahead by a background
    # thread (the DataLoader num_workers/pin_memory analog,
    # master/part1/part1.py:80-93). 0 disables.
    prefetch_depth: int = 2

    # Debug mode: stream per-replica gradient checksums to the host each
    # step and flag replica divergence (utils/debug.py — the race-detection
    # analog, SURVEY §5.2). Adds one scalar transfer per replica per step.
    debug_sync_check: bool = False

    # Logging / instrumentation (reference prints loss every 20 batches and
    # the avg per-batch time over batches 1-10: master/part1/part1.py:39-44)
    log_every: int = 20
    timing_batches: tuple[int, int] = (1, 10)  # inclusive range averaged, step 0 (compile) excluded

    # Telemetry (obs/): metrics_dir writes manifest.json + metrics.jsonl
    # (per-step loss/grad-norm/param-norm/lr/grad_sync_bytes/step-time
    # records, rank-0 on multihost). metrics_every is the emission
    # cadence in steps; 0 = piggyback on the log_every cadence, so
    # telemetry adds no host<->device fetches beyond existing logging.
    metrics_dir: str | None = None
    metrics_every: int = 0

    # Multi-host rendezvous (mirrors init_process's signature,
    # master/part2a/part2a.py:80-85; JAX derives process_id when None)
    coordinator_address: str | None = None
    num_processes: int | None = None
    process_id: int | None = None

    # Checkpointing (capability addition — the reference has none, SURVEY §5.4)
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # steps; 0 = only at end when checkpoint_dir set

    # In-memory replicated snapshots (utils/memstore.py): a second,
    # faster recovery tier above the disk checkpointer — the last
    # snapshot_keep committed TrainStates as host-RAM copies, so a
    # restart after divergence/hang restores with ZERO filesystem reads.
    # snapshot_every is the cadence in steps (0 = tier disabled); the
    # same divergence-safe pending/certify discipline as disk saves.
    snapshot_every: int = 0
    snapshot_keep: int = 2

    # Failure detection (utils/failure.py — the reference's Gloo run just
    # hangs or dies, SURVEY §5.3). halt_on_nonfinite raises
    # NonFiniteLossError when a fetched loss is NaN/inf (checked at
    # logging granularity — zero extra transfers); step_timeout_s arms a
    # host-side watchdog that logs + dumps stacks if a step hangs (the
    # first executed batch is exempt: it blocks on XLA compilation, which
    # the timing window likewise excludes). hang_action picks what the
    # watchdog does after reporting: "log" (observe only), "abort"
    # (os._exit so a supervisor — the coordination service, k8s, a shell
    # loop — restarts the process; a wedged device fetch cannot be
    # unblocked from within the process), or "escalate" (graduated:
    # first expiry warns, second adds the stack/ring/flight post-mortem,
    # third aborts — transient stalls get a chance to clear before the
    # process is killed).
    halt_on_nonfinite: bool = True
    step_timeout_s: float | None = None
    hang_action: str = "log"  # "log" | "abort" | "escalate"

    # Profiler capture (utils/profiling.py — SURVEY §5.1): when
    # profile_dir is set, fit() records an XLA device trace of
    # [profile_start_step, profile_start_step + profile_num_steps) —
    # viewable in TensorBoard's profile plugin or ui.perfetto.dev.
    # Start defaults past step 0 so compilation stays out of the trace.
    profile_dir: str | None = None
    profile_start_step: int = 10
    profile_num_steps: int = 5

    def replace(self, **kw: Any) -> "TrainConfig":
        return dataclasses.replace(self, **kw)

    @property
    def per_device_batch_size(self) -> int:
        n = self.num_devices
        if n is None:  # None = all visible devices; resolve lazily
            import jax

            n = len(jax.devices())
        if self.global_batch_size % n:
            raise ValueError(
                f"global_batch_size={self.global_batch_size} not divisible by "
                f"num_devices={n}"
            )
        return self.global_batch_size // n


# The four reference parts as config presets. Same model, same data, same
# hyperparameters, four sync mechanisms — the pedagogical gradient the
# reference builds (SURVEY §3.5). part1 is single-device batch 256
# (part1.py:17); parts 2-3 are 64/rank x 4 ranks (part2a.py:20,32).
PART_PRESETS: dict[str, dict[str, Any]] = {
    "1": dict(sync="none", num_devices=1, global_batch_size=256),
    "2a": dict(sync="gather_scatter", num_devices=4, global_batch_size=256),
    "2a_extra": dict(sync="p2p_star", num_devices=4, global_batch_size=256),
    "2b": dict(sync="allreduce", num_devices=4, global_batch_size=256),
    "3": dict(sync="auto", num_devices=4, global_batch_size=256),
}


def config_for_part(part: str, **overrides: Any) -> TrainConfig:
    """Build a config for one of the reference's parts (1, 2a, 2a_extra, 2b, 3)."""
    if part not in PART_PRESETS:
        raise ValueError(f"unknown part {part!r}; choose from {sorted(PART_PRESETS)}")
    kw = dict(PART_PRESETS[part])
    kw.update(overrides)
    return TrainConfig(**kw)
