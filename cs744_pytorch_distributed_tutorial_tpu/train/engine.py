"""The single SPMD training engine.

The reference implements its trainer five times (part1, part2a,
part2a_extra, part2b, part3) as copy-pasted scripts differing only in the
gradient-sync section of ``train_model`` and duplicated again across
``master/`` and ``slave/`` trees (SURVEY §1). Here there is ONE engine:
a jitted ``shard_map``-ped train step over a named device mesh, with the
sync strategy plugged in (``parallel/sync.py``). Rank asymmetry lives in
collective semantics, not in parallel source trees.

Step anatomy (all traced into one XLA program — XLA's latency-hiding
scheduler overlaps the collectives with compute, which is what DDP's C++
bucketing reducer does by hand, ``master/part3/part3.py:116``):

1. on-device augmentation of the local uint8 batch shard (``data/augment``);
2. forward + loss (CrossEntropy, mean over local shard) with local
   BatchNorm batch statistics — reference DP semantics;
3. ``jax.grad`` (replaces tape autograd + ``loss.backward()``);
4. strategy-supplied gradient averaging over the ``data`` axis;
5. SGD(momentum, wd) update — replicated, since synced grads are equal.

The ``auto`` strategy is the DDP analog: the user-facing step has no
explicit communication and the engine inserts the averaging itself
(part3: ``DDP(model)`` + a comm-free train loop,
``master/part3/part3.py:34-48,116``). The manual strategies trace their
collectives explicitly per parameter, mirroring the reference's
``for p in model.parameters():`` loops.
"""

from __future__ import annotations

import contextlib
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import BatchLoader, load_cifar10
from cs744_pytorch_distributed_tutorial_tpu.data.augment import (
    augment_train_batch,
    eval_batch,
)
from cs744_pytorch_distributed_tutorial_tpu.data.prefetch import prefetch
from cs744_pytorch_distributed_tutorial_tpu.models import get_model
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    DATA_AXIS,
    device_stats_sharding,
    host_to_global,
    interpret_kernels,
    make_mesh,
    replicated,
)
from cs744_pytorch_distributed_tutorial_tpu.obs.metrics import (
    Telemetry,
    tree_l2_norm,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel import overlap as OV
from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
    UNCHECKED_REPLICATION,
    get_sync,
    sync_grads,
    sync_grads_compressed,
    sync_wire_bytes,
)
from cs744_pytorch_distributed_tutorial_tpu.train.state import (
    TrainState,
    init_state,
    make_optimizer,
    make_schedule,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.logging import get_logger
from cs744_pytorch_distributed_tutorial_tpu.utils.timing import StepTimer

from cs744_pytorch_distributed_tutorial_tpu.config import resolve_dtype


def _load_dataset(cfg: TrainConfig):
    """The config's dataset (real CIFAR-10 from disk or synthetic at the
    configured shape) — shared by fit() and evaluate_only()."""
    return load_cifar10(
        cfg.data_root,
        synthetic=cfg.synthetic_data,
        synthetic_train_size=cfg.synthetic_train_size,
        synthetic_test_size=cfg.synthetic_test_size,
        image_size=cfg.image_size,
        num_classes=cfg.num_classes,
    )


def _smoothed_xent(logits, labels, smoothing: float):
    """Mean CE against the (1-s) one-hot + s/K smoothed target. s=0 is
    exactly the reference's CrossEntropyLoss (verified vs torch)."""
    if smoothing == 0.0:
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels
        ).mean()
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    uniform = -logp.mean(axis=-1)
    return ((1.0 - smoothing) * nll + smoothing * uniform).mean()


class Trainer:
    """One engine, pluggable sync strategies (SURVEY §7 design stance)."""

    def __init__(self, cfg: TrainConfig, mesh=None, memstore=None):
        self.cfg = cfg
        if mesh is None:
            axes = cfg.mesh_axes or {DATA_AXIS: cfg.num_devices or len(jax.devices())}
            mesh = make_mesh(axes)
        self.mesh = mesh
        # In-memory snapshot tier (utils/memstore.py): passed in by
        # parallel/elastic.py::default_remesh so the snapshots survive a
        # re-mesh, else built from cfg. fit() arbitrates restore tiers
        # by step: newest wins, memory on ties (zero filesystem reads).
        if memstore is None and cfg.snapshot_every:
            from cs744_pytorch_distributed_tutorial_tpu.utils.memstore import (
                ReplicatedSnapshot,
            )

            memstore = ReplicatedSnapshot(max_to_keep=cfg.snapshot_keep)
        self.memstore = memstore
        self.axis_size = mesh.shape[DATA_AXIS]
        if cfg.sync == "none" and self.axis_size > 1:
            raise ValueError(
                "sync='none' (part1 semantics) requires a single-device data axis; "
                f"got {self.axis_size}. Pick a sync strategy or shrink the mesh."
            )
        if cfg.global_batch_size % self.axis_size:
            raise ValueError(
                f"global batch {cfg.global_batch_size} not divisible by "
                f"data-axis size {self.axis_size}"
            )
        if not 0.0 <= cfg.label_smoothing < 1.0:
            raise ValueError(
                f"label_smoothing must be in [0, 1), got {cfg.label_smoothing}"
            )
        per_device = cfg.global_batch_size // self.axis_size
        if cfg.accum_steps < 1 or per_device % cfg.accum_steps:
            raise ValueError(
                f"accum_steps {cfg.accum_steps} must divide the per-device "
                f"batch shard ({per_device})"
            )
        model_kw = {}
        if cfg.model.startswith("resnet"):
            use_imagenet_stem = (
                cfg.image_size > 64
                if cfg.imagenet_stem is None
                else cfg.imagenet_stem
            )
            model_kw["cifar_stem"] = not use_imagenet_stem
            if cfg.fast_conv:
                model_kw["fast_conv"] = True
                model_kw["kernel_interpret"] = interpret_kernels(mesh)
        elif cfg.fast_conv:
            raise ValueError(
                f"fast_conv routes ResNet 3x3 convs; {cfg.model!r} has none"
            )
        if cfg.sync_bn:
            if not (
                cfg.model.startswith(("vgg", "resnet")) or cfg.model == "tiny_cnn"
            ):
                raise ValueError(
                    f"sync_bn applies to BatchNorm models only; {cfg.model!r} "
                    "has no BN layers"
                )
            model_kw["bn_axis"] = DATA_AXIS
        if not 0.0 <= cfg.dropout_rate < 1.0:
            raise ValueError(
                f"dropout_rate must be in [0, 1), got {cfg.dropout_rate}"
            )
        if cfg.dropout_rate:
            if not cfg.model.startswith("vit"):
                raise ValueError(
                    f"dropout_rate applies to the ViT family; {cfg.model!r} "
                    "follows the reference (no dropout)"
                )
            model_kw["dropout_rate"] = cfg.dropout_rate
        if cfg.vit_attention is not None:
            if not cfg.model.startswith("vit"):
                raise ValueError(
                    f"vit_attention applies to the ViT family; {cfg.model!r} "
                    "has no attention"
                )
            if cfg.vit_attention not in ("dense", "flash"):
                raise ValueError(
                    f"vit_attention must be 'dense' or 'flash', got "
                    f"{cfg.vit_attention!r}"
                )
            if cfg.vit_attention == "flash" and cfg.sync not in (
                UNCHECKED_REPLICATION | {"none"}
            ):
                # Pallas outputs carry no vma annotation, so the flash
                # kernel cannot trace under check_vma=True — which
                # sync='auto'/'allreduce' need for the AD-inserted psum.
                raise ValueError(
                    "vit_attention='flash' requires an explicit-sync "
                    f"strategy {sorted(UNCHECKED_REPLICATION)} or 'none' "
                    f"(got sync={cfg.sync!r}: its replication analysis "
                    "cannot see through the Pallas kernel)"
                )
            model_kw["attention_impl"] = cfg.vit_attention
            model_kw["flash_interpret"] = interpret_kernels(self.mesh)
        self.model = get_model(
            cfg.model,
            num_classes=cfg.num_classes,
            dtype=resolve_dtype(cfg.compute_dtype),
            **model_kw,
        )
        self._zero1 = cfg.sync == "zero1"
        self._fsdp = cfg.sync == "fsdp"
        if (self._zero1 or self._fsdp) and cfg.fused_optimizer:
            raise ValueError(
                f"sync={cfg.sync!r} shards the optimizer state and supplies its "
                "own update; it cannot combine with fused_optimizer"
            )
        if self._zero1 or self._fsdp or cfg.fused_optimizer:
            # These paths implement the reference's fixed-LR SGD update
            # directly (parallel/zero.py, ops/fused_sgd.py); the optimizer/
            # schedule registry applies only to the optax path.
            if (
                cfg.optimizer != "sgd"
                or cfg.lr_schedule != "constant"
                or cfg.warmup_steps
                or cfg.grad_clip_norm is not None
            ):
                raise ValueError(
                    f"optimizer={cfg.optimizer!r}/lr_schedule={cfg.lr_schedule!r}/"
                    f"warmup_steps={cfg.warmup_steps}/grad_clip_norm="
                    f"{cfg.grad_clip_norm} require the default optax path; "
                    f"sync={cfg.sync!r} fused_optimizer={cfg.fused_optimizer} "
                    "hard-code unclipped SGD(momentum) at a fixed lr"
                )
        if cfg.sync_bucket_mb < 0:
            raise ValueError(
                f"sync_bucket_mb must be >= 0, got {cfg.sync_bucket_mb}"
            )
        self._bucket_bytes = int(cfg.sync_bucket_mb * 2**20)
        if self._zero1 or self._fsdp:
            from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import (
                FsdpSGD,
                Zero1SGD,
            )

            cls = FsdpSGD if self._fsdp else Zero1SGD
            self.tx = cls(
                cfg.learning_rate,
                cfg.momentum,
                cfg.weight_decay,
                DATA_AXIS,
                self.axis_size,
                bucket_bytes=self._bucket_bytes,
                # Overlapped schedule: reverse-order buckets + per-bucket
                # scatter/apply/gather lanes (validated below; an invalid
                # sync_overlap string still raises before any trace).
                overlap=cfg.sync_overlap != "off",
            )
        elif cfg.fused_optimizer:
            from cs744_pytorch_distributed_tutorial_tpu.ops.fused_sgd import FusedSGD

            self.tx = FusedSGD(
                cfg.learning_rate,
                cfg.momentum,
                cfg.weight_decay,
                interpret=interpret_kernels(self.mesh),
            )
        else:
            self.tx = make_optimizer(cfg)
        self.log = get_logger()
        self._sync_fn = get_sync(cfg.sync)
        if cfg.grad_compress not in ("none", "int8"):
            raise ValueError(
                f"unknown grad_compress {cfg.grad_compress!r}; choose "
                "'none' or 'int8'"
            )
        # Naming an int8_* sync strategy implies compression; either way
        # the engine routes the sync through sync_grads_compressed so the
        # quantization residual persists as per-device error feedback.
        self._compress = cfg.grad_compress == "int8" or cfg.sync in (
            "int8_allreduce",
            "int8_ring",
        )
        if self._compress:
            if cfg.sync == "zero1" and cfg.sync_overlap == "bucket+int8":
                # zero1's quantized wire exists only inside the overlapped
                # reduce-scatter schedule: quantization chunks and EF
                # residuals are defined on the reverse-order bucket
                # boundaries (Zero1SGD._apply_bucketed's int8 branch).
                pass
            elif cfg.sync == "fsdp":
                raise ValueError(
                    "grad_compress='int8' cannot ride sync='fsdp': its "
                    "gradient reduction IS the AD transpose of the param "
                    "all_gather (an XLA-inserted float psum_scatter), so "
                    "there is no separate grad-sync pass to quantize; for "
                    "a quantized sharded-optimizer wire use sync='zero1' "
                    "with sync_overlap='bucket+int8'"
                )
            elif cfg.sync not in (
                "allreduce",
                "ring",
                "int8_allreduce",
                "int8_ring",
            ):
                raise ValueError(
                    "grad_compress='int8' applies to the flat allreduce "
                    "syncs only (allreduce, ring, int8_allreduce, "
                    f"int8_ring) or sync='zero1' with "
                    f"sync_overlap='bucket+int8'; sync={cfg.sync!r} either "
                    "has no grad-sync pass to compress (auto/none, zero1 "
                    "without the overlapped schedule) or exists to teach "
                    "an uncompressed wire shape (gather_scatter, p2p_star)"
                )
            if cfg.fused_optimizer:
                raise ValueError(
                    "grad_compress='int8' does not compose with "
                    "fused_optimizer (the fused kernel consumes per-leaf "
                    "grads; the compressed sync hands back bucket-dequantized "
                    "leaves plus error-feedback state the kernel cannot carry)"
                )
        self._compress_ring = cfg.sync in ("ring", "int8_ring")
        if cfg.sync_overlap not in OV.OVERLAP_MODES:
            raise ValueError(
                f"unknown sync_overlap {cfg.sync_overlap!r}; choose from "
                f"{OV.OVERLAP_MODES}"
            )
        self._overlap = cfg.sync_overlap != "off"
        if self._overlap:
            if cfg.fused_optimizer:
                raise ValueError(
                    f"sync_overlap={cfg.sync_overlap!r} replaces the "
                    "tree-wide optimizer apply with per-bucket updates; "
                    "fused_optimizer supplies its own whole-tree Pallas "
                    "kernel and cannot combine"
                )
            # accum>1 composes: intermediate micro-steps stay local adds
            # (microbatch_grads skips the per-microbatch sync under
            # overlap) and only the FINAL micro-step's sync+apply runs
            # the overlapped bucket schedule.
            if (
                cfg.optimizer != "sgd"
                or cfg.lr_schedule != "constant"
                or cfg.warmup_steps
                or cfg.grad_clip_norm is not None
            ):
                raise ValueError(
                    "sync_overlap applies the reference's fixed-LR "
                    "SGD(momentum) per bucket (parallel/overlap.py); "
                    f"optimizer={cfg.optimizer!r}/lr_schedule="
                    f"{cfg.lr_schedule!r}/warmup_steps={cfg.warmup_steps}/"
                    f"grad_clip_norm={cfg.grad_clip_norm} need the tree-wide "
                    "optax path (a global clip or schedule state cannot be "
                    "applied bucket-locally)"
                )
            if cfg.sync_overlap == "bucket":
                if self._compress or cfg.sync not in (
                    "allreduce",
                    "ring",
                    "zero1",
                    "fsdp",
                ):
                    raise ValueError(
                        "sync_overlap='bucket' overlaps the float bucketed "
                        "wire: requires sync in ('allreduce', 'ring', "
                        "'zero1', 'fsdp') and grad_compress='none' (got "
                        f"sync={cfg.sync!r}, "
                        f"grad_compress={cfg.grad_compress!r}; for the "
                        "quantized wire use sync_overlap='bucket+int8')"
                    )
            elif not self._compress:
                raise ValueError(
                    "sync_overlap='bucket+int8' overlaps the int8+EF "
                    "compressed wire: requires grad_compress='int8' or an "
                    f"int8_* sync strategy (got sync={cfg.sync!r}, "
                    f"grad_compress={cfg.grad_compress!r})"
                )
        # The compressed path's all_to_all/all_gather/ppermute outputs are
        # replication-unprovable, like the explicit manual strategies.
        self._check_vma = (
            cfg.sync not in UNCHECKED_REPLICATION and not self._compress
        )
        if cfg.hang_action not in ("log", "abort", "escalate"):
            raise ValueError(
                f"unknown hang_action {cfg.hang_action!r}; choose 'log', "
                "'abort', or 'escalate'"
            )
        self.sync_monitor = None
        if cfg.debug_sync_check and self._fsdp:
            raise ValueError(
                "debug_sync_check is meaningless under sync='fsdp': params are "
                "legitimately per-device shards and the only replicated values "
                "are all_gather outputs, equal by construction — the divergence "
                "monitor could never fire. Check replication under zero1 or a "
                "replicated strategy instead."
            )
        if cfg.debug_sync_check:
            from cs744_pytorch_distributed_tutorial_tpu.utils.debug import (
                DivergenceMonitor,
            )

            self.sync_monitor = DivergenceMonitor()
        self._build_steps()

    # ------------------------------------------------------------------ build
    def _state_specs(self) -> TrainState:
        # zero1/fsdp shard their [axis_size, chunk] momentum leaves over
        # the data axis; fsdp shards the params the same way (each device
        # persists only its flat chunk — the ZeRO-3 layout). Every other
        # strategy replicates both.
        sharded = self._zero1 or self._fsdp
        return TrainState(
            step=P(),
            params=P(DATA_AXIS) if self._fsdp else P(),
            batch_stats=P(DATA_AXIS),
            opt_state=P(DATA_AXIS) if sharded else P(),
            # Error-feedback residuals are per-device (like batch_stats):
            # [num_devices, *param_shape] along the data axis. Empty
            # pytree (no leaves) when compression is off.
            ef=P(DATA_AXIS) if self._compress else P(),
        )

    def _build_steps(self) -> None:
        cfg, model, tx = self.cfg, self.model, self.tx
        axis_size, sync_fn = self.axis_size, self._sync_fn

# Whether gradient averaging is inserted by the framework (the DDP
        # analog) or traced explicitly by the plugged strategy. Key VMA
        # subtlety: under shard_map's replication analysis, differentiating
        # a device-varying loss w.r.t. *replicated* (unvarying) params makes
        # the autodiff transpose insert a psum automatically — grads arrive
        # already globally reduced. The two paths map exactly onto the
        # reference's pedagogy:
        #  - 'auto' (part3/DDP): differentiate the pmean'd global loss and
        #    let the AD transpose insert the collective — communication the
        #    user never writes, exactly DDP's contract
        #    (master/part3/part3.py:34-48,116). 'none' (part1) rides the
        #    same path on a 1-sized axis, where pmean is a no-op.
        #  - manual strategies (part2a/2a_extra/2b): pcast params to
        #    device-varying first, so grads come out purely LOCAL (the state
        #    after the reference's loss.backward() and before its sync
        #    loop), then the strategy's explicit collectives average them.
        framework_inserted_sync = cfg.sync in ("auto", "none")

        # fsdp needs the ORIGINAL param shapes to unshard its flat chunks
        # (zero.py FsdpSGD.gather_params); abstract init gives them without
        # materializing a full replica.
        param_shapes = None
        if self._fsdp:
            sample = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
            param_shapes = jax.eval_shape(
                lambda: model.init(jax.random.key(0), sample, train=False)
            )["params"]

        accum = cfg.accum_steps

        def microbatch_grads(params, local_stats, x, labels, drop_key):
            """One fwd/bwd on an (augmented) local microbatch under the
            configured sync strategy: (loss, local_loss, grads, stats)."""

            def local_loss_fn(p):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": local_stats},
                    x,
                    train=True,
                    mutable=["batch_stats"],
                    rngs={"dropout": drop_key},
                )
                loss = _smoothed_xent(logits, labels, cfg.label_smoothing)
                return loss, mutated["batch_stats"]

            if self._fsdp:
                # Differentiate THROUGH the all_gather unshard: grads come
                # out as [1, chunk] cotangents, already reduce-scattered by
                # the all_gather transpose (zero.py FsdpSGD docstring).
                (local_loss, new_stats), grads = jax.value_and_grad(
                    lambda sh: local_loss_fn(tx.gather_params(sh, param_shapes)),
                    has_aux=True,
                )(params)
                loss = lax.pmean(local_loss, DATA_AXIS)
            elif framework_inserted_sync:

                def global_loss_fn(p):
                    local, new_stats = local_loss_fn(p)
                    return lax.pmean(local, DATA_AXIS), (local, new_stats)

                (loss, (local_loss, new_stats)), grads = jax.value_and_grad(
                    global_loss_fn, has_aux=True
                )(params)
            else:
                params_local = jax.tree.map(
                    lambda p: lax.pcast(p, DATA_AXIS, to="varying"), params
                )
                (local_loss, new_stats), grads = jax.value_and_grad(
                    local_loss_fn, has_aux=True
                )(params_local)
                if not self._compress and not self._overlap:
                    grads = sync_grads(
                        grads,
                        cfg.sync,
                        DATA_AXIS,
                        axis_size,
                        bucket_bytes=self._bucket_bytes,
                    )
                # Overlapped sync happens in local_train_step: each
                # reverse-order bucket's collective AND its slice of the
                # SGD update chain off only that bucket's gradients, so
                # grads must leave here LOCAL (parallel/overlap.py).
                # Compressed sync happens ONCE per step, after gradient
                # accumulation (local_train_step): quantizing each
                # microbatch separately would decouple the error-feedback
                # residual from what was actually transmitted.
                loss = lax.pmean(local_loss, DATA_AXIS)
            return loss, local_loss, grads, new_stats

        def local_train_step(state: TrainState, images, labels, base_key):
            # Per-device, per-step augmentation randomness: fold the run key
            # with the step and the replica index (the DistributedSampler
            # seed-discipline analog, master/part2a/part2a.py:89-90).
            key = jax.random.fold_in(base_key, state.step)
            key = jax.random.fold_in(key, lax.axis_index(DATA_AXIS))
            # graftscope named_scopes: pure HLO metadata that labels the
            # fused step's regions in Perfetto captures (no jaxpr eqns —
            # graftlint/graftcheck see nothing).
            with jax.named_scope("graftscope/input_augment"):
                x = (
                    augment_train_batch(key, images)
                    if cfg.augment
                    else eval_batch(images)
                )
            drop_key = jax.random.fold_in(key, 7)

            local_stats = jax.tree.map(lambda a: a[0], state.batch_stats)

            if accum == 1:
                with jax.named_scope("graftscope/fwd_bwd"):
                    loss, local_loss, grads, new_stats = microbatch_grads(
                        state.params, local_stats, x, labels, drop_key
                    )
            else:
                # Gradient accumulation: scan over microbatches — only ONE
                # microbatch's activations are live at a time; grad sums
                # average into the identical-global-batch gradient (up to
                # summation order). BatchNorm statistics update per
                # MICROBATCH (sequentially, torch-accumulation semantics),
                # so BN models' trajectories legitimately differ from the
                # unaccumulated step; BN-free models match exactly.
                xm = x.reshape(accum, -1, *x.shape[1:])
                ym = labels.reshape(accum, -1)
                mb_keys = jax.random.split(drop_key, accum)

                def body(carry, mb):
                    g_sum, l_sum, ll_sum, stats = carry
                    with jax.named_scope("graftscope/fwd_bwd"):
                        loss, ll, g, stats = microbatch_grads(
                            state.params, stats, mb[0], mb[1], mb[2]
                        )
                    return (
                        jax.tree.map(jnp.add, g_sum, g),
                        l_sum + loss.astype(jnp.float32),
                        ll_sum + ll.astype(jnp.float32),
                        stats,
                    ), None

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, p.dtype), state.params
                )
                zero = jnp.zeros((), jnp.float32)
                # local_loss is device-varying; its accumulator's initial
                # value must carry the same varying-axes type under
                # shard_map's replication analysis.
                zero_var = lax.pcast(zero, DATA_AXIS, to="varying")
                (g_sum, l_sum, ll_sum, new_stats), _ = lax.scan(
                    body, (zeros, zero, zero_var, local_stats), (xm, ym, mb_keys)
                )
                grads = jax.tree.map(lambda g: g / accum, g_sum)
                loss = l_sum / accum
                local_loss = ll_sum / accum

            new_ef = state.ef
            if self._compress and not self._overlap:
                # Quantized all-reduce of the ACCUMULATED local gradient,
                # with this device's untransmitted residual added before
                # quantization and the new residual carried to next step.
                # Global-norm clipping still sees the dequantized mean:
                # make_optimizer chains clip_by_global_norm ahead of the
                # optimizer, downstream of this sync.
                ef_local = jax.tree.map(lambda a: a[0], state.ef)
                grads, ef_out = sync_grads_compressed(
                    grads,
                    ef_local,
                    "int8_ring" if self._compress_ring else "int8_allreduce",
                    DATA_AXIS,
                    axis_size,
                    bucket_bytes=self._bucket_bytes,
                )
                new_ef = jax.tree.map(lambda a: a[None], ef_out)

            if self._overlap and not (self._zero1 or self._fsdp):
                # Overlapped bucket pipeline: per-bucket collective +
                # per-bucket SGD apply over reverse-order buckets — no
                # tree-wide barrier between backward, sync, and apply, so
                # XLA schedules bucket k's collective under the remaining
                # backward and bucket k-1's optimizer math. Bitwise-equal
                # to the fused sync+optax chain for allreduce/ring
                # (tests/test_sync_parity.py); int8 holds the trajectory
                # bar. grads comes back as the synced mean (telemetry).
                # (zero1/fsdp overlap rides INSIDE tx.apply/gather_params
                # below: the per-bucket scatter->apply->gather schedule.)
                ef_local = (
                    jax.tree.map(lambda a: a[0], state.ef)
                    if self._compress
                    else None
                )
                trace, rebuild = OV.split_momentum(state.opt_state)
                wire = (
                    ("int8_ring" if self._compress_ring else "int8_allreduce")
                    if self._compress
                    else cfg.sync
                )
                new_params, new_trace, grads, ef_out = OV.overlapped_sync_apply(
                    grads,
                    state.params,
                    trace,
                    name=wire,
                    axis_name=DATA_AXIS,
                    axis_size=axis_size,
                    lr=cfg.learning_rate,
                    momentum=cfg.momentum,
                    weight_decay=cfg.weight_decay,
                    bucket_bytes=self._bucket_bytes,
                    ef=ef_local,
                )
                new_opt = rebuild(new_trace)
                if self._compress:
                    new_ef = jax.tree.map(lambda a: a[None], ef_out)
            elif self._zero1 or self._fsdp or cfg.fused_optimizer:
                # Under zero1 the grads are still LOCAL here: Zero1SGD
                # fuses the averaging (reduce-scatter) into its sharded
                # update and returns replicated params + the local
                # momentum chunk. Under fsdp grads are the already-
                # scattered [1, chunk] sums and the update stays chunk-wise.
                # With overlap the apply emits its own per-bucket
                # scatter/apply/gather lanes, so the tree-wide optimizer
                # scope would mislabel them — skip it there.
                scope = (
                    contextlib.nullcontext()
                    if self._overlap
                    else jax.named_scope("graftscope/optimizer")
                )
                with scope:
                    if self._compress and self._zero1:
                        # zero1's int8+EF wire: residuals thread through
                        # the bucketed apply (quantization chunks live on
                        # bucket boundaries), one residual tree per device.
                        ef_local = jax.tree.map(lambda a: a[0], state.ef)
                        new_params, new_opt, ef_out = tx.apply(
                            state.params, state.opt_state, grads, ef=ef_local
                        )
                        new_ef = jax.tree.map(lambda a: a[None], ef_out)
                    else:
                        new_params, new_opt = tx.apply(
                            state.params, state.opt_state, grads
                        )
            else:
                with jax.named_scope("graftscope/optimizer"):
                    updates, new_opt = tx.update(
                        grads, state.opt_state, state.params
                    )
                    new_params = optax.apply_updates(state.params, updates)
            if self.sync_monitor is not None:
                from cs744_pytorch_distributed_tutorial_tpu.utils.debug import (
                    tree_checksum,
                )

                # The replication invariant to verify host-side: post-sync
                # grads everywhere — except zero1, which never materializes
                # synced grads, so check the post-all_gather params instead.
                # (fsdp is rejected at construction: it has no replicated
                # state whose divergence the monitor could catch.)
                jax.debug.callback(
                    self.sync_monitor.callback,
                    state.step,
                    lax.axis_index(DATA_AXIS),
                    tree_checksum(new_params if self._zero1 else grads),
                )
            metrics = {
                "loss": loss,  # global mean for logging
                "local_loss": local_loss[None],  # [1]/replica -> [axis_size]
            }
            if obs_norms:
                # Telemetry scalars, computed ON DEVICE where the trees
                # already live; the host sees them only at the logging-
                # cadence fetch. grads here are the post-sync (globally
                # averaged) gradients, so the norm is the true global
                # gradient norm; new_params are replicated.
                with jax.named_scope("graftscope/telemetry"):
                    metrics["grad_norm"] = tree_l2_norm(grads)
                    metrics["param_norm"] = tree_l2_norm(new_params)
            new_state = TrainState(
                step=state.step + 1,
                params=new_params,
                batch_stats=jax.tree.map(lambda a: a[None], new_stats),
                opt_state=new_opt,
                ef=new_ef,
            )
            return new_state, metrics

        # zero1/fsdp never materialize the synced gradient tree (the
        # averaging is fused into the sharded update), so a global grad/
        # param norm would be either wrong or an extra collective — those
        # layouts omit the norm metrics rather than fabricate them.
        obs_norms = not (self._zero1 or self._fsdp)
        self._obs_norms = obs_norms

        state_specs = self._state_specs()
        metric_specs = {"loss": P(), "local_loss": P(DATA_AXIS)}
        if obs_norms:
            metric_specs.update({"grad_norm": P(), "param_norm": P()})

        mapped_train = jax.shard_map(
            local_train_step,
            mesh=self.mesh,
            in_specs=(state_specs, P(DATA_AXIS), P(DATA_AXIS), P()),
            out_specs=(state_specs, metric_specs),
            check_vma=self._check_vma,
        )
        # Un-jitted, un-donated handle for instrumentation: re-jit it
        # WITHOUT donation and repeated calls on the same state don't
        # hit deleted buffers.
        self.mapped_train = mapped_train
        self.train_step = jax.jit(mapped_train, donate_argnums=0)

        def local_train_scan(state: TrainState, images, labels, base_key):
            """Many steps in ONE traced program: ``lax.scan`` over a
            leading ``[num_steps, ...]`` axis of device-resident batches.

            The reference's epoch loop crosses host<->device (and, in
            parts 2-3, the network stack) every batch
            (``master/part1/part1.py:31-38``); here the whole span is a
            single XLA computation — zero per-step dispatch, and the
            latency-hiding scheduler pipelines step N's collectives with
            step N+1's compute across iterations. Per-step randomness
            still advances: ``local_train_step`` folds the key with
            ``state.step``, which increments inside the scan body."""

            def body(st, xy):
                return local_train_step(st, xy[0], xy[1], base_key)

            return lax.scan(body, state, (images, labels))

        scan_metric_specs = {"loss": P(), "local_loss": P(None, DATA_AXIS)}
        if obs_norms:
            scan_metric_specs.update({"grad_norm": P(), "param_norm": P()})
        mapped_scan = jax.shard_map(
            local_train_scan,
            mesh=self.mesh,
            in_specs=(state_specs, P(None, DATA_AXIS), P(None, DATA_AXIS), P()),
            out_specs=(state_specs, scan_metric_specs),
            check_vma=self._check_vma,
        )
        self.train_steps = jax.jit(mapped_scan, donate_argnums=0)

        def local_eval_step(state: TrainState, images, labels, mask):
            """Eval on the local shard with the replica's own running BN
            stats; loss/correct counts reduced with psum — the working
            version of the reference's dead ``isend`` of ``correct`` to
            rank 0 that master never receives
            (``slave/part2b/part2b.py:67-69``, SURVEY §2.1 #6). ``mask``
            (1.0 real / 0.0 padding) keeps batch shapes static on any
            mesh while counting each test example exactly once."""
            local_stats = jax.tree.map(lambda a: a[0], state.batch_stats)
            params = (
                tx.gather_params(state.params, param_shapes)
                if self._fsdp
                else state.params
            )
            logits = model.apply(
                {"params": params, "batch_stats": local_stats},
                eval_batch(images),
                train=False,
            )
            losses = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
            correct = ((jnp.argmax(logits, axis=-1) == labels) * mask).sum()
            return {
                "loss_sum": lax.psum((losses * mask).sum(), DATA_AXIS),
                "correct": lax.psum(correct, DATA_AXIS),
                "count": lax.psum(mask.sum(), DATA_AXIS),
            }

        mapped_eval = jax.shard_map(
            local_eval_step,
            mesh=self.mesh,
            in_specs=(state_specs, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs={"loss_sum": P(), "correct": P(), "count": P()},
            check_vma=self._check_vma,
        )
        self.eval_step = jax.jit(mapped_eval)

    # ------------------------------------------------------------------ state
    def init(self, seed: int | None = None) -> TrainState:
        cfg = self.cfg
        # One-time setup: eager zeros/key creation transfers host
        # scalars, which an outer transfer_guard("disallow") would
        # reject. Scope "allow" here; the guard discipline is for the
        # steady-state step path.
        with jax.transfer_guard("allow"):
            return self._init_impl(cfg, seed)

    def _init_impl(self, cfg, seed) -> TrainState:
        rng = jax.random.key(cfg.seed if seed is None else seed)
        sample = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
        state = init_state(self.model, self.tx, rng, sample, self.axis_size)
        if self._fsdp:
            # The full replica existed only for initialization; persist the
            # [axis_size, chunk] flat shards (ZeRO-3's memory contract).
            state = state.replace(params=self.tx.shard_params(state.params))
        if self._compress:
            # Error feedback starts at zero: step 0's quantization residual
            # is the first thing fed back. f32 regardless of param dtype —
            # the residual must represent values below the int8 step size.
            state = state.replace(
                ef=jax.tree.map(
                    lambda p: jnp.zeros(
                        (self.axis_size, *p.shape), jnp.float32
                    ),
                    state.params,
                )
            )
        return self.place_state(state)

    def place_state(self, state: TrainState) -> TrainState:
        """Lay the state out on the mesh: replicated params, per-replica
        BN stats along the data axis; opt state replicated — except under
        zero1, whose momentum chunks shard over the data axis, and fsdp,
        where params AND momentum live as data-axis-sharded flat chunks.
        Multi-host safe: placement routes through ``host_to_global``."""
        rep = replicated(self.mesh)
        dev = device_stats_sharding(self.mesh)
        sharded_opt = self._zero1 or self._fsdp
        return TrainState(
            step=host_to_global(state.step, rep),
            params=host_to_global(state.params, dev if self._fsdp else rep),
            batch_stats=host_to_global(state.batch_stats, dev),
            opt_state=host_to_global(
                state.opt_state, dev if sharded_opt else rep
            ),
            # ef leaves are [num_devices, ...] like batch_stats; an empty
            # tree (compression off) passes through host_to_global unchanged.
            ef=host_to_global(state.ef, dev),
        )

    # ------------------------------------------------------------------ loops
    def fit(
        self,
        dataset=None,
        state: TrainState | None = None,
        epochs: int | None = None,
    ) -> tuple[TrainState, dict[str, Any]]:
        """Full training run: the reference's epoch loop
        (``master/part1/part1.py:101-103``) with its three signals —
        loss every ``log_every`` batches, average per-batch time over the
        timing window, eval summary after each epoch."""
        cfg = self.cfg
        if dataset is None:
            dataset = _load_dataset(cfg)
        train_loader = BatchLoader(
            dataset.train_images,
            dataset.train_labels,
            cfg.global_batch_size,
            mesh=self.mesh,
            shuffle=True,
            seed=cfg.seed,
        )
        test_loader = BatchLoader(
            dataset.test_images,
            dataset.test_labels,
            cfg.global_batch_size,
            mesh=self.mesh,
            shuffle=False,
            drop_last=False,
        )
        if state is None:
            state = self.init()
        base_key = host_to_global(
            jax.random.key(cfg.seed), replicated(self.mesh)
        )

        # ---- telemetry (obs/): the in-memory ring always exists (the
        # watchdog flushes it post-mortem); manifest + JSONL only when
        # cfg.metrics_dir is set. Emission is gated on the SAME fetch the
        # logging/timing path already performs — zero extra round-trips.
        flops_per_step = None
        if cfg.model == "resnet18":
            from cs744_pytorch_distributed_tutorial_tpu.obs.flops import (
                resnet18_cifar_train_flops_per_sample,
            )

            flops_per_step = (
                resnet18_cifar_train_flops_per_sample() * cfg.global_batch_size
            )
        # Analytic bytes-on-wire of the active sync config, recorded on
        # every step record. Non-compressed strategies sync once per
        # MICROBATCH under gradient accumulation; the compressed path
        # syncs the accumulated gradient once, and zero1 fuses its
        # reduce-scatter into the single sharded update.
        # (fsdp still gathers/scatters per MICROBATCH even overlapped —
        # every microbatch differentiates through the param all_gather —
        # while pure-DP overlap defers the only sync to the final
        # micro-step.)
        syncs_per_step = (
            1
            if (self._compress or self._zero1 or (self._overlap and not self._fsdp))
            else cfg.accum_steps
        )
        wire_bytes = syncs_per_step * sync_wire_bytes(
            state.params,
            cfg.sync,
            self.axis_size,
            cfg.grad_compress,
            bucket_bytes=self._bucket_bytes,
            overlap=self._overlap,
        )
        sched = make_schedule(cfg)
        lr_at = (
            (lambda s: float(sched))
            if isinstance(sched, (int, float))
            else (lambda s: float(sched(s)))
        )
        telemetry = Telemetry(
            cfg.metrics_dir,
            every=cfg.metrics_every or cfg.log_every,
            run="cifar",
            flops_per_step=flops_per_step,
            n_chips=int(self.mesh.devices.size),
            device_kind=jax.devices()[0].device_kind,
        )
        telemetry.write_manifest(
            config=cfg, mesh=self.mesh, grad_sync_bytes_per_step=wire_bytes
        )

        # ---- flight recorder (obs/flight.py): always-on per-step wall
        # ring + MAD straggler detection; its tail dumps as structured
        # events on watchdog fire, uncaught exception, or SIGTERM.
        from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
            FlightRecorder,
            HbmHighWater,
            StragglerMonitor,
        )

        straggler = StragglerMonitor()
        flight = FlightRecorder(
            telemetry=telemetry, straggler=straggler, hbm=HbmHighWater()
        )
        flight.install()

        history: dict[str, Any] = {"train_loss": [], "eval": [], "avg_batch_time": None}
        timer = StepTimer(window=cfg.timing_batches)
        ckpt = None
        mem = self.memstore
        start_epoch = 0
        steps_done = 0
        steps_per_epoch = len(train_loader)
        if cfg.checkpoint_dir:
            from cs744_pytorch_distributed_tutorial_tpu.utils.checkpoint import (
                Checkpointer,
            )

            ckpt = Checkpointer(cfg.checkpoint_dir)
        # Restore-tier arbitration: the newest recoverable state wins;
        # the in-memory snapshot (zero filesystem reads) wins ties with
        # the disk tier — after a restart the two are usually the same
        # step, and host RAM is the one that costs nothing to read.
        mem_step = mem.latest_step() if mem is not None else None
        disk_step = ckpt.latest_step() if ckpt is not None else None
        restored = source = None
        if mem_step is not None and (disk_step is None or disk_step <= mem_step):
            restored, source = mem.restore_latest(state), "memory"
        elif disk_step is not None:
            restored, source = ckpt.restore_latest(state), "disk"
        if restored is not None:
            state = self.place_state(restored)
            steps_done = int(jax.device_get(state.step))
            start_epoch = steps_done // max(steps_per_epoch, 1)
            telemetry.emit_event("restore", source=source, step=steps_done)
            self.log.info(
                "restored %s state at step %d (resuming at epoch %d)",
                source,
                steps_done,
                start_epoch,
            )

        watchdog = None
        if cfg.step_timeout_s:
            from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
                StepWatchdog,
            )

            on_hang = None
            if cfg.hang_action in ("abort", "escalate"):
                import os

                # A wedged device fetch can't be unblocked from inside the
                # process; exit so the supervisor (coordination service,
                # k8s, a shell loop around the CLI) restarts the job, which
                # resumes from the newest checkpoint.
                def on_hang(elapsed_s: float) -> None:
                    os._exit(13)

            # The watchdog gets the telemetry ring (WHAT the run was
            # converging toward) and the flight recorder (what the STEP
            # TIMES were doing): both flush on firing. "escalate" climbs
            # warn -> dump -> abort across successive expiries instead of
            # the all-at-once report.
            watchdog = StepWatchdog(
                cfg.step_timeout_s,
                on_hang=on_hang,
                metric_ring=telemetry.ring,
                flight_recorder=flight,
                escalation=(
                    ("warn", "dump", "abort")
                    if cfg.hang_action == "escalate"
                    else None
                ),
            )
        if cfg.halt_on_nonfinite:
            from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
                NonFiniteLossError,
            )

        # Mid-epoch resume: the restored state already contains the first
        # ``steps_done % steps_per_epoch`` batches of this epoch — replaying
        # them would double-apply updates, so skip forward through the
        # epoch's deterministic batch plan (loader order is a pure function
        # of (seed, epoch)) to the recorded step. The loader's ``start``
        # offsets the index plan itself, so skipped batches are never
        # assembled or transferred — index arithmetic only.
        resume_skip = steps_done % steps_per_epoch if steps_per_epoch else 0

        def guarded_save(save_state, *, force: bool = False) -> None:
            """Checkpoint under a widened watchdog window: saves block on
            serialization + disk and legitimately outlast a step, but a
            wedged device fetch inside the save should still be caught."""
            if watchdog is not None:
                watchdog.arm(cfg.step_timeout_s * 10)
            try:
                ckpt.save(save_state, force=force)
            finally:
                if watchdog is not None:
                    watchdog.disarm()

        # Divergence-safe checkpointing under halt_on_nonfinite: the loss
        # fetched at step k is the forward pass over the params step k-1
        # PRODUCED, so a due checkpoint is held as (step_count, state,
        # to_disk, to_mem) and persisted only once the NEXT step's (or
        # the epoch eval's) loss over those params comes back finite.
        # Restart recovery therefore can never restore a state whose own
        # forward pass diverged — from EITHER tier: the in-memory
        # snapshot rides the same pending/certify gate as the disk save.
        pending_ckpt: tuple[int, TrainState, bool, bool] | None = None

        # The first executed batch blocks on XLA compilation (minutes for
        # large models) — exempt it from the watchdog the same way the
        # timing window excludes step 0 (utils/timing.py, SURVEY §7d).
        compile_pending = True

        capture = None
        if cfg.profile_dir:
            from cs744_pytorch_distributed_tutorial_tpu.utils import profiling

            capture = profiling.StepCapture(
                cfg.profile_dir, cfg.profile_start_step, cfg.profile_num_steps, "train"
            )

        prev_mono = None  # per-step wall clock for the straggler ring
        try:
            for epoch in range(
                start_epoch, epochs if epochs is not None else cfg.epochs
            ):
                timer.start()
                skip = resume_skip if epoch == start_epoch else 0
                batch_iter = enumerate(
                    prefetch(train_loader.epoch(epoch, start=skip), cfg.prefetch_depth),
                    start=skip,
                )
                metrics = None
                while True:
                    # The armed window covers batch acquisition too: a
                    # wedged chip blocks the prefetch producer's device_put
                    # and this thread then hangs in the queue get — the
                    # primary hang mode the watchdog exists to catch.
                    arm_now = watchdog is not None and not compile_pending
                    if arm_now:
                        watchdog.arm()
                    fetch_ctx = capture.fetch() if capture else contextlib.nullcontext()
                    try:
                        with fetch_ctx:
                            batch_idx, (x, y) = next(batch_iter)
                    except StopIteration:
                        if arm_now:
                            watchdog.disarm()
                        # A window still open at epoch end closes HERE so
                        # the capture never swallows eval/checkpointing;
                        # the last step's loss fences its device work.
                        if capture:
                            capture.close(None if metrics is None else metrics["loss"])
                        break
                    if capture:
                        capture.open_if_due(steps_done, self.train_step, state, x, y, base_key)
                    step_ctx = capture.step(steps_done) if capture else contextlib.nullcontext()
                    with step_ctx:
                        state, metrics = self.train_step(state, x, y, base_key)
                    # jit's first call traced+compiled synchronously above,
                    # so every later iteration runs under the watchdog.
                    compile_pending = False
                    if capture:
                        capture.close_if_done(steps_done, metrics["loss"])
                    # Fetch the loss value only while timing or logging needs
                    # it — otherwise leave dispatch fully async so the host
                    # stages batch N+1 while the device runs batch N.
                    timing_active = timer.steps_recorded <= cfg.timing_batches[1]
                    should_log = batch_idx % cfg.log_every == 0
                    metrics_due = telemetry.due(steps_done)
                    checkpoint_due = bool(
                        ckpt
                        and cfg.checkpoint_every
                        and (steps_done + 1) % cfg.checkpoint_every == 0
                    )
                    snapshot_due = bool(
                        mem is not None
                        and cfg.snapshot_every
                        and (steps_done + 1) % cfg.snapshot_every == 0
                    )
                    if (
                        timing_active
                        or should_log
                        or metrics_due
                        or pending_ckpt is not None
                    ):
                        # (wall, mono) pair bracketing the gated fetch:
                        # obs/fleet.py aligns these across ranks for
                        # collective-skew attribution — the stamps ride
                        # a fetch that was already due, no new sync.
                        sync_enter_wall = time.time()
                        sync_enter_mono = time.monotonic()
                        # graftlint: disable=GL001 -- cadence-gated: only
                        # reached when a log/metrics/ckpt boundary is due and
                        # the device work is already fenced.
                        loss = float(metrics["loss"])
                        sync_exit_wall = time.time()
                        sync_exit_mono = time.monotonic()
                        if watchdog is not None:
                            watchdog.disarm()  # the fetch is the hang point
                        if cfg.halt_on_nonfinite and not math.isfinite(loss):
                            telemetry.emit_event(
                                "non_finite_loss", step=steps_done, loss=loss
                            )
                            raise NonFiniteLossError(steps_done, loss)
                        if metrics_due:
                            obs_fields = {}
                            if self._obs_norms:
                                # Same fetch boundary as the loss: the
                                # device work is already fenced, these are
                                # ready scalars.
                                obs_fields["grad_norm"] = float(  # graftlint: disable=GL001 -- same gated fetch boundary
                                    metrics["grad_norm"]
                                )
                                obs_fields["param_norm"] = float(  # graftlint: disable=GL001 -- same gated fetch boundary
                                    metrics["param_norm"]
                                )
                            telemetry.emit_step(
                                steps_done,
                                loss=loss,
                                epoch=epoch,
                                batch=batch_idx,
                                lr=lr_at(steps_done),
                                grad_sync_bytes=wire_bytes,
                                sync_enter_wall=sync_enter_wall,
                                sync_enter_mono=sync_enter_mono,
                                sync_exit_wall=sync_exit_wall,
                                sync_exit_mono=sync_exit_mono,
                                **obs_fields,
                            )
                        if pending_ckpt is not None and steps_done == pending_ckpt[0]:
                            # this loss is the forward pass over the pending
                            # state's params — certified finite, persist it
                            # on each tier that was due
                            _, pstate, to_disk, to_mem = pending_ckpt
                            if to_disk:
                                guarded_save(pstate)
                            if to_mem:
                                mem.save(pstate)
                            pending_ckpt = None
                    elif watchdog is not None:
                        watchdog.disarm()
                    if timing_active:
                        timer.tick()
                        if timer.steps_recorded == cfg.timing_batches[1] + 1:
                            avg = timer.window_average()
                            history["avg_batch_time"] = avg
                            self.log.info("average time:  %f", avg)
                    if should_log:
                        history["train_loss"].append((epoch, batch_idx, loss))
                        self.log.info("%d loss:  %f", batch_idx, loss)
                    # Straggler ring: inter-iteration wall time. Dispatch
                    # is async, so a slow DEVICE step surfaces here at
                    # the next gated fetch (or queue backpressure) — the
                    # jitter signal, not an extra fence. The first
                    # interval starts AFTER the compile step completes.
                    now_mono = time.monotonic()
                    if prev_mono is not None:
                        outlier = straggler.record(
                            steps_done, now_mono - prev_mono
                        )
                        if outlier is not None:
                            telemetry.emit_event("straggler", **outlier)
                    prev_mono = now_mono
                    steps_done += 1
                    if checkpoint_due or snapshot_due:
                        if cfg.halt_on_nonfinite:
                            # Copy: train_step donates its input state, so
                            # holding the live object across the next step
                            # would reference deleted buffers.
                            pending_ckpt = (
                                steps_done,
                                jax.tree.map(jnp.copy, state),
                                checkpoint_due,
                                snapshot_due,
                            )
                        else:
                            if checkpoint_due:
                                guarded_save(state)
                            if snapshot_due:
                                # mem.save gathers to host synchronously,
                                # so the live (donatable) buffers are safe
                                # to reuse the moment it returns.
                                mem.save(state)
                if self.sync_monitor is not None:
                    # Epoch boundary: fence in-flight debug callbacks, put
                    # the verdict on the metric stream, and fail loudly if
                    # any replica drifted (utils/debug.py).
                    divergent = self.sync_monitor.divergent_steps()
                    telemetry.emit_event(
                        "divergence_check",
                        epoch=epoch,
                        steps_checked=self.sync_monitor.steps_recorded,
                        divergent_steps=len(divergent),
                        in_sync=not divergent,
                    )
                    self.sync_monitor.assert_in_sync()
                eval_metrics = self.evaluate(state, test_loader, watchdog=watchdog)
                history["eval"].append(eval_metrics)
                telemetry.emit_event(
                    "eval",
                    epoch=epoch,
                    step=steps_done,
                    avg_loss=eval_metrics["avg_loss"],
                    accuracy=eval_metrics["accuracy"],
                )
                self.log.info(
                    "Test set: Average loss: %.4f, Accuracy: %d/%d (%.0f%%)",
                    eval_metrics["avg_loss"],
                    eval_metrics["correct"],
                    eval_metrics["count"],
                    100.0 * eval_metrics["accuracy"],
                )
                if cfg.halt_on_nonfinite and not math.isfinite(
                    eval_metrics["avg_loss"]
                ):
                    raise NonFiniteLossError(steps_done, eval_metrics["avg_loss"])
                if pending_ckpt is not None and steps_done == pending_ckpt[0]:
                    # epoch ended right after the due step: the eval loss
                    # just certified the pending (== current) state
                    _, pstate, to_disk, to_mem = pending_ckpt
                    if to_disk:
                        guarded_save(pstate)
                    if to_mem:
                        mem.save(pstate)
                    pending_ckpt = None
            if ckpt is not None:
                guarded_save(state, force=True)
            if mem is not None:
                mem.save(state)
            if (
                cfg.profile_dir
                and cfg.profile_num_steps
                and steps_done <= cfg.profile_start_step
            ):
                # The requested window never opened — say so instead of
                # leaving an empty trace directory to be discovered in
                # TensorBoard.
                self.log.warning(
                    "profile window [%d, %d) never opened: run ended after "
                    "%d steps; lower profile_start_step",
                    cfg.profile_start_step,
                    cfg.profile_start_step + cfg.profile_num_steps,
                    steps_done,
                )
        except BaseException as e:
            # Crash post-mortem: the timing tail goes onto the metric
            # stream before the run dies (KeyboardInterrupt included).
            flight.dump("exception", error=repr(e), step=steps_done)
            raise
        finally:
            if capture:
                capture.close()  # exception path: close without a fence
            flight.uninstall()
            if watchdog is not None:
                watchdog.close()
            if ckpt is not None:
                ckpt.close()
            telemetry.close()
        return state, history

    def evaluate_only(self, dataset=None) -> dict[str, float]:
        """Restore the newest checkpoint (``cfg.checkpoint_dir``) and run
        the held-out evaluation without training — the deploy-time/
        validation entry point (CLI: ``--eval-only``). Without a
        checkpoint dir this evaluates freshly initialized params."""
        cfg = self.cfg
        if dataset is None:
            dataset = _load_dataset(cfg)
        test_loader = BatchLoader(
            dataset.test_images,
            dataset.test_labels,
            cfg.global_batch_size,
            mesh=self.mesh,
            shuffle=False,
            drop_last=False,
        )
        state = self.init()
        if cfg.checkpoint_dir:
            from cs744_pytorch_distributed_tutorial_tpu.utils.checkpoint import (
                Checkpointer,
            )

            ckpt = Checkpointer(cfg.checkpoint_dir)
            try:
                restored = ckpt.restore_latest(state)
            finally:
                ckpt.close()
            if restored is None:
                raise FileNotFoundError(
                    f"no checkpoint under {cfg.checkpoint_dir!r} to evaluate"
                )
            state = self.place_state(restored)
        metrics = self.evaluate(state, test_loader)
        self.log.info(
            "Test set: Average loss: %.4f, Accuracy: %d/%d (%.0f%%)",
            metrics["avg_loss"],
            metrics["correct"],
            metrics["count"],
            100.0 * metrics["accuracy"],
        )
        return metrics

    def evaluate(
        self, state: TrainState, test_loader: BatchLoader, watchdog=None
    ) -> dict[str, float]:
        """Eval over the test set; ``watchdog`` (utils/failure.py), when
        supplied, arms around each batch's dispatch+fetch so a wedged
        device fetch during eval is still detected. The first eval batch
        is exempt — it blocks on eval_step's XLA compilation."""
        total_loss, total_correct, total_count = 0.0, 0, 0
        first = True
        batch_iter = iter(
            prefetch(test_loader.epoch_padded(0), self.cfg.prefetch_depth)
        )
        while True:
            # Arm BEFORE acquisition: a wedged chip blocks the prefetch
            # producer's device_put and this thread then hangs in the
            # queue get — same placement as the train loop.
            arm_now = watchdog is not None and not first
            if arm_now:
                watchdog.arm()
            try:
                try:
                    x, y, mask = next(batch_iter)
                except StopIteration:
                    break
                m = self.eval_step(state, x, y, mask)
                total_loss += float(m["loss_sum"])  # graftlint: disable=GL001 -- eval accumulates on host per batch by design
                total_correct += int(m["correct"])  # graftlint: disable=GL001 -- eval accumulates on host per batch by design
                total_count += int(m["count"])  # graftlint: disable=GL001 -- eval accumulates on host per batch by design
            finally:
                if arm_now:
                    watchdog.disarm()
            first = False
        return {
            "avg_loss": total_loss / max(total_count, 1),
            "correct": total_correct,
            "count": total_count,
            "accuracy": total_correct / max(total_count, 1),
        }


# ------------------------------------------------------------------ graftcheck
def make_trace_entry(**overrides):
    """A graftcheck ``TracedStep`` around this engine's REAL jitted
    ``train_step`` (same ``shard_map``, same ``donate_argnums``): a tiny
    model on a small mesh with one synthetic batch, carrying the engine's
    own collective-schedule contract and wire-byte accounting for TA003
    to cross-check against the traced jaxpr. ``overrides`` are
    ``TrainConfig`` fields — the audit tests sweep ``sync=`` through
    every strategy with exactly this function.
    """
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        TracedStep,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
        expected_collective_schedule,
        sync_units,
    )

    ndev = min(4, len(jax.devices()))
    kw: dict[str, Any] = dict(
        model="tiny_cnn",
        num_devices=ndev,
        global_batch_size=8 * ndev,
        synthetic_data=True,
        synthetic_train_size=8 * ndev,
        synthetic_test_size=8 * ndev,
        sync="allreduce",
    )
    kw.update(overrides)
    cfg = TrainConfig(**kw)
    mesh = make_mesh({DATA_AXIS: ndev}, devices=jax.devices()[:ndev])
    trainer = Trainer(cfg, mesh=mesh)
    state = trainer.init()
    ds = _load_dataset(cfg)
    x, y = shard_global_batch(
        mesh,
        ds.train_images[: cfg.global_batch_size],
        ds.train_labels[: cfg.global_batch_size],
    )
    key = jax.random.key(0)

    syncs_per_step = (
        1
        if (
            trainer._compress
            or trainer._zero1
            or (trainer._overlap and not trainer._fsdp)
        )
        else cfg.accum_steps
    )
    # 'auto' has a contract too: the AD transpose inserts one psum a
    # parameter leaf (sync_units counts leaves for it), 'none' inserts none.
    units = sync_units(
        state.params,
        cfg.sync,
        trainer.axis_size,
        bucket_bytes=trainer._bucket_bytes,
        grad_compress=cfg.grad_compress,
        overlap=trainer._overlap,
    )
    schedule = expected_collective_schedule(
        cfg.sync,
        trainer.axis_size,
        units,
        grad_compress=cfg.grad_compress,
        syncs_per_step=syncs_per_step,
    )
    wire_bytes = syncs_per_step * sync_wire_bytes(
        state.params,
        cfg.sync,
        trainer.axis_size,
        cfg.grad_compress,
        bucket_bytes=trainer._bucket_bytes,
        overlap=trainer._overlap,
    )
    # graftmem TA008 contract: which input leaves the sync strategy
    # promises to shard. _state_specs shards opt_state under zero1/fsdp
    # and params under fsdp (state is arg 0 of train_step).
    if trainer._fsdp:
        sharded_paths = ("[0].params", "[0].opt_state")
    elif trainer._zero1:
        sharded_paths = ("[0].opt_state",)
    else:
        sharded_paths = ()
    return TracedStep(
        name="cifar",
        fn=trainer.train_step,
        args=(state, x, y, key),
        axis_sizes={DATA_AXIS: trainer.axis_size},
        sync=cfg.sync,
        grad_compress=cfg.grad_compress,
        compute_dtype=cfg.compute_dtype,
        expected_schedule=schedule,
        expected_wire_bytes=float(wire_bytes),
        check_donation=True,
        sharded_param_paths=sharded_paths,
        detail={
            "model": cfg.model,
            "accum_steps": cfg.accum_steps,
            "sync_overlap": cfg.sync_overlap,
        },
    )


def _cifar_int8_entry():
    return make_trace_entry(sync="int8_allreduce")


def _cifar_overlap_entry():
    # The overlapped schedule's TA003 contract: same collective classes
    # and byte counts as fused bucketed allreduce, placed per reverse-
    # order bucket (sync_units(overlap=True) counts that layout).
    return make_trace_entry(sync_overlap="bucket")


def _cifar_overlap_zero1_entry():
    # Overlapped reduce-scatter schedule: per-bucket psum_scatter ->
    # per-shard SGD apply -> per-bucket delta all_gather, reverse-order
    # buckets, no cross-bucket barrier. TA003 checks the reduce_scatter
    # and all_gather counts/bytes against the rows=axis_size layout.
    return make_trace_entry(sync="zero1", sync_overlap="bucket")


def _register_trace_entries() -> None:
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        register_entrypoint,
    )

    register_entrypoint("cifar", make_trace_entry, tags=("cifar",))
    register_entrypoint("cifar-int8", _cifar_int8_entry, tags=("cifar", "int8"))
    register_entrypoint(
        "cifar-overlap", _cifar_overlap_entry, tags=("cifar", "overlap")
    )
    register_entrypoint(
        "cifar-overlap-zero1",
        _cifar_overlap_zero1_entry,
        tags=("cifar", "overlap", "zero1"),
    )


_register_trace_entries()
