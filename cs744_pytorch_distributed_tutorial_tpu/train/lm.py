"""LM training engine: data x sequence x tensor parallelism on one mesh.

The CIFAR engine (``train/engine.py``) reproduces the reference's
data-parallel pedagogy; this engine is the long-context counterpart the
reference never reaches: batch sharded along ``data``, sequence sharded
along ``seq`` (ring ppermute hops or Ulysses all-to-all —
``parallel/ring_attention.py``), and attention heads + FFN hidden units
sharded along ``tensor`` (Megatron-style column/row-parallel sublayers —
``parallel/tensor.py``, ``models/transformer.py``). Tensor-sharded
parameters live and update as shards (their optimizer state too — the
ZeRO-flavored consequence of tensor parallelism); replicated parameters
get their gradients explicitly averaged over all mesh axes.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time
from typing import Any

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from cs744_pytorch_distributed_tutorial_tpu.config import resolve_dtype
from cs744_pytorch_distributed_tutorial_tpu.obs.metrics import (
    Telemetry,
    sown_scalar_mean,
    tree_l2_norm,
)
from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
    ATTENTION_IMPLS,
    TransformerLM,
    lm_param_specs,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    DATA_AXIS,
    host_to_global,
    interpret_kernels,
    make_mesh,
)

SEQ_AXIS = "seq"
TENSOR_AXIS = "tensor"


def _resolve_quant_modules(modules: str) -> tuple:
    """Map the user-facing int8-decode scope name to the module tuple
    (``ops/quant.py``): "head" = lm_head only (the measured decode win),
    "all" = every Dense projection (the weight-memory-bound choice)."""
    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
        QUANT_HEAD_ONLY,
        QUANT_MODULES,
    )

    if modules == "head":
        return QUANT_HEAD_ONLY
    if modules == "all":
        return tuple(sorted(QUANT_MODULES))
    raise ValueError(
        f"unknown int8-decode scope {modules!r}; choose 'head' or 'all'"
    )


def evaluate_heldout(trainer, params, tokens) -> dict[str, float]:
    """Shared held-out evaluation contract (LM + pipeline engines):
    mean next-token cross-entropy and perplexity (exp of it) over
    ``tokens`` [N, seq_len + 1]. Batches of ``cfg.global_batch_size``
    sequences; a ragged tail is dropped (like the train loaders'
    drop_last) so every batch keeps the static shard shape. ``trainer``
    needs ``cfg.global_batch_size``, ``shard_batch`` and ``eval_step``."""
    b = trainer.cfg.global_batch_size
    n_batches = len(tokens) // b
    if n_batches == 0:
        raise ValueError(
            f"need at least global_batch_size={b} sequences, got {len(tokens)}"
        )
    total = 0.0
    for i in range(n_batches):
        x, y = trainer.shard_batch(tokens[i * b : (i + 1) * b])
        total += float(trainer.eval_step(params, x, y)["loss"])
    mean_loss = total / n_batches
    return {"loss": mean_loss, "perplexity": math.exp(mean_loss)}


@flax.struct.dataclass
class LMState:
    """Checkpointable LM training state (utils/checkpoint.py keys saves
    by ``step``)."""

    step: jax.Array  # scalar int32
    params: Any
    opt_state: Any


@dataclasses.dataclass
class LMConfig:
    """Long-context training run: model dims + 2-D mesh layout."""

    vocab_size: int = 1024
    num_layers: int = 2
    num_heads: int = 8
    d_model: int = 128
    d_ff: int = 512
    max_seq_len: int = 2048
    attention_impl: str = "ring"  # ring | ulysses | ulysses_flash | dense | flash
    compute_dtype: str = "float32"  # "bfloat16" on real TPU runs

    data_parallel: int = 1
    seq_parallel: int = 1
    tensor_parallel: int = 1

    # MoE: num_experts > 0 swaps the dense FFN for a routed expert
    # mixture (models/moe.py); expert_parallel shards the experts over
    # the DATA axis (the standard EP-over-DP layout) with all-to-all
    # token dispatch.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # Token groups for routing/capacity (models/moe.py::MoEFFN — the
    # GShard dispatch-cost lever; 0 = auto ~1024 tokens/group, 1 = one
    # global group). Part of routing semantics: capacity is per group.
    moe_groups: int = 1
    # Token movement (models/moe.py::MoEFFN.dispatch_impl): "einsum"
    # (GShard one-hot contractions), "scatter" (scatter-add/gather —
    # round 5, targeting the measured dispatch tax), or "dropless"
    # (late round 5 — NO capacity: tokens argsort by expert and the
    # expert FFN runs as ragged grouped matmuls, ops/gmm.py; every
    # routed token computes, capacity/groups are ignored, and
    # moe_expert_parallel is rejected — EP's all_to_all needs the
    # static per-destination counts capacity slots provide).
    # einsum/scatter share routing and drop semantics exactly;
    # trajectories match to float tolerance.
    moe_dispatch: str = "scatter"
    # Grouped-matmul backend for moe_dispatch="dropless": "auto"
    # (default — the Pallas megablox-style kernels with fused bias/gelu
    # epilogues on TPU, measured 1.13x over ragged_dot in-model;
    # lax.ragged_dot where kernels would interpret), "pallas", or
    # "ragged".
    moe_gmm_impl: str = "auto"
    moe_expert_parallel: bool = False
    moe_aux_coef: float = 0.01

    global_batch_size: int = 8
    seq_len: int = 256  # tokens per sequence fed to the model
    learning_rate: float = 1e-3
    seed: int = 0
    # Optimizer/schedule registry (same options as the CIFAR engine's
    # TrainConfig; resolved through train/state.py): cosine schedules
    # need total_steps, warmup ramps linearly from 0 first.
    optimizer: str = "adamw"  # "adamw" | "sgd" | "lion"
    lr_schedule: str = "constant"  # "constant" | "cosine" | "warmup_cosine"
    warmup_steps: int = 0
    total_steps: int | None = None
    momentum: float = 0.9  # adamw/lion b1; sgd momentum
    weight_decay: float = 1e-4  # optax.adamw's default, kept for the golden trace
    # Clip the global gradient norm before AdamW sees it; None disables.
    # The standard long-context stabilizer (loss spikes on long sequences).
    grad_clip_norm: float | None = None

    # Gradient compression on the data-parallel sync (parallel/sync.py,
    # same semantics as the CIFAR engine's TrainConfig.grad_compress):
    # "int8" quantizes each gradient bucket per-chunk to int8 + f32
    # scales and carries the quantization residual as per-device error
    # feedback inside the optimizer state. Data-parallel layouts only
    # (tensor_parallel == seq_parallel == 1, no EP): sharded-grad paths
    # ship on wires the bucket quantizer does not model. zero1 composes
    # via sync_overlap="bucket+int8" (quantization chunks on the
    # overlapped schedule's bucket boundaries); fsdp has no separate
    # grad wire to quantize (the reduction is the param all_gather's AD
    # transpose). The clip still sees the dequantized mean.
    grad_compress: str = "none"  # "none" | "int8"
    # Bucket size (MiB) for the compressed sync's coalesced buffers;
    # 0 falls back to the default bucket size.
    sync_bucket_mb: float = 4.0
    # Overlapped gradient sync (parallel/overlap.py, parallel/zero.py):
    # reverse-layer-order buckets, per-bucket collective + per-bucket
    # optimizer apply — DDP's reducer schedule as dataflow. "bucket"
    # overlaps the float wire: the pure-DP pmean (fixed-LR SGD recipe
    # required: optimizer="sgd", constant lr, no warmup/clip) or, under
    # zero1/fsdp, the per-bucket psum_scatter -> chunk apply ->
    # all_gather schedule inside the sharded optimizer (any registry
    # optimizer + schedule; grad_clip_norm stays fused-only).
    # "bucket+int8" overlaps the int8+EF wire (grad_compress="int8";
    # pure DP or zero1). accum_steps>1 composes: only the final
    # micro-step's sync overlaps. No seq/tensor/expert sharding.
    sync_overlap: str = "off"  # "off" | "bucket" | "bucket+int8"

    # Rematerialization: recompute block activations in backward instead
    # of storing them (jax.checkpoint) — identical numerics, O(layers)
    # less activation HBM, one extra forward of FLOPs. remat_policy
    # "dots" keeps matmul outputs (recompute elementwise only).
    remat: bool = False
    remat_policy: str = "none"

    # ZeRO-1 (parallel/zero.py::Zero1Adam): shard BOTH AdamW moments
    # over the data axis as flat chunks — optimizer memory per device
    # drops from 2x params to 2x params / data_parallel (the lever that
    # matters at transformer scale; GPT-2-medium's f32 moments are
    # ~2.8 GB replicated). Grads arrive pre-sharded via psum_scatter
    # (half an allreduce's bytes) and parameter deltas all_gather back —
    # the same total bytes as the allreduce it replaces. Trajectory
    # matches the replicated optimizer to float tolerance (tested).
    # Composes with tensor_parallel (local tensor shards chunk per
    # (data, tensor) coordinate), grad_clip_norm (exact global norm
    # via one psum of per-chunk squared sums), and all three registry
    # optimizers (adamw / lion — one sharded moment / sgd). No expert
    # parallelism. Checkpoint resume is
    # mesh-ELASTIC over data_parallel (round 5): flat chunks re-chunk
    # on restore ([dp_old, c_old] -> [dp_new, c_new], host-side);
    # tensor_parallel is layout-pinned and must match the save.
    zero1: bool = False

    # ZeRO-3/FSDP (parallel/zero.py::FsdpAdam): params AND both AdamW
    # moments persist only as data-axis-sharded flat chunks — 3x params
    # of persistent state becomes 3x params / data_parallel per device.
    # Full weights exist only transiently inside the step (one
    # all_gather per leaf, freed after last use; the all_gather's AD
    # transpose delivers grads pre-scattered). Same compositions and
    # restrictions as zero1 (all three optimizer rules via
    # FsdpLion/FsdpSgdLM), same trajectory-parity guarantee; params
    # leave fit() as chunked arrays (gather_for_decode unshards them).
    fsdp: bool = False

    # Layer stacking (models/transformer.py::TransformerLM.scan_layers):
    # run the homogeneous blocks as one nn.scan body instead of L
    # unrolled copies — identical numerics, O(L) smaller traced program.
    # The compile-wall lever for deep / big-batch configs; params carry
    # a leading [L] axis (convert with stack/unstack_block_params).
    scan_layers: bool = False

    # Weight tying: logits = x @ tok_embed^T instead of a separate
    # lm_head (halves the vocab parameters).
    tie_embeddings: bool = False
    # Llama-family block options (models/transformer.py): norm
    # "layernorm"|"rmsnorm", mlp "gelu"|"swiglu" (swiglu adds the
    # column-parallel mlp_gate projection; d_ff semantics unchanged).
    norm: str = "layernorm"
    mlp: str = "gelu"

    # Rotary position embeddings: relative positions inside attention
    # instead of the learned absolute table (exact under sequence
    # sharding and cached decode).
    use_rope: bool = False

    # Grouped-query attention: KV head count (None = num_heads; 1 = MQA).
    # Shrinks the decode KV cache by num_heads/num_kv_heads.
    num_kv_heads: int | None = None

    # Pallas fused softmax-CE (ops/fused_xent.py): one pass over the
    # logits instead of materializing the [N, V] log-softmax — the
    # large-vocab loss lever. Interpret mode off-TPU.
    fused_xent: bool = False

    # Label smoothing: (1-s) one-hot + s/vocab target; 0.0 = plain CE.
    # Incompatible with fused_xent (the kernel computes plain CE).
    label_smoothing: float = 0.0

    # Residual dropout on each block's attention/MLP sublayer outputs —
    # the round-1 deferred rng migration. The step
    # index keys the mask stream: ``train_step(..., step=k)`` draws the
    # same masks for the same k on every run, different masks per step.
    # 0.0 reproduces the dropout-free path exactly (golden traces pin
    # this).
    dropout_rate: float = 0.0

    # Gradient accumulation: split each device's batch shard into
    # ``accum_steps`` microbatches, run fwd/bwd per microbatch under
    # ``lax.scan`` (activations for only ONE microbatch live at a time —
    # the long-context memory lever), average the gradient sums, and
    # apply a single optimizer update. With dense FFNs this is
    # numerically identical to the unaccumulated step up to summation
    # order; with MoE (moe_experts > 0) expert capacity is computed per
    # MICROBATCH, so routing/drop decisions — and hence the trajectory —
    # legitimately differ from the unaccumulated step.
    accum_steps: int = 1

    # Checkpoint/resume (Orbax, utils/checkpoint.py). fit()'s batch plan
    # is a pure function of the step index, so restarts resume exactly.
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0  # steps; 0 = only at end when dir set

    # In-memory replicated snapshots (utils/memstore.py): a second,
    # faster recovery tier above the disk checkpointer — restart
    # recovery restores from host RAM with ZERO filesystem reads, under
    # the same divergence-safe pending/certify gate as disk saves.
    # snapshot_every is the cadence in steps; 0 disables the tier.
    snapshot_every: int = 0
    snapshot_keep: int = 2

    # Failure detection (utils/failure.py), same contract as the CIFAR
    # engine: NaN/inf losses raise NonFiniteLossError (fit() fetches
    # every loss anyway — zero extra transfers); step_timeout_s arms a
    # hang watchdog around each step (first step exempt: XLA compile).
    halt_on_nonfinite: bool = True
    step_timeout_s: float | None = None

    # Telemetry (obs/), same contract as TrainConfig: metrics_dir writes
    # manifest.json + metrics.jsonl. fit() fetches every loss already,
    # so the default cadence is every step — still zero extra transfers.
    metrics_dir: str | None = None
    metrics_every: int = 1

    # Profiler capture (utils/profiling.py), same contract as the CIFAR
    # engine: trace steps [profile_start_step, + profile_num_steps) to
    # profile_dir. Start defaults past step 0 to keep compile out.
    profile_dir: str | None = None
    profile_start_step: int = 2
    profile_num_steps: int = 3

    def replace(self, **kw: Any) -> "LMConfig":
        return dataclasses.replace(self, **kw)


class LMTrainer:
    """Jitted shard_map train/eval steps for ``TransformerLM`` on a
    ``{"data": d, "seq": s}`` mesh."""

    def __init__(self, cfg: LMConfig, mesh=None, memstore=None):
        self.cfg = cfg
        if mesh is None:
            mesh = make_mesh(
                {
                    DATA_AXIS: cfg.data_parallel,
                    SEQ_AXIS: cfg.seq_parallel,
                    TENSOR_AXIS: cfg.tensor_parallel,
                }
            )
        self.mesh = mesh
        # In-memory snapshot tier (utils/memstore.py): passed in by
        # parallel/elastic.py::default_remesh so snapshots survive a
        # re-mesh, else built from cfg; fit() arbitrates restore tiers
        # by step (newest wins, memory on ties — zero filesystem reads).
        if memstore is None and cfg.snapshot_every:
            from cs744_pytorch_distributed_tutorial_tpu.utils.memstore import (
                ReplicatedSnapshot,
            )

            memstore = ReplicatedSnapshot(max_to_keep=cfg.snapshot_keep)
        self.memstore = memstore
        self.data_size = mesh.shape[DATA_AXIS]
        self.seq_size = mesh.shape[SEQ_AXIS]
        self.tensor_size = mesh.shape.get(TENSOR_AXIS, 1)
        if cfg.global_batch_size % self.data_size:
            raise ValueError(
                f"global batch {cfg.global_batch_size} not divisible by "
                f"data axis {self.data_size}"
            )
        if cfg.seq_len % self.seq_size:
            raise ValueError(
                f"seq_len {cfg.seq_len} not divisible by seq axis {self.seq_size}"
            )
        if cfg.seq_len > cfg.max_seq_len:
            raise ValueError(
                f"seq_len {cfg.seq_len} exceeds max_seq_len {cfg.max_seq_len}: "
                "position indices would gather out of bounds (NaN on CPU, "
                "silently clamped/wrong positions on TPU)"
            )
        if cfg.attention_impl not in ATTENTION_IMPLS:
            raise ValueError(
                f"unknown attention_impl {cfg.attention_impl!r}; "
                f"choose from {ATTENTION_IMPLS}"
            )
        if cfg.attention_impl in ("dense", "flash") and self.seq_size > 1:
            raise ValueError(
                f"attention_impl={cfg.attention_impl!r} is incompatible with "
                "seq_parallel > 1 (a sequence-sharded block cannot attend to "
                "the full sequence without communication); use 'ring', "
                "'ulysses', or 'ulysses_flash'"
            )
        if cfg.num_heads % self.tensor_size:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by tensor axis "
                f"{self.tensor_size}"
            )
        if cfg.d_ff % self.tensor_size:
            raise ValueError(
                f"d_ff {cfg.d_ff} not divisible by tensor axis {self.tensor_size}"
            )
        heads_local = cfg.num_heads // self.tensor_size
        if (
            cfg.attention_impl in ("ulysses", "ulysses_flash")
            and heads_local % self.seq_size
        ):
            raise ValueError(
                f"ulysses needs per-tensor-shard heads ({heads_local}) divisible "
                f"by the seq axis ({self.seq_size})"
            )
        local_batch = cfg.global_batch_size // self.data_size
        if cfg.accum_steps < 1 or local_batch % cfg.accum_steps:
            raise ValueError(
                f"accum_steps {cfg.accum_steps} must divide the per-device "
                f"batch shard ({local_batch} sequences)"
            )
        self.expert_parallel = bool(
            cfg.moe_expert_parallel and cfg.moe_experts > 0 and self.data_size > 1
        )
        if self.expert_parallel and cfg.moe_experts % self.data_size:
            raise ValueError(
                f"moe_experts {cfg.moe_experts} not divisible by the data axis "
                f"({self.data_size}) for expert parallelism"
            )
        if self.expert_parallel and cfg.moe_dispatch == "dropless":
            raise ValueError(
                "moe_dispatch='dropless' does not compose with "
                "moe_expert_parallel: EP's all_to_all needs static "
                "per-destination counts (capacity slots); use "
                "moe_dispatch='scatter' for expert-parallel layouts"
            )
        if cfg.grad_compress not in ("none", "int8"):
            raise ValueError(
                f"unknown grad_compress {cfg.grad_compress!r}; choose "
                "'none' or 'int8'"
            )
        self._compress = cfg.grad_compress == "int8"
        if self._compress:
            if cfg.fsdp:
                raise ValueError(
                    "grad_compress='int8' cannot ride fsdp: its gradient "
                    "reduction IS the AD transpose of the param all_gather "
                    "(an XLA-inserted float psum_scatter), so there is no "
                    "separate grad-sync pass to quantize; for a quantized "
                    "sharded-optimizer wire use zero1 with "
                    "sync_overlap='bucket+int8'"
                )
            if (
                self.seq_size > 1
                or self.tensor_size > 1
                or self.expert_parallel
            ):
                raise ValueError(
                    "grad_compress='int8' requires a data-parallel layout "
                    "(tensor_parallel == seq_parallel == 1, no expert "
                    "parallelism): the quantized bucket all-reduce models "
                    "the plain data-axis gradient reduction, not "
                    "locally-sharded grads"
                )
            if cfg.zero1 and cfg.sync_overlap != "bucket+int8":
                raise ValueError(
                    "grad_compress='int8' under zero1 quantizes on the "
                    "overlapped schedule's bucket boundaries "
                    "(Zero1Adam._apply_overlapped): arm it with "
                    "sync_overlap='bucket+int8' (the fused zero1 path has "
                    "no separate grad-sync pass to compress)"
                )
        if cfg.sync_bucket_mb < 0:
            raise ValueError(
                f"sync_bucket_mb must be >= 0, got {cfg.sync_bucket_mb}"
            )
        self._bucket_bytes = int(cfg.sync_bucket_mb * 2**20)
        from cs744_pytorch_distributed_tutorial_tpu.parallel.overlap import (
            OVERLAP_MODES,
        )

        if cfg.sync_overlap not in OVERLAP_MODES:
            raise ValueError(
                f"unknown sync_overlap {cfg.sync_overlap!r}; choose from "
                f"{OVERLAP_MODES}"
            )
        self._overlap = cfg.sync_overlap != "off"
        if self._overlap:
            if (
                self.seq_size > 1
                or self.tensor_size > 1
                or self.expert_parallel
            ):
                raise ValueError(
                    "sync_overlap requires a data-parallel layout "
                    "(tensor_parallel == seq_parallel == 1, no expert "
                    "parallelism): seq/tensor/expert sharding needs "
                    "cross-chunk joins (psums over other axes) that "
                    "defeat the per-bucket schedule"
                )
            # accum>1 composes: intermediate micro-steps stay local adds
            # and only the FINAL micro-step's sync+apply runs the
            # overlapped bucket schedule.
            if not (cfg.zero1 or cfg.fsdp) and (
                cfg.optimizer != "sgd"
                or cfg.lr_schedule != "constant"
                or cfg.warmup_steps
                or cfg.grad_clip_norm is not None
            ):
                raise ValueError(
                    "pure-DP sync_overlap requires the reference's fixed-LR "
                    "SGD recipe (optimizer='sgd', lr_schedule='constant', "
                    "warmup_steps=0, grad_clip_norm=None): the per-bucket "
                    "apply is the flat torch-SGD update, and a clip or "
                    "schedule would reintroduce the tree-wide barrier the "
                    "overlap removes. zero1/fsdp overlap admits any "
                    "registry optimizer and LR schedule (the sharded "
                    "optimizers apply their chunk rules per bucket)"
                )
            if cfg.sync_overlap == "bucket" and self._compress:
                raise ValueError(
                    "sync_overlap='bucket' overlaps the float wire; with "
                    "grad_compress='int8' use sync_overlap='bucket+int8'"
                )
            if cfg.sync_overlap == "bucket+int8" and not self._compress:
                raise ValueError(
                    "sync_overlap='bucket+int8' overlaps the int8+EF wire; "
                    "set grad_compress='int8'"
                )
        dtype = resolve_dtype(cfg.compute_dtype)
        flash_interpret = interpret_kernels(self.mesh)
        self._flash_interpret = flash_interpret
        self.model = TransformerLM(
            vocab_size=cfg.vocab_size,
            num_layers=cfg.num_layers,
            num_heads=cfg.num_heads,
            d_model=cfg.d_model,
            d_ff=cfg.d_ff,
            max_seq_len=cfg.max_seq_len,
            dtype=dtype,
            attention_impl=cfg.attention_impl,
            flash_interpret=flash_interpret,
            seq_axis=SEQ_AXIS,
            seq_axis_size=self.seq_size,
            tensor_axis=TENSOR_AXIS if TENSOR_AXIS in self.mesh.shape else None,
            tensor_axis_size=self.tensor_size,
            num_experts=cfg.moe_experts,
            moe_top_k=cfg.moe_top_k,
            moe_capacity_factor=cfg.moe_capacity_factor,
            moe_num_groups=cfg.moe_groups,
            moe_dispatch=cfg.moe_dispatch,
            moe_gmm_impl=cfg.moe_gmm_impl,
            expert_axis=DATA_AXIS if self.expert_parallel else None,
            expert_axis_size=self.data_size if self.expert_parallel else 1,
            remat=cfg.remat,
            remat_policy=cfg.remat_policy,
            tie_embeddings=cfg.tie_embeddings,
            use_rope=cfg.use_rope,
            num_kv_heads=cfg.num_kv_heads,
            dropout_rate=cfg.dropout_rate,
            norm=cfg.norm,
            mlp=cfg.mlp,
            scan_layers=cfg.scan_layers,
        )
        # grad_clip_norm composes with tensor/expert sharding via the
        # spec-aware clip (train/state.py::clip_by_global_norm_sharded):
        # plain optax clip would compute each device's LOCAL norm inside
        # shard_map — incomplete AND device-varying over sharded leaves
        # (a replication-divergence bug) — so the sharded transform
        # psums each leaf's squared-sum over the axes its spec names.
        # The shared optimizer/schedule registry (train/state.py) reads
        # the same field names LMConfig defines — duck-typed on purpose.
        from cs744_pytorch_distributed_tutorial_tpu.train.state import (
            make_optimizer,
        )

        # Partition specs: how each GLOBAL param (and its optimizer state)
        # splits over the tensor axis. Built once from the init shapes.
        param_shapes = jax.eval_shape(
            lambda: self._init_model().init(
                jax.random.key(0), jnp.zeros(self._local_batch_shape(), jnp.int32)
            )["params"]
        )
        self.param_specs = lm_param_specs(
            param_shapes,
            TENSOR_AXIS if TENSOR_AXIS in self.mesh.shape else None,
            DATA_AXIS if self.expert_parallel else None,
        )
        if cfg.zero1 and cfg.fsdp:
            raise ValueError(
                "zero1 and fsdp are mutually exclusive (fsdp subsumes "
                "zero1's moment sharding and additionally shards params)"
            )
        if cfg.zero1 or cfg.fsdp:
            # ZeRO: chunked AdamW with data-axis-sharded state
            # (parallel/zero.py::Zero1Adam / FsdpAdam). Tensor-sharded
            # leaves chunk their LOCAL shard per (data, tensor)
            # coordinate (round 5). Expert-parallel leaves (late round
            # 5 — the last ZeRO rejection removed) keep NATURAL-shaped
            # LOCAL state: EP already shards them over the data axis,
            # so their optimizer memory is divided by construction and
            # the update needs no collectives (the all_to_all
            # transpose delivered full expert grads; sync_grad's EP
            # scaling moves into the optimizer's _expert_mean).
            from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import (
                FsdpAdam,
                FsdpLion,
                FsdpSgdLM,
                Zero1Adam,
                Zero1Lion,
                Zero1SgdLM,
                spec_dim,
            )
            from cs744_pytorch_distributed_tutorial_tpu.train.state import (
                make_schedule,
            )

            self.tx = None
            # zero1 carries all three registry rules chunk-wise (round
            # 5 — lion halves the sharded state, sgd matches the
            # torch-SGD chain); the b2 defaults mirror make_optimizer's
            # optax constructors.
            rules = {
                "adamw": ((Zero1Adam, FsdpAdam), 0.999),
                "lion": ((Zero1Lion, FsdpLion), 0.99),
                "sgd": ((Zero1SgdLM, FsdpSgdLM), 0.0),
            }
            try:
                (z1_cls, fsdp_cls), b2 = rules[cfg.optimizer]
            except KeyError:
                raise ValueError(
                    f"unknown optimizer {cfg.optimizer!r}; choose from "
                    "('sgd', 'adamw', 'lion')"
                ) from None
            opt_cls = fsdp_cls if cfg.fsdp else z1_cls
            self._zero1_opt = opt_cls(
                make_schedule(cfg), b1=cfg.momentum, b2=b2, eps=1e-8,
                weight_decay=cfg.weight_decay, axis_name=DATA_AXIS,
                axis_size=self.data_size, seq_axis=SEQ_AXIS,
                seq_size=self.seq_size,
                shard_axes=(
                    {TENSOR_AXIS: self.tensor_size}
                    if TENSOR_AXIS in self.mesh.shape
                    else None
                ),
                clip_norm=cfg.grad_clip_norm,
                bucket_bytes=self._bucket_bytes,
                overlap=self._overlap,
            )
            # The original (tensor-aware) specs drive the chunk layout;
            # chunked leaves shard [dp, chunk] over data or
            # [dp, tp, chunk] over (data, tensor).
            self._orig_param_specs = self.param_specs

            def chunk_spec(_, spec):
                if spec_dim(spec, DATA_AXIS) is not None:
                    # Expert-parallel leaf: natural-shaped local state,
                    # sharded exactly like the param.
                    return spec
                if (
                    self.tensor_size > 1
                    and spec_dim(spec, TENSOR_AXIS) is not None
                ):
                    return P(DATA_AXIS, TENSOR_AXIS)
                return P(DATA_AXIS)

            moment_specs = jax.tree.map(
                chunk_spec, param_shapes, self._orig_param_specs
            )
            self.opt_specs = {
                name: moment_specs for name in opt_cls.MOMENTS
            }
            self.opt_specs["count"] = P()
            # Mesh-elastic resume: re-chunk flat [dp_old(, tp), chunk]
            # checkpoint state to the current data_parallel's layout
            # (parallel/zero.py::make_elastic_adapt; moments always,
            # chunked params too under fsdp; tensor coordinates are
            # layout-pinned).
            from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import (
                chunk_local_sizes,
                make_elastic_adapt,
            )

            self._zero_elastic_adapt = make_elastic_adapt(
                chunk_local_sizes(
                    param_shapes,
                    self._orig_param_specs,
                    {TENSOR_AXIS: self.tensor_size},
                    # Expert-parallel leaves restore by plain
                    # re-sharding (natural global shapes) — no re-chunk.
                    exclude_axis=DATA_AXIS,
                ),
                prefixes=("opt_state/mu/", "opt_state/nu/")
                + (("params/",) if cfg.fsdp else ()),
            )
            if cfg.fsdp:
                # Params live as flat chunked shards too: the original
                # full shapes/dtypes are the unshard template, and the
                # LOCAL shapes (tensor dim divided) template the
                # in-shard_map gather (shared rule:
                # parallel/zero.py::local_chunk_shapes).
                from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import (
                    local_chunk_shapes,
                )

                self._param_shapes = param_shapes
                self._local_param_shapes = local_chunk_shapes(
                    param_shapes,
                    self._orig_param_specs,
                    {TENSOR_AXIS: self.tensor_size},
                )
                self.param_specs = moment_specs
        else:
            self._zero1_opt = None
            self._orig_param_specs = self.param_specs
            if cfg.grad_clip_norm is not None and (
                self.tensor_size > 1 or self.expert_parallel
            ):
                from cs744_pytorch_distributed_tutorial_tpu.train.state import (
                    clip_by_global_norm_sharded,
                )

                self.tx = optax.chain(
                    clip_by_global_norm_sharded(
                        cfg.grad_clip_norm, self.param_specs
                    ),
                    make_optimizer(cfg.replace(grad_clip_norm=None)),
                )
            else:
                self.tx = make_optimizer(cfg)
            self.opt_specs = optax.tree_map_params(
                self.tx,
                lambda _, spec: spec,
                jax.eval_shape(self.tx.init, param_shapes),
                self.param_specs,
                transform_non_params=lambda _: P(),
            )
        if self._compress:
            # Error-feedback residuals ride inside the optimizer state as
            # a 2-tuple (tx_state, ef_tree): they are step-carried
            # per-DEVICE state, and train_step's (params, opt_state)
            # signature — and the checkpoint layout, which snapshots
            # opt_state — stays unchanged. ef leaves are
            # [data_parallel, *param_shape] f32 sharded over the data axis.
            self.opt_specs = (
                self.opt_specs,
                jax.tree.map(lambda _: P(DATA_AXIS), param_shapes),
            )
        self._build_steps()

    def _init_model(self) -> TransformerLM:
        """Clone for host-side init: no mesh axes in scope, GLOBAL shapes
        (attention carries no parameters; tensor- and expert-sharded
        kernels are initialized full-size then sharded by ``device_put``)."""
        return self.model.clone(
            seq_axis=None,
            seq_axis_size=1,
            tensor_axis=None,
            tensor_axis_size=1,
            expert_axis=None,
            expert_axis_size=1,
        )

    def decode_model(self) -> TransformerLM:
        """Single-sequence clone for autoregressive generation
        (``infer/generate.py``): no mesh axes, dense attention over the
        cache. Trained params drop in directly — they are global arrays
        (jit re-gathers tensor/expert shards as needed) and attention
        carries no parameters, so the trees are identical::

            params, _, _ = trainer.fit(tokens, steps)
            generate = make_generator(trainer.decode_model(),
                                      max_new_tokens=64, temperature=0.8)
            out = generate(params, prompt, jax.random.key(0))
        """
        return self._init_model().clone(
            attention_impl="dense", flash_interpret=None, remat=False
        )

    def quantized_decode_model(
        self, modules: str = "head", kv_cache: bool = False
    ) -> TransformerLM:
        """``decode_model`` with weight-only int8 projections
        (``ops/quant.py``): selected Dense kernels are stored int8 + a
        per-channel scale and dequantized inside the Pallas matmul.
        ``modules="head"`` (default) quantizes only ``lm_head`` — the
        measured decode win (the wide head matmul is most of the weight
        bytes at LM vocab sizes, while per-call dispatch cost makes the
        small per-layer projections a loss on the v5e);
        ``modules="all"`` quantizes every projection — the
        weight-MEMORY-bound choice. ``kv_cache=True`` additionally stores
        the KV cache int8 with per-row scales (``quantize_kv``) — the
        LONG-context lever, orthogonal to the weight scopes (params need
        no conversion for it; the cache is written at run time). Pair
        with ``quantize_for_decode`` using the same ``modules``::

            qparams = trainer.quantize_for_decode(
                trainer.gather_for_decode(params))
            gen = make_generator(trainer.quantized_decode_model(),
                                 max_new_tokens=64, temperature=0.0)
            out = gen(qparams, prompt, jax.random.key(0))
        """
        if self.cfg.tie_embeddings and modules == "head":
            # Tied embeddings have no lm_head module (logits ride
            # tok_embed.attend, deliberately float), so the default
            # weight scope quantizes NOTHING. With kv_cache=True that is
            # fine — the KV cache is the requested lever and needs no
            # weight scope — so return the KV-only model (its own error
            # message used to recommend exactly this call). Without it
            # the whole request would be a silent no-op: raise.
            if kv_cache:
                return self.decode_model().clone(quant_kv_cache=True)
            raise ValueError(
                "int8-decode scope 'head' is a no-op with tied embeddings "
                "(no lm_head exists; the attend path stays float) — use "
                "modules='all' for the per-layer projections, or "
                "kv_cache=True which needs no weight scope"
            )
        return self.decode_model().clone(
            quant_dense=True,
            quant_modules=_resolve_quant_modules(modules),
            quant_kv_cache=kv_cache,
        )

    @staticmethod
    def quantize_for_decode(params, modules: str = "head"):
        """Convert trained (full, host-side) params into the int8 tree a
        ``quantized_decode_model(modules)`` expects — see
        ``ops/quant.py::quantize_lm_params``."""
        from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
            quantize_lm_params,
        )

        return quantize_lm_params(params, _resolve_quant_modules(modules))

    def gather_for_decode(self, params):
        """Materialize tensor-/expert-sharded params as full host arrays
        (one all-gather + fetch) for the non-shard_map decode path
        (``decode_model``). Host-side on purpose: the training mesh's
        axes are Explicit (sharding-in-types), and arrays carried on
        that mesh cannot mix with the decode program's mesh-free
        intermediates — while plain host arrays re-place under the
        decode jit's own defaults. The tensor-parallel path
        (``tp_decode_model``) needs none of this. FSDP-chunked params
        unshard to the original shapes first (host math — the global
        ``[dp, chunk]`` arrays already hold every chunk)."""
        from jax.sharding import NamedSharding

        if self.cfg.fsdp:
            # unshard_host is already host-side numpy (no collectives);
            # tensor-sharded leaves reassemble from their per-shard rows.
            return self._zero1_opt.unshard_host(
                params, self._param_shapes, self._orig_param_specs
            )
        rep = NamedSharding(self.mesh, P())
        return jax.tree.map(
            lambda x: jax.device_get(jax.device_put(x, rep)), params
        )

    def tp_decode_model(self) -> TransformerLM:
        """Tensor-parallel decode clone: no sequence axis (the KV cache
        holds the full sequence), tensor axis KEPT — each device caches
        its local heads and generation runs inside shard_map on the
        trainer's sharded params, no full gather
        (``infer/generate.py``'s ``mesh=`` path)::

            gen = make_generator(trainer.tp_decode_model(),
                                 max_new_tokens=32, temperature=0.0,
                                 mesh=trainer.mesh,
                                 param_specs=trainer.param_specs)
            out = gen(params, prompt, jax.random.key(0))
        """
        if self.expert_parallel:
            raise ValueError(
                "tp_decode_model does not support expert parallelism; "
                "decode EP models from gathered params (decode_model)"
            )
        if self.cfg.fsdp:
            raise ValueError(
                "tp_decode_model does not apply to fsdp-chunked params "
                "(they are flat [dp(, tp), chunk] shards, not the "
                "tensor-sharded layout this model expects); use "
                "gather_for_decode + decode_model"
            )
        return self.model.clone(
            seq_axis=None,
            seq_axis_size=1,
            attention_impl="dense",
            flash_interpret=None,
            remat=False,
        )

    def _local_batch_shape(self) -> tuple[int, int]:
        return (
            self.cfg.global_batch_size // self.data_size,
            self.cfg.seq_len // self.seq_size,
        )

    # ------------------------------------------------------------------ build
    def _build_steps(self) -> None:
        model, tx = self.model, self.tx
        zero1_opt = self._zero1_opt
        batch_spec = P(DATA_AXIS, SEQ_AXIS)  # [batch, seq] token grids
        param_specs, opt_specs = self.param_specs, self.opt_specs
        has_tensor = TENSOR_AXIS in self.mesh.shape
        data_size, seq_size = self.data_size, self.seq_size
        aux_coef = self.cfg.moe_aux_coef
        moe_on = self.cfg.moe_experts > 0

        def mean_over_replicas(x):
            x = lax.pmean(lax.pmean(x, DATA_AXIS), SEQ_AXIS)
            return lax.pmean(x, TENSOR_AXIS) if has_tensor else x

        def sync_grad(g, spec):
            # Expert-SHARDED params (EP over the data axis, spec mentions
            # DATA_AXIS): the all_to_all transpose already summed each
            # shard's grad over its whole data row, so the remaining job
            # is the sum over seq replicas and the 1/num_devices of the
            # global-mean loss — psum(seq) / (data*seq), then the tensor
            # drift-guard pmean (expert compute is replicated over tensor).
            if DATA_AXIS in spec:
                g = lax.psum(g, SEQ_AXIS) / (data_size * seq_size)
                return lax.pmean(g, TENSOR_AXIS) if has_tensor else g
            # Data/seq axes replicate every other param -> average there.
            # Tensor-SHARDED params (spec mentions the axis) have purely
            # local grads — the Megatron f/g boundaries already routed the
            # cross-shard terms — while replicated params' grads are full
            # and identical across the tensor axis (the f-boundary psum),
            # so the pmean is a drift guard, not a correction.
            g = lax.pmean(lax.pmean(g, DATA_AXIS), SEQ_AXIS)
            if has_tensor and TENSOR_AXIS not in spec:
                g = lax.pmean(g, TENSOR_AXIS)
            return g

        accum = self.cfg.accum_steps
        compress = self._compress
        bucket_bytes = self._bucket_bytes
        overlap = self._overlap
        if compress:
            from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
                sync_grads_compressed,
            )
        if overlap:
            from cs744_pytorch_distributed_tutorial_tpu.parallel import (
                overlap as OV,
            )

            overlap_hp = dict(
                lr=self.cfg.learning_rate,
                momentum=self.cfg.momentum,
                weight_decay=self.cfg.weight_decay,
            )

        fused_xent = self.cfg.fused_xent
        xent_interpret = self._flash_interpret
        smoothing = self.cfg.label_smoothing
        if not 0.0 <= smoothing < 1.0:
            raise ValueError(
                f"label_smoothing must be in [0, 1), got {smoothing}"
            )
        if smoothing and fused_xent:
            raise ValueError(
                "label_smoothing is incompatible with fused_xent: the Pallas "
                "kernel computes plain CE"
            )

        dropout = self.cfg.dropout_rate
        seed = self.cfg.seed

        is_fsdp = self.cfg.fsdp
        orig_specs = self._orig_param_specs
        if is_fsdp:
            # gather_params reconstructs each device's LOCAL view: the
            # full leaf for replicated params, the tensor shard for
            # tensor-sharded ones; expert-parallel leaves pass through
            # (already local).
            shapes_tree = self._local_param_shapes
            unshard = lambda ch: zero1_opt.gather_params(
                ch, shapes_tree, orig_specs
            )
        else:
            unshard = lambda p: p

        def local_step(params, opt_state, tokens, targets, step):
            if compress:
                # (tx_state, ef_tree) — see __init__'s opt_specs comment.
                opt_state, ef = opt_state
            # Dropout rng: keyed by (step, data index, seq index) — NOT
            # the tensor index: the MLP dropout applies to row-parallel
            # partial sums before their psum, so tensor shards must draw
            # IDENTICAL masks for the sum to remain a dropout of the sum.
            # Data/seq shards hold different tokens and fold their axis
            # indices for independent masks.
            drop_base = jax.random.fold_in(jax.random.key(seed), step)
            drop_base = jax.random.fold_in(
                drop_base, lax.axis_index(DATA_AXIS)
            )
            drop_base = jax.random.fold_in(drop_base, lax.axis_index(SEQ_AXIS))

            def loss_fn(p, toks, tgts, drop_key):
                # mutable=["losses"] collects each MoE layer's sown
                # load-balancing aux term (empty when the FFNs are
                # dense); "metrics" its sown drop rate (monitoring only
                # — kept out of the objective).
                apply_kw = (
                    dict(rngs={"dropout": drop_key}, deterministic=False)
                    if dropout > 0.0
                    else {}
                )
                logits, mut = model.apply(
                    {"params": p}, toks, mutable=["losses", "metrics"],
                    **apply_kw
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

                aux = moe_aux_loss(mut)
                # Name-filtered collection: the "metrics" collection now
                # carries more than the drop rate (each MoE layer also
                # sows its expert-load entropy), so averaging ALL leaves
                # would mix the two.
                sown = mut.get("metrics", {})
                drop = sown_scalar_mean(sown, "moe_drop")
                ent = sown_scalar_mean(sown, "moe_load_entropy")
                return ce + aux_coef * aux, (aux, drop, ent)

            def diff_loss(p_or_chunks, toks, tgts, key):
                # FSDP differentiates THROUGH the just-in-time unshard:
                # the all_gather's transpose (psum_scatter) delivers the
                # grads pre-scattered to each device's chunk. Identity
                # otherwise.
                return loss_fn(unshard(p_or_chunks), toks, tgts, key)

            # Differentiate the LOCAL loss, then average grads explicitly
            # per mesh axis. Under ``check_vma=False`` (which the
            # axis-index-routed attention collectives require) shard_map
            # disables the replication analysis that would let the AD
            # transpose insert the psum automatically — the engine's
            # 'auto' trick (train/engine.py) — so relying on it here
            # silently yields per-device partial grads and divergent
            # replicas. Autodiff through the ring/all-to-all collectives
            # is joint (ppermute transposes to the reverse ring), so each
            # device's grad already carries the cross-shard attention
            # terms; ``sync_grad`` supplies the final cross-device
            # averaging (spec-aware: tensor-sharded leaves stay local).
            # Equal token counts per shard make pmean of local means the
            # exact global mean.
            if accum == 1:
                with jax.named_scope("graftscope/fwd_bwd"):
                    (local_loss, (aux, drop, ent)), grads = jax.value_and_grad(
                        diff_loss, has_aux=True
                    )(params, tokens, targets, drop_base)
            else:
                # Gradient accumulation: scan over microbatches so only
                # one microbatch's activations are live at a time; the
                # gradient SUM accumulates in the carry and averages out.
                mb_tok = tokens.reshape(accum, -1, tokens.shape[-1])
                mb_tgt = targets.reshape(accum, -1, targets.shape[-1])
                mb_keys = jax.random.split(drop_base, accum)

                def body(carry, mb):
                    g_sum, l_sum, a_sum, d_sum, e_sum = carry
                    with jax.named_scope("graftscope/fwd_bwd"):
                        (l, (a, dr, en)), g = jax.value_and_grad(
                            diff_loss, has_aux=True
                        )(params, mb[0], mb[1], mb[2])
                    return (
                        jax.tree.map(jnp.add, g_sum, g),
                        l_sum + l,
                        a_sum + a,
                        d_sum + dr,
                        e_sum + en,
                    ), None

                zeros = jax.tree.map(jnp.zeros_like, params)
                z = jnp.zeros((), jnp.float32)
                (g_sum, l_sum, a_sum, d_sum, e_sum), _ = lax.scan(
                    body, (zeros, z, z, z, z), (mb_tok, mb_tgt, mb_keys)
                )
                grads = jax.tree.map(lambda g: g / accum, g_sum)
                local_loss = l_sum / accum
                aux, drop = a_sum / accum, d_sum / accum
                ent = e_sum / accum
            loss = mean_over_replicas(local_loss)
            if zero1_opt is not None:
                # ZeRO-1 consumes the RAW local grads: its per-leaf
                # psum_scatter IS the data-axis reduction (half an
                # allreduce's bytes, delivered pre-sharded) and the seq
                # pmean runs on the 1/dp chunk inside. The original
                # specs tell it which leaves are tensor shards (chunked
                # per (data, tensor) coordinate) and drive the exact
                # global-norm clip when configured. With overlap the
                # apply emits its own per-bucket scatter/apply/gather
                # lanes, so the tree-wide scope would mislabel them.
                scope = (
                    contextlib.nullcontext()
                    if overlap
                    else jax.named_scope("graftscope/optimizer_zero1")
                )
                with scope:
                    if compress:
                        # zero1's int8+EF wire (sync_overlap='bucket+int8'):
                        # residuals thread through the bucketed apply.
                        ef_local = jax.tree.map(lambda a: a[0], ef)
                        params, opt_state, ef_out = zero1_opt.apply(
                            params, opt_state, grads, orig_specs,
                            ef=ef_local,
                        )
                        ef = jax.tree.map(lambda a: a[None], ef_out)
                    else:
                        params, opt_state = zero1_opt.apply(
                            params, opt_state, grads, orig_specs
                        )
            elif overlap:
                # Overlapped schedule (parallel/overlap.py): per-bucket
                # sync + per-bucket torch-SGD apply over reverse-order
                # buckets, one fused program with no tree-wide barrier.
                # Pure DP + fixed-LR SGD (validated in __init__), so the
                # data-axis mean is the whole sync and the flat update is
                # bitwise the optax chain. grads rebind to the synced
                # mean so the telemetry norms below read the same tree
                # the fused path logs.
                ef_local = (
                    jax.tree.map(lambda a: a[0], ef) if compress else None
                )
                trace, rebuild = OV.split_momentum(opt_state)
                params, new_trace, grads, ef_out = OV.overlapped_sync_apply(
                    grads,
                    params,
                    trace,
                    name="allreduce",
                    axis_name=DATA_AXIS,
                    axis_size=data_size,
                    bucket_bytes=bucket_bytes,
                    ef=ef_local,
                    **overlap_hp,
                )
                opt_state = rebuild(new_trace)
                if compress:
                    ef = jax.tree.map(lambda a: a[None], ef_out)
            elif compress:
                # Quantized bucket all-reduce of the accumulated local
                # gradient with this device's error-feedback residual
                # folded in; the new residual rides back in opt_state.
                # Pure DP (validated in __init__), so this one collective
                # IS the whole sync — no seq/tensor replicas to average.
                ef_local = jax.tree.map(lambda a: a[0], ef)
                grads, ef_out = sync_grads_compressed(
                    grads,
                    ef_local,
                    "int8_allreduce",
                    DATA_AXIS,
                    data_size,
                    bucket_bytes=bucket_bytes,
                )
                ef = jax.tree.map(lambda a: a[None], ef_out)
                with jax.named_scope("graftscope/optimizer"):
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
            else:
                # graftscope Perfetto label for the per-leaf spec-aware
                # pmean sync (the compressed path is labeled inside
                # sync_grads_compressed).
                with jax.named_scope("graftscope/sync/dp_pmean"):
                    grads = jax.tree.map(sync_grad, grads, param_specs)
                with jax.named_scope("graftscope/optimizer"):
                    updates, opt_state = tx.update(grads, opt_state, params)
                    params = optax.apply_updates(params, updates)
            if compress:
                opt_state = (opt_state, ef)
            metrics = {"loss": loss}
            with jax.named_scope("graftscope/telemetry"):
                if zero1_opt is None:
                    # Telemetry norms, on device at the trees' native
                    # sharding: spec-aware psums give the GLOBAL norms
                    # (tensor/expert-sharded leaves summed over their axes,
                    # replicated leaves counted once). zero1/fsdp omit them —
                    # the synced gradient tree never materializes there.
                    metrics["grad_norm"] = tree_l2_norm(grads, param_specs)
                    metrics["param_norm"] = tree_l2_norm(params, param_specs)
                if moe_on:
                    # MoE observability (VERDICT r3 #6): the load-balancing
                    # aux term, the capacity-overflow drop rate, and the
                    # expert-load entropy, averaged over replicas like the loss.
                    metrics["moe_aux"] = mean_over_replicas(aux)
                    metrics["moe_drop"] = mean_over_replicas(drop)
                    metrics["moe_load_entropy"] = mean_over_replicas(ent)
            return params, opt_state, metrics

        metric_specs = {"loss": P()}
        if zero1_opt is None:
            metric_specs.update({"grad_norm": P(), "param_norm": P()})
        if moe_on:
            metric_specs.update(
                {"moe_aux": P(), "moe_drop": P(), "moe_load_entropy": P()}
            )
        mapped_train = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(param_specs, opt_specs, batch_spec, batch_spec, P()),
            out_specs=(param_specs, opt_specs, metric_specs),
            check_vma=False,
        )
        # Un-jitted, un-donated handle for instrumentation: re-jit it
        # WITHOUT donation and repeated calls on the same
        # (params, opt_state) don't hit deleted buffers.
        self.mapped_train = mapped_train
        mapped_step = jax.jit(mapped_train, donate_argnums=(0, 1))

        def train_step(params, opt_state, tokens, targets, step=0):
            """``step`` keys the dropout mask stream (ignored at
            dropout_rate=0, so existing call sites stay valid); ``fit``
            threads the real step index. A host int is converted under a
            scoped transfer_guard("allow"): the 4-byte scalar transfer
            is deliberate, and callers that keep a device-resident
            counter pass it through untouched."""
            if not isinstance(step, jax.Array):
                with jax.transfer_guard("allow"):
                    step = jnp.int32(step)
            return mapped_step(params, opt_state, tokens, targets, step)

        self.train_step = train_step
        # The raw jitted step, for AOT lower/compile with explicit
        # compiler_options or for memory_analysis(); call with an
        # explicit jnp.int32 step argument.
        self.jitted_train_step = mapped_step

        def local_eval(params, tokens, targets):
            logits = model.apply({"params": unshard(params)}, tokens)
            local = optax.softmax_cross_entropy_with_integer_labels(
                logits, targets
            ).mean()
            return {"loss": mean_over_replicas(local)}

        self.eval_step = jax.jit(
            jax.shard_map(
                local_eval,
                mesh=self.mesh,
                in_specs=(param_specs, batch_spec, batch_spec),
                out_specs={"loss": P()},
                check_vma=False,
            )
        )

    # ------------------------------------------------------------------ state
    def init(self, seed: int | None = None):
        """Host-side init at GLOBAL shapes (the ``_init_model`` clone has
        no mesh axes in scope), then laid out per the partition specs:
        tensor-sharded kernels split over the tensor axis, everything
        else replicated. The same global params produce the same model
        function at every tensor_parallel setting (tested)."""
        cfg = self.cfg
        # Init is one-time setup: eager constant/key creation here may
        # transfer host scalars, which is fine. Scoping "allow" keeps
        # init working under an outer transfer_guard("disallow") (the
        # strict discipline is for the steady-state step path).
        with jax.transfer_guard("allow"):
            return self._init_impl(cfg, seed)

    def _init_impl(self, cfg, seed):
        dummy = jnp.zeros(self._local_batch_shape(), jnp.int32)
        variables = self._init_model().init(
            jax.random.key(cfg.seed if seed is None else seed), dummy
        )
        params = variables["params"]
        opt_state = (
            self._zero1_opt.init(params, self._orig_param_specs)
            if self._zero1_opt is not None
            else self.tx.init(params)
        )
        if self._compress:
            # Zero error-feedback residuals, one [data_parallel, *shape]
            # f32 stack per param (each device's row is ITS residual).
            opt_state = (
                opt_state,
                jax.tree.map(
                    lambda p: jnp.zeros(
                        (self.data_size, *p.shape), jnp.float32
                    ),
                    params,
                ),
            )
        if self.cfg.fsdp:
            # Params live chunked from here on (the chunked
            # self.param_specs lay them out below).
            params = self._zero1_opt.shard_params(
                params, self._orig_param_specs
            )
        mesh = self.mesh
        params = jax.tree.map(
            lambda p, s: host_to_global(p, NamedSharding(mesh, s)),
            params,
            self.param_specs,
        )
        opt_state = jax.tree.map(
            lambda o, s: host_to_global(o, NamedSharding(mesh, s)),
            opt_state,
            self.opt_specs,
        )
        return params, opt_state

    def shard_batch(self, tokens):
        """[B, seq_len + 1] host tokens -> (inputs, targets) global arrays
        sharded [data, seq]. The shifted targets are materialized BEFORE
        sharding, so each sequence shard's last position still has its
        true next token as the label (no cross-shard halo needed)."""
        inputs = tokens[:, :-1]
        targets = tokens[:, 1:]
        sharding = NamedSharding(self.mesh, P(DATA_AXIS, SEQ_AXIS))
        return (
            host_to_global(inputs, sharding),
            host_to_global(targets, sharding),
        )

    def evaluate(self, params, tokens) -> dict[str, float]:
        """Held-out evaluation over ``tokens`` [N, seq_len + 1]: mean
        next-token cross-entropy and perplexity (``evaluate_heldout``)."""
        return evaluate_heldout(self, params, tokens)

    # ------------------------------------------------------------------ loop
    def fit(self, tokens, steps: int) -> tuple[Any, Any, list[float]]:
        """Cycle batches of ``global_batch_size`` sequences from ``tokens``
        [N, seq_len + 1] until ``steps`` total steps have run.

        With ``cfg.checkpoint_dir`` set, training resumes exactly from the
        newest checkpoint: the batch at step k is a pure function of k, so
        a restarted run replays the identical remaining plan.
        """
        cfg = self.cfg
        params, opt_state = self.init()
        start_step = 0
        ckpt = None
        mem = self.memstore
        if cfg.checkpoint_dir:
            from cs744_pytorch_distributed_tutorial_tpu.utils.checkpoint import (
                Checkpointer,
            )

            ckpt = Checkpointer(cfg.checkpoint_dir)
        # Restore-tier arbitration (same rule as the CIFAR engine): the
        # newest recoverable state wins; the in-memory snapshot (zero
        # filesystem reads) wins ties with the disk tier. Both tiers
        # pass the same ZeRO elastic adapt, so a snapshot taken at one
        # data_parallel re-chunks onto another exactly like a disk
        # checkpoint would.
        restore_source = None  # emitted once telemetry exists below
        adapt = (
            self._zero_elastic_adapt if self._zero1_opt is not None else None
        )
        template = LMState(jnp.zeros((), jnp.int32), params, opt_state)
        mem_step = mem.latest_step() if mem is not None else None
        disk_step = ckpt.latest_step() if ckpt is not None else None
        restored = None
        if mem_step is not None and (disk_step is None or disk_step <= mem_step):
            restored, restore_source = (
                mem.restore_latest(template, adapt=adapt),
                "memory",
            )
        elif disk_step is not None:
            restored, restore_source = (
                ckpt.restore_latest(template, adapt=adapt),
                "disk",
            )
        if restored is not None:
            start_step = int(jax.device_get(restored.step))
            params, opt_state = restored.params, restored.opt_state
        losses: list[float] = []
        # Per-step metrics beyond the loss (MoE aux/drop when routed
        # FFNs are active) — inspect after fit() via ``self.history``.
        self.history: dict[str, list[float]] = {"loss": losses}
        n = len(tokens)
        b = cfg.global_batch_size

        # ---- telemetry (obs/): ring always (watchdog post-mortems),
        # manifest + JSONL when cfg.metrics_dir is set. fit() fetches
        # every metric scalar per step already (losses/history), so
        # emission adds no transfers.
        from cs744_pytorch_distributed_tutorial_tpu.obs.flops import (
            transformer_train_flops_per_token,
        )
        from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
            sync_wire_bytes,
        )
        from cs744_pytorch_distributed_tutorial_tpu.train.state import (
            make_schedule,
        )

        n_params = sum(
            int(l.size) for l in jax.tree_util.tree_leaves(params)
        )
        # Data-parallel gradient-sync bytes of the active layout; the
        # tensor/seq-axis collectives (activations, f/g boundaries) are
        # deliberately out of scope — this ledger tracks the DP wire the
        # compression strategies target.
        if cfg.fsdp:
            dp_strategy = "fsdp"
        elif self._zero1_opt is not None:
            # grad_compress routes the accounting to the zero1_int8 wire
            # (quantized scatter + float delta gather) inside
            # sync_wire_bytes.
            dp_strategy = "zero1"
        elif self._compress:
            dp_strategy = "int8_allreduce"
        else:
            dp_strategy = "allreduce"
        wire_bytes = sync_wire_bytes(
            params,
            dp_strategy,
            self.data_size,
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
            every=cfg.metrics_every,
            run="lm",
            flops_per_step=(
                transformer_train_flops_per_token(n_params)
                * b
                * cfg.seq_len
            ),
            n_chips=int(self.mesh.devices.size),
            device_kind=jax.devices()[0].device_kind,
        )
        telemetry.write_manifest(
            config=cfg,
            mesh=self.mesh,
            n_params=n_params,
            grad_sync_bytes_per_step=wire_bytes,
        )
        if restore_source is not None:
            telemetry.emit_event(
                "restore", source=restore_source, step=start_step
            )

        # ---- flight recorder (obs/flight.py): per-step wall ring + MAD
        # straggler detection, dumped as events on watchdog fire,
        # uncaught exception, or SIGTERM (same wiring as the CIFAR engine).
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

        watchdog = None
        if cfg.step_timeout_s:
            from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
                StepWatchdog,
            )

            watchdog = StepWatchdog(
                cfg.step_timeout_s,
                metric_ring=telemetry.ring,
                flight_recorder=flight,
            )
        if cfg.halt_on_nonfinite:
            from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
                NonFiniteLossError,
            )
        # fit() fetches every loss, so the traced steps' device work is
        # already fenced when the capture closes.
        capture = None
        if cfg.profile_dir:
            from cs744_pytorch_distributed_tutorial_tpu.utils import profiling

            capture = profiling.StepCapture(
                cfg.profile_dir, cfg.profile_start_step, cfg.profile_num_steps, "lm"
            )

        # Divergence-safe checkpointing (the CIFAR engine's ordering,
        # train/engine.py): the loss fetched at step k is the forward
        # over the params the PREVIOUS update produced, so a due
        # checkpoint is held and persisted only once a later finite
        # loss certifies its params — restart recovery can never
        # restore a state whose own forward diverged. KEEP IN SYNC with
        # the sibling implementations in train/engine.py (epoch loop,
        # watchdog-guarded saves) and parallel/pipeline.py::fit.
        pending_ckpt = None
        x = y = None
        prev_mono = None  # per-step wall clock for the straggler ring
        step = start_step
        try:
            for step in range(start_step, steps):
                lo = (step * b) % max(n - b + 1, 1)
                fetch_ctx = capture.fetch() if capture else contextlib.nullcontext()
                with fetch_ctx:
                    x, y = self.shard_batch(tokens[lo : lo + b])
                if capture:
                    # The step index only by its type: lowering needs no
                    # value, and a device scalar a step would cost a put.
                    capture.open_if_due(
                        step, self.jitted_train_step, params, opt_state, x, y,
                        jax.ShapeDtypeStruct((), jnp.int32),
                    )
                # First executed step blocks on XLA compilation — exempt
                # it from the watchdog (same policy as the CIFAR engine).
                arm_now = watchdog is not None and step > start_step
                if arm_now:
                    watchdog.arm()
                step_ctx = capture.step(step) if capture else contextlib.nullcontext()
                try:
                    with step_ctx:
                        params, opt_state, m = self.train_step(
                            params, opt_state, x, y, step
                        )
                        # (wall, mono) bracketing the blocking fetch:
                        # obs/fleet.py aligns these across ranks for
                        # collective-skew attribution.
                        sync_enter_wall = time.time()
                        sync_enter_mono = time.monotonic()
                        loss = float(m["loss"])
                        sync_exit_wall = time.time()
                        sync_exit_mono = time.monotonic()
                finally:
                    if arm_now:
                        watchdog.disarm()
                # Straggler ring: inter-iteration wall time (fit fetches
                # every loss, so each interval covers one fenced step).
                # The first interval starts AFTER the compile step.
                now_mono = time.monotonic()
                if prev_mono is not None:
                    outlier = straggler.record(step, now_mono - prev_mono)
                    if outlier is not None:
                        telemetry.emit_event("straggler", **outlier)
                prev_mono = now_mono
                if capture:
                    capture.close_if_done(step)
                if cfg.halt_on_nonfinite and not math.isfinite(loss):
                    telemetry.emit_event(
                        "non_finite_loss", step=step, loss=loss
                    )
                    raise NonFiniteLossError(step, loss)
                if pending_ckpt is not None:
                    # This finite loss ran over pending_ckpt's params —
                    # certified; persist on each tier that was due.
                    pstate, to_disk, to_mem = pending_ckpt
                    if to_disk:
                        ckpt.save(pstate)
                    if to_mem:
                        mem.save(pstate)
                    pending_ckpt = None
                losses.append(loss)
                step_fields: dict[str, float] = {}
                for key in m:
                    if key != "loss":
                        val = float(m[key])
                        step_fields[key] = val
                        self.history.setdefault(key, []).append(val)
                if telemetry.due(step):
                    telemetry.emit_step(
                        step,
                        loss=loss,
                        lr=lr_at(step),
                        grad_sync_bytes=wire_bytes,
                        sync_enter_wall=sync_enter_wall,
                        sync_enter_mono=sync_enter_mono,
                        sync_exit_wall=sync_exit_wall,
                        sync_exit_mono=sync_exit_mono,
                        **step_fields,
                    )
                ckpt_due = bool(
                    ckpt
                    and cfg.checkpoint_every
                    and (step + 1) % cfg.checkpoint_every == 0
                )
                snap_due = bool(
                    mem is not None
                    and cfg.snapshot_every
                    and (step + 1) % cfg.snapshot_every == 0
                )
                if ckpt_due or snap_due:
                    if cfg.halt_on_nonfinite:
                        # Copy: train_step donates its input state, so
                        # holding the live arrays across the next step
                        # would reference deleted buffers (same as the
                        # CIFAR engine's pending copy).
                        pending_ckpt = (
                            LMState(
                                jnp.int32(step + 1),
                                jax.tree.map(jnp.copy, params),
                                jax.tree.map(jnp.copy, opt_state),
                            ),
                            ckpt_due,
                            snap_due,
                        )
                    else:
                        live = LMState(jnp.int32(step + 1), params, opt_state)
                        if ckpt_due:
                            ckpt.save(live)
                        if snap_due:
                            # mem.save gathers to host synchronously, so
                            # the live (donatable) buffers are safe to
                            # reuse the moment it returns.
                            mem.save(live)
            if ckpt is not None or mem is not None:
                final = max(steps, start_step)
                if cfg.halt_on_nonfinite and steps > start_step:
                    # Certify the final params with one eval forward
                    # before persisting (no later train step will).
                    f_loss = float(self.eval_step(params, x, y)["loss"])
                    if not math.isfinite(f_loss):
                        raise NonFiniteLossError(steps, f_loss)
                final_state = LMState(jnp.int32(final), params, opt_state)
                if ckpt is not None:
                    ckpt.save(final_state, force=True)
                if mem is not None:
                    mem.save(final_state)
        except BaseException as e:
            # Crash post-mortem: the timing tail goes onto the metric
            # stream before the run dies (KeyboardInterrupt included).
            flight.dump("exception", error=repr(e), step=step)
            raise
        finally:
            if capture:
                capture.close()  # exception path: close any open capture
            flight.uninstall()
            if watchdog is not None:
                watchdog.close()
            if ckpt is not None:
                ckpt.close()
            telemetry.close()
        return params, opt_state, losses


# ------------------------------------------------------------------ graftcheck
def make_lm_trace_entry(**overrides):
    """A graftcheck ``TracedStep`` around the LM engine's real
    ``jitted_train_step`` (the raw jitted ``shard_map`` with
    ``donate_argnums=(0, 1)``): a tiny transformer on the configured
    mesh, carrying the DP-sync contract and the same wire-byte
    accounting ``fit`` writes to telemetry. ``overrides`` are
    ``LMConfig`` fields — the audit tests sweep the DP modes
    (allreduce / int8 / zero1 / fsdp) through this function.
    """
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        TracedStep,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel.sync import (
        expected_collective_schedule,
        sync_units,
        sync_wire_bytes,
    )

    ndev = min(4, len(jax.devices()))
    kw: dict[str, Any] = dict(
        vocab_size=64,
        num_layers=2,
        num_heads=2,
        d_model=32,
        d_ff=64,
        max_seq_len=16,
        seq_len=16,
        global_batch_size=2 * ndev,
        data_parallel=ndev,
        seq_parallel=1,
        attention_impl="dense",
    )
    kw.update(overrides)
    cfg = LMConfig(**kw)
    trainer = LMTrainer(cfg)
    params, opt_state = trainer.init()
    tokens = jnp.zeros((cfg.global_batch_size, cfg.seq_len), jnp.int32)
    targets = jnp.zeros_like(tokens)
    step = jnp.int32(0)

    # Mirror fit()'s dp_strategy resolution and wire accounting exactly.
    if cfg.fsdp:
        dp_strategy = "fsdp"
    elif trainer._zero1_opt is not None:
        dp_strategy = "zero1"
    elif trainer._compress:
        dp_strategy = "int8_allreduce"
    else:
        dp_strategy = "allreduce"
    # The LM sync is per-LEAF for every uncompressed path (sync_grad /
    # Zero1Adam map over leaves); the int8 path and the overlapped
    # schedule bucket (reverse-order buckets under overlap).
    units = sync_units(
        params,
        dp_strategy,
        trainer.data_size,
        bucket_bytes=(
            trainer._bucket_bytes
            if (trainer._compress or trainer._overlap)
            else None
        ),
        grad_compress=cfg.grad_compress,
        overlap=trainer._overlap,
    )
    schedule = expected_collective_schedule(
        dp_strategy,
        trainer.data_size,
        units,
        grad_compress=cfg.grad_compress,
    )
    wire_bytes = sync_wire_bytes(
        params,
        dp_strategy,
        trainer.data_size,
        cfg.grad_compress,
        bucket_bytes=trainer._bucket_bytes,
        overlap=trainer._overlap,
    )
    # graftmem TA008 contract: fsdp shards params AND optimizer moments
    # (args 0 and 1 of jitted_train_step); zero1 shards the moments only.
    if dp_strategy == "fsdp":
        sharded_paths: tuple[str, ...] = ("[0]", "[1]")
    elif dp_strategy == "zero1":
        sharded_paths = ("[1]",)
    else:
        sharded_paths = ()
    return TracedStep(
        name="lm",
        fn=trainer.jitted_train_step,
        args=(params, opt_state, tokens, targets, step),
        axis_sizes=dict(trainer.mesh.shape),
        sync=dp_strategy,
        grad_compress=cfg.grad_compress,
        compute_dtype=cfg.compute_dtype,
        expected_schedule=schedule,
        expected_wire_bytes=float(wire_bytes),
        check_donation=True,
        sharded_param_paths=sharded_paths,
        detail={
            "layers": cfg.num_layers,
            "d_model": cfg.d_model,
            "dp": trainer.data_size,
            "sync_overlap": cfg.sync_overlap,
        },
    )


def _lm_overlap_entry():
    # The pure-DP overlapped schedule needs the fixed-LR SGD recipe (LM
    # defaults to adamw).
    return make_lm_trace_entry(optimizer="sgd", sync_overlap="bucket")


def _lm_overlap_fsdp_entry():
    # Overlapped reduce-scatter schedule under fsdp: the forward gathers
    # params per reverse-order bucket (so the AD transpose scatters the
    # grads per bucket) and the sharded AdamW applies chunk-wise. TA003
    # checks the per-bucket reduce_scatter/all_gather counts and bytes.
    return make_lm_trace_entry(fsdp=True, sync_overlap="bucket")


def _register_lm_trace_entries() -> None:
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        register_entrypoint,
    )

    register_entrypoint("lm", make_lm_trace_entry, tags=("lm",))
    register_entrypoint(
        "lm-overlap", _lm_overlap_entry, tags=("lm", "overlap")
    )
    register_entrypoint(
        "lm-overlap-fsdp",
        _lm_overlap_fsdp_entry,
        tags=("lm", "overlap", "fsdp"),
    )


_register_lm_trace_entries()
