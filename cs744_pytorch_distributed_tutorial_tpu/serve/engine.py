"""Continuous-batching serving engine over a paged KV pool.

Batch-at-a-time generation (``infer/generate.py``) starts and finishes
every request in a batch together: short requests pay for the longest
one, and the dense ``[B, max_seq_len, H, D]`` cache spends HBM on
padding. This engine serves at REQUEST granularity instead:

- decode runs a fixed-shape jitted step over ``num_slots`` slots —
  ``(params, pages, packed, key) -> (pages, next_tokens[B])``, where
  ``packed`` is ONE int32 vector (tokens, lengths, actives, request ids,
  token indices, the page table ``[B,P]``: one put a step) — so batch
  membership changes (retire, refill, preempt) without retracing
  (graftlint GL002; the 0-retrace contract is pinned by
  tests/test_serve.py);
- KV lives in per-layer page POOLS (``[num_pages, page_size, Hkv*D]``,
  the "pages" variable collection of ``mode="paged_decode"``; heads
  folded so that no program copies a pool, ``serve/layout.py``),
  indexed by each slot's row of the page table. Pool memory scales
  with LIVE tokens across the engine, not B x max_seq_len, and a
  retired slot's pages recycle immediately (``pool.PagePool``);
- prefill is its own jitted program per prompt-length bucket: a dense
  causal pass over the padded prompt, the first sampled token, and a
  scatter of the prompt's KV into the slot's pages, one index a whole
  page — all one program, so the hand-off to the decode pool is a
  device-side commit.
  Because it is a separate program from decode, running it on separate
  mesh slices (prefill/decode disaggregation) is a deployment choice,
  not a code change. An admission never waits for the device: it costs
  the host one packed put and one dispatch (a chunk), the step's
  admissions are enqueued back to back, and their first tokens come
  back in ONE blocking fetch after the last is enqueued;
- when the pool runs dry the engine PREEMPTS the most recently admitted
  slot (LIFO victim): its pages free instantly and the request re-queues
  with prompt+generated as the new prompt (recompute-style preemption).
  Admission guarantees any single request fits the pool alone, so the
  oldest request always completes — no deadlock.

A model whose layers differ by kind (``TransformerLM.layer_types``:
sliding-window and full attention mixed) is served from TWO page groups:
the full layers' pages grow with the context as above, the window
layers' live in a second, small pool under a second page table and are
given back as the window passes them, in prefill and in decode
(``window_pool``, ``_window_advance``; docs/serving.md). A model without
window layers has one pool, one table and the programs it always had.

The pools are the model's own: whatever leaves its ``pages`` collection
declares (``_pages_shape_tree``). A layer with latent attention
(``TransformerLM.latent``, models/latent.py) declares ONE pool a
sublayer, ``latent_pages [num_pages, page_size, lanes]``, where another
declares ``key_pages`` and ``value_pages``; the engine builds, donates,
leases and frees it under the same page table (docs/serving.md).

A second kind of cache is addressed by slot and not by page table: a
model with lightning layers (``TransformerLM.slot_state_layers``,
models/lightning.py) declares ``lightning_state [num_slots, H, d, d]``
in the same collection, one float32 state row a slot. It is built,
donated and carried with the pools; the chunk program is told its
slot's row (one more scalar of its packed argument, for such a model
only) and starts it from zero at position 0, and the decode step tells
the model which slots are active, so only their rows advance. A
preemption gives a row up as it gives up pages: the request's
re-admission recomputes it from position 0. A block-sparse layer
(models/block_sparse.py) adds ``compressed_key_pages``, one row a page,
under the page table (docs/serving.md).

Decode attention has two implementations (``paged_attention_impl``):
the "gather" reference is BITWISE-identical to the dense-cache path (the
gathered page view reproduces the cache layout exactly and runs the same
``decode_attention`` einsum — ``parallel/ring_attention.py::
paged_decode_attention``), so greedy engine output matches
``make_generator`` token for token; the "kernel" path runs the Pallas
paged-attention kernel (``ops/paged_attention.py``) that reads ONLY each
slot's live pages straight from the pools — HBM traffic per step scales
with live tokens instead of page capacity, at tolerance-level (online
softmax) parity. "auto" picks the kernel on TPU backends.

Sampling draws each request's token ``t`` from a per-request PRNG stream
keyed by ``(req_id, t)`` — prefill and decode share it, so
recompute-preemption replays a sampled victim's original tokens exactly.
Tokens SURFACE as they decode (``on_token`` callback / ``iter_tokens``),
not at retire; per-token surface times feed the ITL percentiles.

Telemetry flows through ``obs`` sinks as ``kind:"serve"`` records
(per-request TTFT / per-token decode latency / queue time) —
``benchmarks/metrics_summary.py`` renders them and ``regress.py`` gates
them. The decode step registers as graftcheck entrypoints ``lm-serve``
(gather) and ``lm-serve-paged`` (kernel).
"""

from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.infer.generate import (
    check_decode_model,
    sample_tokens,
)
from cs744_pytorch_distributed_tutorial_tpu.serve.pool import PagePool
from cs744_pytorch_distributed_tutorial_tpu.utils import profiling
from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
    DecodeNanError,
)

# cache leaf -> pages leaf: the one-shot prefill's commit scatters the
# dense cache rows a prefill pass wrote into the slot's pages. Names
# mirror the cache's on purpose (models/transformer.py keeps them
# mechanical). A pool with no dense-cache counterpart (a window layer's
# group, a latent layer's ``latent_pages``) is filled by the chunk
# program alone, and the engine refuses such a model the one-shot path.
_CACHE_TO_PAGES = {
    "cached_key": "key_pages",
    "cached_value": "value_pages",
    "key_scale": "key_scale_pages",
    "value_scale": "value_scale_pages",
    "cached_index_key": "index_key_pages",
}


def _named_leaves(tree: Any, name: str) -> list[Any]:
    """Every leaf sown under ``name`` (flax keeps a tuple a name)."""
    return [
        leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if any(getattr(k, "key", None) == name for k in path)
    ]


# The counters a decode step can carry behind its tokens, by the group
# of the model's mechanism that sows them; ``_step_counters`` returns the
# names of those a model has, in this order.
_ROUTING_COUNTERS = (
    "selected_tokens", "scored_tokens", "experts_hit", "expert_ratio_milli",
)
_WINDOW_COUNTERS = ("full_tokens_read", "window_tokens_read")
_LATENT_COUNTERS = ("latent_tokens_read",)
_SHARE_COUNTERS = (
    "held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs",
)
_GROUP_COUNTERS = ("held_group_tokens",)
_SLOT_STATE_COUNTERS = ("lightning_state_updates",)
_BLOCK_SPARSE_COUNTERS = (
    "sparse_selected_tokens", "sparse_live_tokens", "sparse_scored_kernels",
)


def _step_counters(stats: Any, active: jnp.ndarray, num_experts: int):
    """What the model sowed into "serve_stats" in one decode step, over
    the active slots and summed over the layers, as int32 that ride
    behind the step's tokens, and their names. First four: tokens the
    selection kept, tokens the indexer scored, experts (of the
    ``num_experts`` computed here) that received a token, and a thousand
    times the layers' mean of (most tokens on one expert / mean tokens
    an expert). Behind them, from a model with window layers, the keys
    attended on its full and on its window layers; from a model with
    latent attention, the latent rows attended; from an expert layer
    that holds a share or has zero-compute experts, its (token, expert)
    pairs by where the expert is; from a share under group-limited
    choice, the tokens whose kept groups hold a held expert; from a model
    with lightning layers, the (slot, layer) state updates; from one with
    block-sparse layers, the positions its queries attended and the
    positions live, and the compressed keys scored, over the KV groups.
    (None, ()) where the model sowed nothing."""
    selected = _named_leaves(stats, "selected_tokens")
    scored = _named_leaves(stats, "scored_tokens")
    routed = _named_leaves(stats, "expert_idx")
    full_read = _named_leaves(stats, "full_tokens_read")
    window_read = _named_leaves(stats, "window_tokens_read")
    latent_read = _named_leaves(stats, "latent_tokens_read")
    state_updates = _named_leaves(stats, _SLOT_STATE_COUNTERS[0])
    sparse_read = _named_leaves(stats, _BLOCK_SPARSE_COUNTERS[0])
    if not (
        selected or routed or full_read or latent_read or state_updates
        or sparse_read
    ):
        return None, ()
    zero = jnp.int32(0)

    def over_active(leaves):
        return sum((jnp.sum(jnp.where(active, x, 0)) for x in leaves), zero)

    hit, ratio = zero, jnp.float32(0.0)
    for idx in routed:  # [B, K] expert ids of one layer (-1: not here)
        counts = jnp.sum(
            jax.nn.one_hot(idx, num_experts, dtype=jnp.int32)
            * active[:, None, None],
            axis=(0, 1),
        )
        hit = hit + jnp.sum(counts > 0)
        ratio = ratio + jnp.max(counts) * num_experts / jnp.maximum(
            jnp.sum(counts), 1
        )
    milli = jnp.round(1e3 * ratio / max(len(routed), 1))
    counters = [over_active(selected), over_active(scored), hit, milli]
    names = _ROUTING_COUNTERS
    if full_read:
        counters += [over_active(full_read), over_active(window_read)]
        names += _WINDOW_COUNTERS
    if latent_read:
        counters.append(over_active(latent_read))
        names += _LATENT_COUNTERS
    if _named_leaves(stats, _SHARE_COUNTERS[0]):
        counters += [
            over_active(_named_leaves(stats, name)) for name in _SHARE_COUNTERS
        ]
        names += _SHARE_COUNTERS
    if _named_leaves(stats, _GROUP_COUNTERS[0]):
        counters.append(over_active(_named_leaves(stats, _GROUP_COUNTERS[0])))
        names += _GROUP_COUNTERS
    if state_updates:
        counters.append(over_active(state_updates))
        names += _SLOT_STATE_COUNTERS
    if sparse_read:
        counters += [
            over_active(_named_leaves(stats, name))
            for name in _BLOCK_SPARSE_COUNTERS
        ]
        names += _BLOCK_SPARSE_COUNTERS
    return jnp.stack(counters).astype(jnp.int32), names


@dataclass
class ServeConfig:
    """Engine geometry and sampling policy.

    The page-table width ``max_pages_per_slot`` bounds one request's KV
    (``max_pages_per_slot * page_size`` tokens); ``num_pages`` bounds
    the LIVE total across all slots (page 0 is the reserved trash page,
    so ``num_pages - 1`` are allocatable). HBM for KV is
    ``num_pages * page_size`` token-rows per layer — compare against the
    dense generator's ``B * max_seq_len`` (docs/serving.md).
    """

    num_slots: int = 4
    page_size: int = 16
    num_pages: int = 64
    max_pages_per_slot: int = 8
    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    eos_id: int | None = None
    pad_id: int = 0
    seed: int = 0
    # Decode attention over the pools: "gather" materializes each slot's
    # dense page view (reference; bitwise vs the dense cache), "kernel"
    # runs the Pallas paged-attention kernel that reads only live pages
    # (ops/paged_attention.py; tolerance-level parity). "auto" picks
    # "kernel" on TPU backends and "gather" elsewhere — interpret-mode
    # Pallas would throttle a CPU deployment for no byte savings.
    paged_attention_impl: str = "auto"
    # Prefill by chunks of this many tokens through ONE compiled program
    # (mode="paged_prefill": a chunk writes its rows into the slot's
    # pages and attends over what the slot already holds), every chunk
    # of a request inside its admission. None = one dense pass over the
    # whole prompt, a program a power-of-two length bucket.
    prefill_chunk: int | None = None


@dataclass
class Request:
    """One generation request plus its engine-side lifecycle record."""

    prompt: np.ndarray  # [T] int32 token ids
    max_new_tokens: int
    req_id: int = -1
    arrival_time: float | None = None  # loadgen wall-clock; None = submit
    # SLO budgets (serve/guard.py): ``deadline_s`` bounds TOTAL wall time
    # from arrival to retire; ``max_queue_s`` bounds time spent queued
    # before the FIRST admission. None defers to the guard's defaults
    # (and stays unbounded when no guard is armed). Both survive
    # snapshot/resume, so a recovered request keeps its original budget.
    deadline_s: float | None = None
    max_queue_s: float | None = None
    # Terminal disposition, set exactly once by the engine when the
    # request leaves the system: "completed" (budget/EOS), "rejected"
    # (shed at admission control), or "timed_out" (deadline expiry).
    status: str | None = None
    # engine-owned lifecycle state
    generated: list[int] = field(default_factory=list)
    submit_time: float = 0.0
    first_token_time: float | None = None
    done_time: float | None = None
    preemptions: int = 0
    # Wall-clock time each output token SURFACED (streaming delivery) —
    # one entry per produced token, monotone across preemptions (replayed
    # recompute work produces new indices, never re-surfaces old ones).
    # Consecutive diffs are the request's inter-token latencies, so the
    # ITL tail (serve_itl_p99_ms, serve/loadgen.py) honestly includes
    # preemption stalls.
    token_times: list[float] = field(default_factory=list)
    # recompute-preemption carries prompt+generated as the new prompt;
    # these keep the ORIGINAL accounting across the re-queue.
    orig_prompt_len: int = -1
    orig_max_new_tokens: int = -1
    # kill/resume bookkeeping (``ServingEngine.resume``): ``recovered``
    # marks a request replayed from a ServeSnapshot; each entry of
    # ``resume_boundaries`` is the ``token_times`` index of the first
    # post-resume token, so the gap it opens against the previous token
    # — the kill gap, stamped on a DIFFERENT process's clock — can be
    # excluded from ITL percentiles (serve/loadgen.py) and from the
    # tracer's ITL reservoir (obs/serve_trace.py).
    recovered: bool = False
    resume_boundaries: list[int] = field(default_factory=list)
    # set by ``resume`` on in-flight requests: the next admission is a
    # resume-replay (span vocabulary), not an ordinary recompute.
    replay_pending: bool = False

    @property
    def output_tokens(self) -> int:
        done = self.orig_max_new_tokens - self.max_new_tokens
        return done + len(self.generated)

    @property
    def terminal_status(self) -> str | None:
        """One of ``completed`` / ``rejected`` / ``timed_out`` /
        ``recovered`` once the request has left the system, else None.
        ``recovered`` is a completed request that was replayed through a
        ``ServeSnapshot`` resume — loadgen's terminal accounting keys
        off this (every submitted request must reach exactly one)."""
        status = self.status
        if status is None and self.done_time is not None:
            status = "completed"  # pre-guard paths (batch baseline)
        if status == "completed" and self.recovered:
            return "recovered"
        return status


@dataclass
class _Slot:
    req: Request
    length: int  # committed KV rows (prompt + fed tokens)
    pages: list[int]
    last_tok: int
    admit_seq: int  # global admission counter — LIFO preemption order
    # The window page group (a model with window layers): the slot's
    # live window pages in sequence order, and the index of the first
    # (it holds positions from ``window_first * page_size`` on).
    window_pages: list[int] = field(default_factory=list)
    window_first: int = 0


@dataclass
class _Admission:
    """An admission whose programs are enqueued and whose first token is
    still on the device: what ``_complete_admit`` needs once the step's
    one fetch has brought the token. Until then the request is in no
    slot, no table row is written, and only the pools know its pages."""

    slot_idx: int
    req: Request
    kind: str  # the tracer's span vocabulary: prefill / recompute / ...
    t_admit: float
    bucket: int
    pages: list[int]
    first_tok: Any  # device scalar: the (last chunk's) sampled token
    window_pages: list[int]  # as in _Slot
    window_first: int


@dataclass
class ServeSnapshot:
    """Recoverable image of an engine's request state (not its KV).

    KV pages are deliberately NOT captured: the recompute-preemption
    path already rebuilds any slot's KV from prompt+generated, and the
    per-request PRNG streams (keyed by request id and ABSOLUTE output
    token index) make that rebuild output-invariant. So a snapshot is
    just the requests — in-flight ones recorded with the preemption
    transform pre-applied (produced tokens folded into the prompt) —
    plus the PRNG seed and the id counter. ``resume`` on a fresh engine
    replays every in-flight request token-for-token identically, greedy
    or sampled (tests/test_serve_recovery.py pins both).
    """

    seed: int
    next_id: int
    requests: list[dict[str, Any]] = field(default_factory=list)


class ServingEngine:
    """In-flight batching loop over ``cfg.num_slots`` decode slots.

    ``model`` is a decode-configured ``TransformerLM`` (``seq_axis``
    unsharded — e.g. ``LMTrainer.decode_model()`` or
    ``quantized_decode_model(kv_cache=True)``; tensor-parallel models
    pass ``mesh=``/``param_specs=`` as with ``make_generator``). The
    engine clones it with the page geometry; trained params drop in
    unchanged.

    Drive it with ``submit()`` + ``step()`` (one admission/decode
    iteration; returns requests completed in it) or ``run()`` (loop to
    drain). ``serve/loadgen.py`` adds wall-clock Poisson replay.
    """

    def __init__(
        self,
        model: Any,
        params: Any,
        cfg: ServeConfig,
        *,
        mesh: Any = None,
        param_specs: Any = None,
        sink: Any = None,
        clock: Callable[[], float] = time.monotonic,
        on_token: Callable[[Request, int], None] | None = None,
        tracer: Any = None,
        guard: Any = None,
    ) -> None:
        check_decode_model(model, "serving", allow_tensor=mesh is not None)
        if cfg.num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {cfg.num_slots}")
        if cfg.max_pages_per_slot < 1:
            raise ValueError(
                f"max_pages_per_slot must be >= 1, got {cfg.max_pages_per_slot}"
            )
        if cfg.paged_attention_impl not in ("auto", "gather", "kernel"):
            raise ValueError(
                "paged_attention_impl must be 'auto', 'gather' or "
                f"'kernel', got {cfg.paged_attention_impl!r}"
            )
        impl = cfg.paged_attention_impl
        if impl == "auto":
            from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
                default_interpret,
            )

            impl = "gather" if default_interpret() else "kernel"
        self.paged_attention_impl = impl
        self.cfg = cfg
        self.params = params
        self.mesh = mesh
        self.param_specs = param_specs
        if tracer is not None and getattr(
            tracer, "num_slots", cfg.num_slots
        ) != cfg.num_slots:
            raise ValueError(
                f"tracer was built for {tracer.num_slots} slots, engine "
                f"has {cfg.num_slots}"
            )
        self.sink = sink
        self.clock = clock
        self.on_token = on_token
        self.tracer = tracer
        # serve/guard.py::ServeGuard — admission control (shed/degrade at
        # submit) + deadline expiry (swept at the top of every step).
        # Optional and host-side only: with no guard, behavior is
        # byte-identical to the unguarded engine.
        self.guard = guard
        self.pool = PagePool(cfg.num_pages, cfg.page_size)
        # The window page group: a model with sliding-window layers
        # keeps their K and V in pools of their own, under a second page
        # table of ``window_table_width`` (P_w) pages a slot: what a
        # window and a chunk can touch, whatever the context. Its pool
        # holds num_slots * P_w + 1 pages, so it never runs dry and is
        # no ServeConfig field. A model without such layers has no
        # second pool, table or program argument.
        self.window_pool: PagePool | None = None
        self.window_table_width = 0
        self._window = None
        geometry: dict[str, Any] = {}
        if getattr(model, "layer_types", None) and model.window_layers():
            if not cfg.prefill_chunk:
                raise ValueError(
                    "a model with sliding-window layers is served by "
                    "chunks: the one-shot prefill keeps a dense cache of "
                    "every position, which a window layer neither needs "
                    "nor masks. Set ServeConfig.prefill_chunk"
                )
            if mesh is not None:
                raise ValueError(
                    "a model with sliding-window layers serves on one "
                    "device: the window page group is not sharded"
                )
            self._window = int(model.window)
            self.window_table_width = (
                -(-(self._window + cfg.prefill_chunk - 1) // cfg.page_size) + 1
            )
            self.window_pool = PagePool(
                cfg.num_slots * self.window_table_width + 1, cfg.page_size
            )
            geometry["window_num_pages"] = self.window_pool.num_pages
        if getattr(model, "latent", None) is not None and not cfg.prefill_chunk:
            raise ValueError(
                "a model with latent attention is served by chunks: the "
                "one-shot prefill keeps a dense cache of keys and values a "
                "head, which the latent pool exists to avoid. Set "
                "ServeConfig.prefill_chunk"
            )
        self.model = model.clone(
            page_size=cfg.page_size,
            num_pages=cfg.num_pages,
            paged_attention_impl=impl,
            **geometry,
        )
        self.max_seq_len = model.max_seq_len
        self._scanned = bool(getattr(model, "scan_layers", False))
        # A model with a state row a slot (lightning layers): the chunk
        # program is told its slot's row, the decode step which slots
        # advance (``_slot_kw``). A model without one has neither.
        self._slot_state = bool(getattr(model, "slot_state_layers", lambda: 0)())
        # a latent chunk attends by the Pallas walk over the slot's pages
        # (models/latent.py): ``chunk_attn_pairs`` counts what it scores
        self._chunk_walks = impl == "kernel" and (
            getattr(model, "latent", None) is not None
        )

        self._queue: deque[Request] = deque()
        self._slots: list[_Slot | None] = [None] * cfg.num_slots
        # The decode step's ONE host argument, allocated once; the tables
        # (0 = trash page) are views of it, so the bookkeeping that edits
        # a table row edits the next step's argument in place. The window
        # group's two are empty for a model without window layers.
        self._decode_arg = np.zeros((self._decode_arg_len(),), np.int32)
        (
            self._decode_head, self._page_table, self._window_table,
            self._window_first,
        ) = self._unpack_decode_arg(self._decode_arg)
        self._window_pages_freed = 0  # given back as the window passed
        self._next_id = 0
        self._admit_seq = 0
        self._step_count = 0
        self._active_slot_steps = 0
        self._preemptions = 0
        self._recovered = 0  # requests resumed from a ServeSnapshot
        # graftserve bookkeeping (obs/serve_trace.py + obs/flight.py):
        # the tail of every emitted serve record (crash-dump payload),
        # host wall per decode step (decode_host_exposed_ms), trash-page
        # rows written by the fixed-shape programs, and an optional
        # decode-step straggler window (make_flight_recorder).
        self._event_ring: deque[dict[str, Any]] = deque(maxlen=256)
        self._decode_walls: deque[float] = deque(maxlen=4096)
        self._trash_rows = 0
        self._straggler: Any = None
        self._completed: list[Request] = []
        self._timed_out = 0  # requests retired at deadline expiry
        self._shed = 0  # requests rejected at admission control
        # Phase counters at the span boundaries of step() (the table in
        # docs/observability.md): kept whether or not a profiler runs,
        # so stats() gives admissions per step without a trace.
        self._admissions = 0
        self._admit_steps = 0  # steps that admitted at least one request
        self._admit_fetches = 0  # blocking first-token fetches made
        self._max_admits_in_step = 0
        self._pages_grown = 0  # pages the grow loop allocated
        self._prefill_chunks = 0  # chunk programs run (prefill_chunk set)
        # causal (query, key) pairs a layer the latent chunk walk scored
        self._chunk_attn_pairs = 0
        # pages the one-shot commits wrote, trash pages included
        self._commit_pages = 0
        self._decode_puts = 0  # host-to-device puts made in decode_prep
        self._pool_audits = 0  # whole-pool audits run (``_audit_pools``)
        # What the decode steps' models sowed (``_step_counters``), summed
        # over steps and layers: sparse attention's kept and scored
        # tokens, experts that received a token, and the running sum of
        # the steps' expert-load ratio.
        self._counter_names: tuple[str, ...] = ()  # set when the step traces
        self._counts = dict.fromkeys(
            _ROUTING_COUNTERS + _WINDOW_COUNTERS + _LATENT_COUNTERS
            + _SHARE_COUNTERS + _GROUP_COUNTERS + _SLOT_STATE_COUNTERS
            + _BLOCK_SPARSE_COUNTERS, 0,
        )
        if cfg.prefill_chunk is not None and cfg.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 or None, got {cfg.prefill_chunk}"
            )
        self._chunk_program: Any = None
        self._base_key = jax.random.key(cfg.seed)
        # One PRNG stream PER REQUEST, indexed by absolute output-token
        # position: token t of request r always samples from
        # fold_in(fold_in(root, r), t), whether it is produced by a
        # prefill (t = tokens already produced before this admission) or
        # a decode step. Recompute-preemption therefore REPLAYS a
        # sampled victim's original tokens exactly — preemption is
        # output-invariant for every temperature, not just greedy.
        self._sample_root = jax.random.fold_in(self._base_key, 1)
        self._prefill_cache: dict[int, Any] = {}  # bucket len -> jitted fn

        self._pages = self._init_pages()
        self._decode_step = self._build_decode_step()
        # Set by the steps of ``keep_logits``' program alone.
        self.last_logits: Any = None
        self.last_logit_rows: dict[int, int] = {}

    # ---------------------------------------------------------- build

    def _init_pages(self):
        """Materialize the per-layer page pools ("pages" collection) via
        ``eval_shape`` of the model's own variable init — shapes/dtypes
        come from the model, zero params are ever materialized. Scale
        pools init to ones (matching the in-model variable init); data
        pools to zeros."""

        def materialize(path, s):
            if "scale" in path[-1].key:
                return jnp.ones(s.shape, s.dtype)
            return jnp.zeros(s.shape, s.dtype)

        shapes = self._pages_shape_tree()
        if not self.cfg.prefill_chunk:
            # the one-shot commit fills a pool from the dense cache's
            # rows of the same name; any other pool only chunks fill
            names = {
                path[-1].key
                for path, _ in jax.tree_util.tree_leaves_with_path(shapes)
            }
            if not names <= set(_CACHE_TO_PAGES.values()):
                raise ValueError(
                    "a model that keeps "
                    f"{sorted(names - set(_CACHE_TO_PAGES.values()))} "
                    "beside its pools is served by chunks: the one-shot "
                    "prefill fills only what a dense cache holds. Set "
                    "ServeConfig.prefill_chunk"
                )
        pages = jax.tree_util.tree_map_with_path(materialize, shapes)
        if self.mesh is not None:
            from jax.sharding import NamedSharding

            specs = self._page_specs()
            pages = jax.tree.map(
                lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
                pages,
                specs,
            )
        return pages

    def _page_specs(self):
        """PartitionSpecs for the pools: KV heads shard over the tensor
        axis, everything else replicated — the paged mirror of the
        tensor-sharded dense cache in ``tp_decode_model``. Heads are the
        last dimension of the data pools (``[num_pages, page_size,
        Hkv*D]``, head ``h`` in lanes ``[h*D, (h+1)*D)``, so a shard is
        a contiguous slice of whole heads) and of the scale pools
        (``[num_pages, page_size, Hkv]``) alike: one spec serves both.
        Folded, because the TPU's default layout of a 4-D ``[.., Hkv,
        D]`` pool puts ``num_pages`` minor-most and every program then
        converts the pool to row-major and back (``serve/layout.py``;
        ``tests/test_serve_layout.py`` checks it with the compile-only
        topology)."""
        from jax.sharding import PartitionSpec as P

        # scan_layers stacks every pool with a leading [num_layers] axis
        # (replicated), shifting the head dim right by one.
        lead = (None,) if self._scanned else ()
        spec = P(*lead, None, None, self.model.tensor_axis)
        return jax.tree.map(lambda _: spec, self._pages_shape_tree())

    def _pages_shape_tree(self):
        cfg = self.cfg
        b, p = cfg.num_slots, cfg.max_pages_per_slot
        # A mesh-free clone yields GLOBAL kv-head shapes; the TP path
        # shards the pools over the tensor axis (_page_specs).
        shape_model = self.model.clone(tensor_axis=None, tensor_axis_size=1)

        def init_fn():
            return shape_model.init(
                jax.random.key(0),
                jnp.zeros((b, 1), jnp.int32),
                mode="paged_decode",
                decode_pos=jnp.zeros((b,), jnp.int32),
                page_table=jnp.zeros((b, p), jnp.int32),
                **self._window_kw(
                    jnp.zeros((b, self.window_table_width), jnp.int32),
                    jnp.zeros((b,), jnp.int32),
                ),
            )["pages"]

        return jax.eval_shape(init_fn)

    def _slot_kw(self, **kw: Any) -> dict[str, Any]:
        """The model's keywords for its state rows (a chunk's
        ``slot_rows``, a decode step's ``slot_live``); none for a model
        without them."""
        return kw if self._slot_state else {}

    def _chunk_scalars(self) -> int:
        """Scalars of the chunk program's packed argument: offset, last
        index, (the slot, with state rows), and the sampling stream's
        two."""
        return 5 if self._slot_state else 4

    def _window_kw(self, *window: Any) -> dict[str, Any]:
        """The model's keywords for the window group's table and its
        first row's positions; none for a model without window layers
        (``window`` is then empty or not looked at)."""
        if self.window_pool is None:
            return {}
        table, first = window
        return {"window_page_table": table, "window_first_pos": first}

    def _jit_pages_program(self, fn, n_replicated: int, n_out: int = 1):
        """``jax.jit`` of one of the engine's programs, ``fn(params,
        pages, *replicated) -> (pages, tokens, ...)`` with ``n_out``
        replicated outputs behind the pools, the pools donated (XLA
        aliases them in place; no program allocates a pool); under a mesh
        wrapped in ``shard_map`` with the params' and the pools' specs."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=(1,))
        from jax.sharding import PartitionSpec as P

        page_specs = self._page_specs()
        return jax.jit(
            jax.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=(self.param_specs, page_specs)
                + (P(),) * n_replicated,
                out_specs=(page_specs,) + (P(),) * n_out,
                check_vma=False,
            ),
            donate_argnums=(1,),
        )

    def keep_logits(self) -> None:
        """Decode from here on by a second compiled step that also
        returns the float32 logits it sampled from, kept on the device as
        ``last_logits`` [num_slots, vocab], with ``last_logit_rows``
        ({request id: row}) of the step that made them: what the served
        cache gives, for checking it against a reference. The logits are
        not fetched; the step is otherwise the one it replaces."""
        self._decode_step = self._build_decode_step(keep_logits=True)

    def _build_decode_step(self, keep_logits: bool = False):
        """ONE jitted fixed-shape step for the engine's lifetime: what
        varies from step to step arrives in one traced vector of static
        shape (``_unpack_decode_arg``), so slot churn (retire / refill /
        preempt — different page tables, lengths, actives, request ids,
        token indices) re-runs the SAME executable. Pages are donated:
        XLA aliases the pool buffers in place, the step allocates no new
        pool. With ``keep_logits`` it returns the logits too."""
        cfg = self.cfg
        model = self.model

        def step(params, pages, packed, key):
            head, page_table, *window = self._unpack_decode_arg(packed)
            tokens, lengths, active, req_ids, tok_idx = head
            active = active != 0
            logits, mutated = model.apply(
                {"params": params, "pages": pages},
                tokens[:, None],
                mode="paged_decode",
                decode_pos=lengths,
                page_table=page_table,
                mutable=["pages", "serve_stats"],
                **self._window_kw(*window),
                **self._slot_kw(slot_live=active),
            )
            # Per-slot sampling keys from the (request, token-index)
            # stream — see _sample_root. ``key`` is the constant stream
            # root; it stays an argument so the executable is key-free.
            keys = jax.vmap(
                lambda r, t: jax.random.fold_in(jax.random.fold_in(key, r), t)
            )(req_ids, tok_idx)
            tok = jax.vmap(
                lambda row, k: sample_tokens(
                    row[None],
                    k,
                    temperature=cfg.temperature,
                    top_k=cfg.top_k,
                    top_p=cfg.top_p,
                )[0]
            )(logits[:, 0].astype(jnp.float32), keys)
            tok = jnp.where(active, tok, cfg.pad_id).astype(jnp.int32)
            # Device-side counters ride behind the tokens, in the one
            # array the host fetches a step; a model that sows none
            # compiles the step it always did.
            held = getattr(model, "moe_held_experts", None)
            counters, names = _step_counters(
                mutated.get("serve_stats", {}), active,
                getattr(model, "num_experts", 0) if held is None else len(held),
            )
            # which counters this model's step carries is known once it
            # is traced, before any step's result is read
            self._counter_names = names
            if counters is not None:
                tok = jnp.concatenate([tok, counters])
            if keep_logits:
                return mutated["pages"], tok, logits[:, 0].astype(jnp.float32)
            return mutated["pages"], tok

        return self._jit_pages_program(step, 2, 2 if keep_logits else 1)

    # The decode step takes ONE int32 vector beside the pools and the
    # constant stream root, as the prefill programs do, so a step is one
    # put:
    #   [tokens | lengths | active (0/1) | req_ids | tok_idx |
    #    page table | window table | window first pos]
    # (five rows of ``num_slots``, the head; then the tables row-major,
    # the last two of no width without window layers). ``req_ids`` and
    # ``tok_idx`` are the sampling stream's (see _sample_root).

    def _decode_arg_len(self) -> int:
        b, w = self.cfg.num_slots, self.window_table_width
        return b * (5 + self.cfg.max_pages_per_slot + (w + 1 if w else 0))

    def _unpack_decode_arg(self, packed):
        """(head [5, B], page table [B, P], the window group's table
        [B, P_w] and first positions [B] or [0]) of the decode step's
        argument, by slices and reshapes alone: views of a host buffer,
        static slices of a traced vector."""
        b, p = self.cfg.num_slots, self.cfg.max_pages_per_slot
        w = self.window_table_width
        table_end = 5 * b + b * p
        window_end = table_end + b * w
        return (
            packed[: 5 * b].reshape(5, b),
            packed[5 * b: table_end].reshape(b, p),
            packed[table_end:window_end].reshape(b, w),
            packed[window_end:],
        )

    def _probe_decode_arg(self) -> np.ndarray:
        """A decode argument for tools that lower or time the program off
        the serving path: every slot active at length 1, request ids
        0..B-1, every table row on the trash page."""
        packed = np.zeros_like(self._decode_arg)
        head = self._unpack_decode_arg(packed)[0]
        head[1:3] = 1
        head[3] = np.arange(self.cfg.num_slots)
        return packed

    def _prefill_fn(self, bucket: int):
        """Jitted prefill+commit for one prompt-length bucket: dense
        causal pass over the padded prompt, sample the first token from
        the true last position, scatter the prompt's KV into the slot's
        pages a whole page an index (``commit``). One trace per bucket
        (buckets are powers of two — a bounded set); prompt, true
        length, page row and the sampling stream's two integers arrive
        in one traced vector (``_pack_program_arg``), so every prompt in
        the bucket reuses the executable."""
        cached = self._prefill_cache.get(bucket)
        if cached is not None:
            return cached
        cfg = self.cfg
        model = self.model
        page_size = cfg.page_size
        scanned = self._scanned

        # The padded bucket in whole pages: a prompt shorter than a page
        # still writes one.
        n_pages = -(-bucket // page_size)

        def commit(pages, cache, page_row, true_len):
            # One index per PAGE, not per row (XLA's scatter pays per
            # index): the prompt's pages go to the slot's, every page
            # past it to the trash page 0. Rows at or past the true
            # length take the pool's fresh value, so a page holds what a
            # row-wise commit would have left on a fresh pool.
            j = jnp.arange(n_pages)
            pidx = jnp.where(j * page_size < true_len, page_row[j], 0)
            live = jnp.arange(n_pages * page_size)[:, None] < true_len

            def put(pname, p, c):
                # The cache's rows are [bucket, Hkv, D] (scales
                # [bucket, Hkv]); the pools fold the heads into the last
                # dimension, so each row reshapes to the pool's own.
                fresh = 1 if "scale" in pname else 0
                lanes = p.shape[-1]
                if scanned:
                    # scan_layers stacks both collections with a leading
                    # [num_layers] axis (one "blocks" subtree). Layers
                    # and pages merge into one axis (free: the tiled
                    # dimensions are the last two), so one scatter
                    # commits every layer at layer * num_pages + page.
                    layers, num_pages = p.shape[:2]
                    flat = p.reshape(layers * num_pages, *p.shape[2:])
                    lidx = jnp.arange(layers)[:, None] * num_pages + pidx
                    rows = c[:, 0, :bucket].reshape(layers, -1, lanes)
                else:
                    flat, lidx = p, pidx
                    rows = c[0, :bucket].reshape(-1, lanes)
                pad = n_pages * page_size - rows.shape[-2]
                if pad:
                    rows = jnp.pad(
                        rows, ((0, 0),) * (rows.ndim - 2) + ((0, pad), (0, 0))
                    )
                rows = jnp.where(live, rows, jnp.asarray(fresh, rows.dtype))
                rows = rows.reshape(*lidx.shape, page_size, lanes)
                return flat.at[lidx].set(rows).reshape(p.shape)

            def walk(p, c):
                if any(k in p for k in _CACHE_TO_PAGES.values()):
                    return {
                        pname: put(pname, p[pname], c[cname])
                        for cname, pname in _CACHE_TO_PAGES.items()
                        if pname in p
                    }
                return {k: walk(p[k], c[k]) for k in p}

            return walk(pages, cache)

        def prefill(params, pages, packed, key):
            prompt, (true_len,), page_row, key, _ = self._unpack_program_arg(
                packed, key, bucket, 3
            )
            logits, mutated = model.apply(
                {"params": params}, prompt, mode="prefill", mutable=["cache"]
            )
            last = lax.dynamic_slice_in_dim(logits, true_len - 1, 1, axis=1)
            tok = sample_tokens(
                last[:, 0].astype(jnp.float32),
                key,
                temperature=cfg.temperature,
                top_k=cfg.top_k,
                top_p=cfg.top_p,
            )
            pages = commit(pages, mutated["cache"], page_row, true_len)
            return pages, tok[0].astype(jnp.int32)

        fn = self._jit_pages_program(prefill, 2)
        self._prefill_cache[bucket] = fn
        return fn

    def _chunk_fn(self):
        """The one jitted prefill program of a chunked engine: ``chunk``
        tokens of one slot at ``offset``, written into the slot's pages
        and attended over pages plus chunk (mode="paged_prefill"), and
        the token sampled at ``last_idx`` of the chunk. Tokens, offset,
        index and page rows arrive in one traced vector
        (``_pack_program_arg``), so every chunk of every prompt runs the
        same executable."""
        if self._chunk_program is not None:
            return self._chunk_program
        cfg = self.cfg
        model = self.model

        def prefill_chunk(params, pages, packed, key):
            tokens, (offset, last_idx, *slot), page_row, key, window = (
                self._unpack_program_arg(
                    packed, key, cfg.prefill_chunk, self._chunk_scalars()
                )
            )
            logits, mutated = model.apply(
                {"params": params, "pages": pages},
                tokens,
                mode="paged_prefill",
                decode_pos=offset[None],
                page_table=page_row[None],
                logits_at=last_idx[None],
                mutable=["pages"],
                **self._window_kw(*(w[None] for w in window)),
                **self._slot_kw(slot_rows=slot[0][None] if slot else None),
            )
            tok = sample_tokens(
                logits[:, 0].astype(jnp.float32),
                key,
                temperature=cfg.temperature,
                top_k=cfg.top_k,
                top_p=cfg.top_p,
            )
            return mutated["pages"], tok[0].astype(jnp.int32)

        self._chunk_program = self._jit_pages_program(prefill_chunk, 2)
        return self._chunk_program

    # The prefill programs take ONE int32 vector beside the pools and
    # the constant stream root, so an admission (a chunk) is one put:
    #   [tokens, padded to ``width`` | scalars | page row |
    #    window row | window first pos]
    # (the last two of a model with window layers only). The bucket
    # program's scalars are (true_len, req_id, token index), the chunk
    # program's (offset, last_idx, req_id, token index), with the slot
    # after last_idx for a model with state rows: the last two are the
    # sampling stream's, and the programs fold the key from them as the
    # decode step does.

    def _program_arg_len(self, width: int, n_scalars: int) -> int:
        w = self.window_table_width
        return (
            width + n_scalars + self.cfg.max_pages_per_slot
            + (w + 1 if w else 0)
        )

    def _pack_program_arg(
        self, width: int, tokens: np.ndarray, scalars: tuple[int, ...],
        pages: Any, window_pages: Any = (), window_first: int = 0,
    ) -> np.ndarray:
        packed = np.zeros(
            (self._program_arg_len(width, len(scalars)),), np.int32
        )
        packed[: tokens.size] = tokens
        row = width + len(scalars)
        packed[width:row] = scalars
        packed[row: row + len(pages)] = pages
        if self.window_pool is not None:
            w0 = row + self.cfg.max_pages_per_slot
            packed[w0: w0 + len(window_pages)] = window_pages
            packed[-1] = window_first * self.cfg.page_size
        return packed

    def _unpack_program_arg(self, packed, key, width: int, n_scalars: int):
        """Inside a program: (tokens [1, width], the scalars before the
        stream's two, page row, this token's key, the window group's
        (row, first position) or ())."""
        row = width + n_scalars
        row_end = row + self.cfg.max_pages_per_slot
        key = jax.random.fold_in(
            jax.random.fold_in(key, packed[row - 2]), packed[row - 1]
        )
        window = ()
        if self.window_pool is not None:
            window = (packed[row_end:-1], packed[-1])
        return (
            packed[None, :width], packed[width: row - 2],
            packed[row:row_end], key, window,
        )

    @staticmethod
    def _bucket_for(n: int) -> int:
        b = 8
        while b < n:
            b *= 2
        return b

    # ------------------------------------------------------ admission

    def submit(self, req: Request) -> Request:
        """Queue a request. Raises if it can NEVER fit (admission-time
        capacity check — this is what makes preemption deadlock-free:
        any admitted request fits the pool alone, so the oldest active
        request always completes)."""
        req.prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if req.prompt.size < 1:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {req.max_new_tokens}"
            )
        # Ids assign BEFORE admission control, so a guarded run's
        # req_ids line up with an unguarded oracle run of the same
        # workload regardless of which requests shed — and the shed
        # events themselves carry a real id.
        if req.req_id < 0:
            req.req_id = self._next_id
            self._next_id += 1
        # Admission control (serve/guard.py): may terminally REJECT the
        # request (bounded queue; returned unqueued with
        # status="rejected" and a serve_shed event already emitted) or
        # DEGRADE it (trim max_new_tokens under pool pressure — before
        # orig_max_new_tokens is recorded, so the trimmed budget IS the
        # request's budget and its output stays an oracle prefix).
        if self.guard is not None and not self.guard.admit(self, req):
            return req
        if req.orig_prompt_len < 0:
            req.orig_prompt_len = int(req.prompt.size)
            req.orig_max_new_tokens = int(req.max_new_tokens)
        total = int(req.prompt.size) + int(req.max_new_tokens)
        if total > self.max_seq_len:
            raise ValueError(
                f"prompt ({req.prompt.size}) + max_new_tokens "
                f"({req.max_new_tokens}) exceeds max_seq_len "
                f"({self.max_seq_len})"
            )
        # KV rows a request can occupy: prompt + budget - 1 (the final
        # sampled token is never fed back, so its KV is never written).
        need = self.pool.pages_for(total - 1)
        cap = min(self.cfg.max_pages_per_slot, self.cfg.num_pages - 1)
        if need > cap:
            raise ValueError(
                f"request needs {need} pages ({total - 1} KV rows at "
                f"page_size {self.cfg.page_size}); the engine caps a slot "
                f"at {cap} pages — raise max_pages_per_slot/num_pages or "
                "shrink the request"
            )
        req.submit_time = self.clock()
        if req.arrival_time is None:
            req.arrival_time = req.submit_time
        self._queue.append(req)
        if self.tracer is not None:
            self.tracer.on_submit(req, req.submit_time)
        return req

    @property
    def busy(self) -> bool:
        return bool(self._queue) or any(s is not None for s in self._slots)

    # ------------------------------------------------------- telemetry

    def _emit(self, record: dict[str, Any]) -> None:
        """Route one record to the sink AND the in-memory event ring —
        the ring is what the flight recorder dumps on a crash, so it
        keeps the tail even when the sink is detached (warmup)."""
        self._event_ring.append(record)
        if self.sink is not None:
            self.sink.emit(record)

    def _pool_stats(self) -> dict[str, int]:
        """Pool counters at decode-step cadence for the tracer's
        utilization time series and SLO windows."""
        pool = self.pool
        return {
            "live": pool.allocated_pages,
            "free": pool.free_pages,
            "high_water": pool.high_water,
            "churn": pool.total_allocs + pool.total_frees,
            "trash": self._trash_rows,
        }

    def finalize_trace(self) -> None:
        """Flush the tracer's final partial SLO window through the sink
        (``run_poisson`` calls this once the engine drains)."""
        if self.tracer is None:
            return
        rec = self.tracer.flush_window(
            self.clock(), queue_depth=len(self._queue)
        )
        if rec is not None:
            self._emit(rec)

    def make_flight_recorder(
        self,
        telemetry: Any = None,
        *,
        emit: Callable[..., None] | None = None,
        ring_tail: int = 32,
        hbm: bool = True,
    ) -> Any:
        """A FlightRecorder over the serving loop — the serve analog of
        what ``LMTrainer.fit`` wires for training: a crash/watchdog/
        SIGTERM dump carries the pool + queue high-water header, the
        decode-step straggler window, and the tail of the serve event
        ring (preempt/request/recovered/serve_window records). With no
        telemetry/emit given, dump events flow through the engine's own
        sink."""
        from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
            FlightRecorder,
            HbmHighWater,
            StragglerMonitor,
        )

        if self._straggler is None:
            self._straggler = StragglerMonitor()
        if telemetry is None and emit is None:
            def emit(event, **fields):
                self._emit({
                    "kind": "event", "event": event, "time": time.time(),
                    **fields,
                })

        def serve_tail():
            # Re-key the ring records so they nest under flight_serve
            # events without colliding with Telemetry's kind/event/time.
            out = []
            for rec in list(self._event_ring)[-ring_tail:]:
                row = {}
                for k, v in rec.items():
                    if k == "kind":
                        continue
                    row["serve_event" if k == "event" else
                        "t" if k == "time" else k] = v
                out.append(row)
            return out

        def header():
            pool = self.pool
            return {
                "queue_depth": len(self._queue),
                "active_slots": sum(s is not None for s in self._slots),
                "decode_steps": self._step_count,
                "preemptions": self._preemptions,
                "pages_live": pool.allocated_pages,
                "page_high_water": pool.high_water,
                "page_churn": pool.total_allocs + pool.total_frees,
                "trash_rows_written": self._trash_rows,
            }

        return FlightRecorder(
            telemetry=telemetry,
            straggler=self._straggler,
            hbm=HbmHighWater() if hbm else None,
            ring_tail=ring_tail,
            emit=emit,
            tails={"serve": serve_tail},
            header_fn=header,
        )

    # ------------------------------------------------------ scheduling

    def _preempt_lifo(self) -> bool:
        """Free the most recently admitted active slot: its pages return
        to the pool NOW and the request re-queues (front) with
        prompt+generated as the new prompt — recompute-style preemption.
        Returns False when nothing is active to preempt."""
        victim_idx = -1
        for i, s in enumerate(self._slots):
            if s is not None and (
                victim_idx < 0
                or s.admit_seq > self._slots[victim_idx].admit_seq
            ):
                victim_idx = i
        if victim_idx < 0:
            return False
        slot = self._slots[victim_idx]
        req = slot.req
        req.preemptions += 1
        self._preemptions += 1
        replayed = len(req.generated)
        now = self.clock()
        if self.tracer is not None:
            self.tracer.on_preempt(req, victim_idx, now, replayed)
        self._emit({
            "kind": "serve",
            "event": "preempt",
            "time": time.time(),
            "id": req.req_id,
            "replayed_tokens": replayed,
        })
        # prompt + everything generated so far (minus nothing: the last
        # sampled token re-enters as prompt tail and its KV recomputes)
        req.prompt = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)]
        )
        req.max_new_tokens -= len(req.generated)
        req.generated = []
        self._free_slot(victim_idx)
        if req.max_new_tokens >= 1:
            self._queue.appendleft(req)
            if self.tracer is not None:
                self.tracer.on_requeue(req, now)
        else:  # budget spent exactly at preemption — it is just done
            self._finish(req)
        return True

    def _free_slot(self, i: int) -> None:
        slot = self._slots[i]
        self.pool.free(slot.pages)
        self._page_table[i, :] = 0
        if self.window_pool is not None:
            self.window_pool.free(slot.window_pages)
            self._window_table[i, :] = 0
            self._window_first[i] = 0
        self._slots[i] = None

    def _audit_pools(self) -> None:
        """The whole-pool audit (``PagePool.check_invariants``), which
        costs a walk of every page: run where the engine is off the
        clock, never on the path of a step. Every page-freeing path
        (retire, preempt, deadline expiry) funnels through
        ``_free_slot``, and ``PagePool.free`` checks there what a free
        can break, at the cost of the pages freed."""
        self._pool_audits += 1
        self.pool.check_invariants()
        if self.window_pool is not None:
            self.window_pool.check_invariants()

    def _window_advance(
        self, pages: list[int], first: int, oldest: int, newest: int
    ) -> tuple[int, int]:
        """Move one slot's window pages on: give back those that lie
        wholly behind position ``oldest`` (the first a coming query
        sees), lease those needed to hold position ``newest``. ``pages``
        is edited in place; returns (index of its first page, pages
        given back). The group's pool cannot run dry: a slot never holds
        more than ``window_table_width`` pages, and the pool has that
        many a slot."""
        size = self.cfg.page_size
        keep_from = max(oldest, 0) // size
        dropped = min(max(keep_from - first, 0), len(pages))
        if dropped:
            self.window_pool.free(pages[:dropped])
            del pages[:dropped]
        if not pages:
            first = keep_from
        else:
            first += dropped
        wanted = newest // size + 1 - (first + len(pages))
        if wanted > 0:
            pages.extend(self.window_pool.alloc(wanted))
        assert len(pages) <= self.window_table_width, (len(pages), oldest, newest)
        return first, dropped

    def _can_admit(self, prompt_len: int) -> bool:
        """Pages for the prompt are free, in each group that exists."""
        ok = self.pool.can_alloc(max(1, self.pool.pages_for(prompt_len)))
        if ok and self.window_pool is not None:
            ok = self.window_pool.can_alloc(self.window_table_width)
        return ok

    def _ensure_pages(self, n: int) -> bool:
        """Make n pages allocatable, preempting LIFO as needed."""
        while not self.pool.can_alloc(n):
            if not self._preempt_lifo():
                return False
        return True

    def _admit(self, slot_idx: int, req: Request) -> _Admission:
        """Lease the prompt's pages and ENQUEUE its prefill (every chunk
        of it): one packed put and one dispatch a program, no fetch, so
        the host prepares the next admission while the device runs this
        one. The first token stays on the device until the step's one
        fetch (``_step``); ``_complete_admit`` then makes the slot."""
        t_admit = self.clock()
        # Span vocabulary for this admission (obs/serve_trace.py): a
        # first admission is a plain prefill, a preempted request's
        # re-admission is a recompute, and a resumed in-flight request's
        # first re-admission is a resume-replay.
        if req.replay_pending:
            admit_kind = "resume-replay"
        elif req.preemptions > 0:
            admit_kind = "recompute"
        else:
            admit_kind = "prefill"
        req.replay_pending = False
        plen = int(req.prompt.size)
        chunk = self.cfg.prefill_chunk
        # the padded length a program sees: the chunk, or the bucket
        bucket = chunk if chunk else self._bucket_for(plen)
        # The profiler's spans (docs/observability.md) open at the stamps
        # the tracer's hooks get, so both describe the same intervals.
        with profiling.annotate(
            "serve/admit", step=self._step_count, req=req.req_id,
            bucket=bucket, kind=admit_kind, prompt_len=plen,
        ):
            with profiling.annotate("serve/admit_prep", req=req.req_id):
                need = max(1, self.pool.pages_for(plen))
                pages = self.pool.alloc(need)
                n_chunks = -(-plen // chunk) if chunk else 1
                # The (request, token-index) stream: a recompute-preempted
                # request's re-prefill samples token index
                # ``output_tokens`` (the first NOT-yet-produced one) from
                # the same key a decode step would have used, so replay
                # reproduces the original tokens at any temperature. The
                # programs fold it from the two integers themselves.
                tok_idx = req.output_tokens
                window_pages: list[int] = []
                window_first = 0
                row = np.asarray(pages, np.int32)
                # Rows of the padded prompt past its pages go to trash
                # (the padding first fills the last page's tail, which
                # decode overwrites); the one-shot commit writes whole
                # pages, the bucket's ``n_pages`` of them.
                size = self.cfg.page_size
                if chunk:
                    self._trash_rows += max(0, n_chunks * chunk - need * size)
                    prefill = self._chunk_fn()
                else:
                    n_pages = -(-bucket // size)
                    self._trash_rows += (n_pages - need) * size
                    self._commit_pages += n_pages
                    prefill = self._prefill_fn(bucket)
                    packed = self._pack_program_arg(
                        bucket, req.prompt, (plen, req.req_id, tok_idx), row
                    )
            with profiling.annotate(
                "serve/prefill", req=req.req_id, bucket=bucket
            ):
                if chunk:
                    # Every chunk inside this admission, one program each
                    # and no fetch; the last chunk's token is the
                    # request's first.
                    for ci in range(n_chunks):
                        off = ci * chunk
                        n = min(chunk, plen - off)
                        if self._chunk_walks:
                            # each real row sees the slot's rows before
                            # the chunk and the chunk's up to its own
                            self._chunk_attn_pairs += (
                                n * off + n * (n + 1) // 2
                            )
                        if self.window_pool is not None:
                            # The chunk's queries see back to
                            # off - window + 1: what lies behind goes
                            # back, what the chunk writes is leased. The
                            # device runs programs in order, so a page
                            # given back and leased again is written only
                            # after the chunks that read it.
                            with profiling.annotate(
                                "serve/window_free", req=req.req_id
                            ) as free_span:
                                window_first, freed = self._window_advance(
                                    window_pages, window_first,
                                    off - self._window + 1, off + n - 1,
                                )
                                self._window_pages_freed += freed
                                free_span.set_metadata(pages=freed)
                        with profiling.annotate(
                            "serve/prefill_chunk", req=req.req_id, chunk=ci,
                            offset=off, len=n,
                        ):
                            self._pages, first_tok = prefill(
                                self.params, self._pages,
                                self._pack_program_arg(
                                    chunk, req.prompt[off:off + n],
                                    (off, n - 1)
                                    + ((slot_idx,) if self._slot_state else ())
                                    + (req.req_id, tok_idx), row,
                                    window_pages, window_first,
                                ),
                                self._sample_root,
                            )
                    self._prefill_chunks += n_chunks
                else:
                    self._pages, first_tok = prefill(
                        self.params, self._pages, packed, self._sample_root
                    )
        return _Admission(
            slot_idx=slot_idx, req=req, kind=admit_kind, t_admit=t_admit,
            bucket=bucket, pages=pages, first_tok=first_tok,
            window_pages=window_pages, window_first=window_first,
        )

    def _complete_admit(self, adm: _Admission, tok: int) -> None:
        """The bookkeeping that needs an admission's first token, once
        the step's fetch has brought it: stamps, surfacing, the tracer's
        hooks, the slot and its table rows, retiring a request that is
        already done."""
        req, slot_idx = adm.req, adm.slot_idx
        plen = int(req.prompt.size)
        now = self.clock()
        first = req.first_token_time is None
        if first:
            req.first_token_time = now
        if self.tracer is not None:
            self.tracer.on_admit(
                req, slot=slot_idx, bucket=adm.bucket, t0=adm.t_admit,
                t1=now, kind=adm.kind,
                replayed=max(0, plen - req.orig_prompt_len),
            )
            if first:
                self.tracer.sample_ttft(
                    (now - req.arrival_time) * 1e3, now
                )
        req.generated.append(tok)
        self._surface(req, tok, now)
        self._admit_seq += 1
        self._slots[slot_idx] = _Slot(
            req=req, length=plen, pages=adm.pages, last_tok=tok,
            admit_seq=self._admit_seq, window_pages=adm.window_pages,
            window_first=adm.window_first,
        )
        self._page_table[slot_idx, :] = 0
        self._page_table[slot_idx, : len(adm.pages)] = adm.pages
        if self.window_pool is not None:
            self._set_window_row(
                slot_idx, adm.window_pages, adm.window_first
            )
        if self._slot_done(self._slots[slot_idx]):
            self._retire(slot_idx)

    def _slot_done(self, slot: _Slot) -> bool:
        if len(slot.req.generated) >= slot.req.max_new_tokens:
            return True
        return (
            self.cfg.eos_id is not None and slot.last_tok == self.cfg.eos_id
        )

    def _retire(self, i: int, status: str = "completed") -> None:
        req = self._slots[i].req
        self._free_slot(i)
        self._finish(req, slot=i, status=status)

    def _finish(
        self, req: Request, slot: int | None = None,
        status: str = "completed",
    ) -> None:
        req.status = status
        req.done_time = self.clock()
        if status == "timed_out":
            self._timed_out += 1
        self._completed.append(req)
        if self.tracer is not None:
            self.tracer.on_retire(req, slot, req.done_time)
        # A request that timed out while QUEUED never produced a token —
        # its latency fields are honestly absent, not zero.
        ttft_ms = None
        decode_ms = None
        out = req.output_tokens
        if req.first_token_time is not None:
            ttft_ms = round(
                (req.first_token_time - req.arrival_time) * 1e3, 3
            )
            decode_s = req.done_time - req.first_token_time
            decode_ms = round(decode_s * 1e3 / max(1, out - 1), 4)
        queue_ms = (req.submit_time - req.arrival_time) * 1e3
        self._emit({
            "kind": "serve",
            "event": "request",
            "time": time.time(),
            "id": req.req_id,
            "status": req.terminal_status,
            "prompt_tokens": req.orig_prompt_len,
            "output_tokens": out,
            "queue_ms": round(queue_ms, 3),
            "ttft_ms": ttft_ms,
            "decode_ms_per_token": decode_ms,
            "preemptions": req.preemptions,
            "recovered": req.recovered,
        })

    def _shed_reject(self, req: Request, reason: str, **fields: Any) -> None:
        """Terminally reject ``req`` at admission control: it never
        queues, never touches the pool, and resolves immediately with
        status ``rejected``. Called by the guard from inside
        ``submit()``."""
        now = self.clock()
        req.submit_time = now
        if req.arrival_time is None:
            req.arrival_time = now
        if req.orig_prompt_len < 0:
            req.orig_prompt_len = int(req.prompt.size)
            req.orig_max_new_tokens = int(req.max_new_tokens)
        req.status = "rejected"
        req.done_time = now
        self._shed += 1
        self._completed.append(req)
        if self.tracer is not None:
            self.tracer.on_shed(req, now, reason)
        self._emit({
            "kind": "serve_shed",
            "time": time.time(),
            "id": req.req_id,
            "reason": reason,
            "terminal": True,
            **fields,
        })

    def _expire_request(self, req: Request, slot: int | None,
                        reason: str) -> None:
        """Retire ``req`` with terminal status ``timed_out``: an active
        slot's pages free immediately (``PagePool.free`` checks each
        page of the reclamation), a queued request just resolves.
        ``reason`` is the budget that expired (``deadline`` or
        ``queue_wait``)."""
        self._emit({
            "kind": "serve",
            "event": "timed_out",
            "time": time.time(),
            "id": req.req_id,
            "reason": reason,
            "queued": slot is None,
        })
        if slot is not None:
            self._retire(slot, status="timed_out")
        else:
            self._finish(req, slot=None, status="timed_out")

    # ------------------------------------------------------------ loop

    def step(self) -> list[Request]:
        """One engine iteration: refill free slots from the queue
        (prefill+commit each, enqueued back to back; one fetch of their
        first tokens), grow page tables for slots crossing a page
        boundary (preempting LIFO if the pool is dry), then run ONE
        fixed-shape decode step over all slots and retire the finished.
        Returns the requests completed during this iteration.

        Each phase is a span on the profiler's timeline (``serve/step``
        and its children; docs/observability.md has the table). The
        spans partition the step's wall time; the device works through
        the step's prefills from the first ``serve/admit`` to the end of
        ``serve/admit_fetch``, every later phase is synchronous. With no
        capture running they record nothing."""
        with profiling.annotate(
            "serve/step", step=self._step_count, queued=len(self._queue),
            active=sum(s is not None for s in self._slots),
        ):
            return self._step()

    def _step(self) -> list[Request]:
        done_before = len(self._completed)
        step = self._step_count

        # Deadline sweep BEFORE refill: an expired queue head must not
        # be admitted, and an expired active slot's pages must be free
        # for this step's refill/grow to use. Host-side only — the
        # decode step below never sees a deadline, so the zero-retrace
        # contract is untouched.
        if self.guard is not None:
            with profiling.annotate("serve/expire", step=step):
                self.guard.expire(self)

        # refill — FCFS with head-of-line blocking: a new request only
        # admits when its prompt's pages are FREE. Never preempt to
        # admit (the queue head is by definition younger than every
        # active request — killing running work for it would invert
        # priority and can livelock with re-queued victims).
        # Every admission the step can take is ENQUEUED first (pages
        # leased, programs dispatched), and only then does the host wait:
        # one blocking fetch brings all their first tokens, and the
        # bookkeeping that needs a token runs in admission order. A slot
        # exists only from there on, so nothing outside this block ever
        # sees an admission half-made; pages a request frees by
        # finishing on its first token serve the NEXT step's admissions.
        admitted: list[_Admission] = []
        for i in range(self.cfg.num_slots):
            if not self._queue:
                break
            if self._slots[i] is not None:
                continue
            if not self._can_admit(int(self._queue[0].prompt.size)):
                break
            admitted.append(self._admit(i, self._queue.popleft()))
        if admitted:
            self._admissions += len(admitted)
            self._admit_steps += 1
            self._max_admits_in_step = max(
                self._max_admits_in_step, len(admitted)
            )
            with profiling.annotate(
                "serve/admit_fetch", step=step, admits=len(admitted)
            ):
                # the step's ONE wait for its admissions: the decode
                # step is fed these tokens from the host
                toks = jax.device_get([a.first_tok for a in admitted])
                self._admit_fetches += 1
                for adm, tok in zip(admitted, toks):
                    self._complete_admit(adm, int(tok))

        # grow: every active slot needs a page for the KV row its next
        # fed token writes (position slot.length)
        with profiling.annotate("serve/grow", step=step) as grow_span:
            grown = 0
            for i in range(self.cfg.num_slots):
                slot = self._slots[i]
                if slot is None or self._slot_done(slot):
                    continue
                page_idx = slot.length // self.cfg.page_size
                if page_idx < len(slot.pages):
                    continue
                if not self._ensure_pages(1):
                    raise RuntimeError("page pool dry with no active slots")
                slot = self._slots[i]  # _ensure_pages may have preempted i
                if slot is None or slot.length // self.cfg.page_size < len(
                    slot.pages
                ):
                    continue
                new_page = self.pool.alloc(1)[0]
                self._page_table[i, len(slot.pages)] = new_page
                slot.pages.append(new_page)
                grown += 1
            self._pages_grown += grown
            grow_span.set_metadata(pages=grown)

        if not any(s is not None for s in self._slots):
            return self._completed[done_before:]

        # decode one token for every active slot
        cfg = self.cfg
        t_d0 = self.clock()
        with profiling.annotate("serve/decode_prep", step=step) as prep_span:
            if self.window_pool is not None:
                self._window_step(step)
            # The five rows at the head of the packed argument; the
            # tables behind them are kept current as slots change.
            tokens, lengths, is_active, req_ids, tok_idx = self._decode_head
            self._decode_head[:] = 0
            tokens[:] = cfg.pad_id
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                tokens[i] = slot.last_tok
                lengths[i] = slot.length
                is_active[i] = 1
                req_ids[i] = slot.req.req_id
                # Absolute output-token index this step produces for the
                # request — the per-request PRNG stream position (see
                # _sample_root; replay-exact across preemptions).
                tok_idx[i] = slot.req.output_tokens
            active = is_active != 0
            n_active = int(active.sum())
            packed = jax.device_put(self._decode_arg)
            self._decode_puts += 1
            prep_span.set_metadata(puts=1)
        with profiling.annotate("serve/decode", step=step, active=n_active):
            out = self._decode_step(
                self.params, self._pages, packed, self._sample_root
            )
            self._pages, toks = out[0], out[1]
            if len(out) > 2:
                self.last_logits = out[2]
                self.last_logit_rows = {
                    s.req.req_id: i for i, s in enumerate(self._slots)
                    if s is not None
                }
            toks = np.asarray(toks)  # graftlint: disable=GL001 -- the scheduler NEEDS this sync: retire/refill decisions read the sampled tokens; one fetch per engine step, outside any jit
            toks, counters = toks[: cfg.num_slots], toks[cfg.num_slots:]
        with profiling.annotate("serve/retire", step=step) as retire_span:
            # NaN detection on the already-fetched tokens (zero extra
            # transfers): poisoned logits sample out-of-vocab. Raised
            # BEFORE any per-step bookkeeping mutates, so the host state a
            # post-crash snapshot() captures is exactly the pre-step world
            # — run_serve_with_recovery replays this step on a fresh
            # engine.
            bad = active & ((toks < 0) | (toks >= self.model.vocab_size))
            if bad.any():
                raise DecodeNanError(
                    step=self._step_count, slots=np.nonzero(bad)[0]
                )
            self._step_count += 1
            self._active_slot_steps += n_active
            # _step_counters, behind the tokens
            for name, value in zip(self._counter_names, counters.tolist()):
                self._counts[name] += value
            # Inactive slots still write one KV row per step — to the
            # trash page (fixed-shape contract).
            self._trash_rows += cfg.num_slots - n_active
            now = self.clock()
            self._decode_walls.append(now - t_d0)
            if self._straggler is not None:
                self._straggler.record(self._step_count, now - t_d0)
            window = None
            if self.tracer is not None:
                # Snapshot slot residency BEFORE retiring — the hook
                # extends each live slot's coalesced decode_run span to
                # ``now``, the same stamp the tokens below surface with.
                slot_reqs = {
                    i: s.req.req_id
                    for i, s in enumerate(self._slots)
                    if s is not None
                }
                window = self.tracer.on_decode_step(
                    t_d0, now, slot_reqs, self._pool_stats(),
                    len(self._queue),
                )
            done_at_fetch = len(self._completed)
            for i, (slot, tok) in enumerate(zip(self._slots, toks.tolist())):
                if slot is None:
                    continue
                slot.length += 1
                slot.last_tok = tok
                slot.req.generated.append(tok)
                self._surface(slot.req, slot.last_tok, now)
                if self._slot_done(slot):
                    self._retire(i)
            if window is not None:
                self._emit(window)
            retire_span.set_metadata(
                retired=len(self._completed) - done_at_fetch
            )
            return self._completed[done_before:]

    def _set_window_row(self, i: int, pages: list[int], first: int) -> None:
        """Slot ``i``'s row of the window group's table: its live window
        pages in sequence order, and the position of the first's row."""
        row = self._window_table[i]
        row[:] = 0
        row[: len(pages)] = pages
        self._window_first[i] = first * self.cfg.page_size

    def _window_step(self, step: int) -> None:
        """Before a decode step of a model with window layers: every
        active slot gives back the window pages its next query no longer
        sees and leases the page its next row needs. The group's table
        and first positions are views of the step's packed argument
        (``_set_window_row`` writes there): nothing is put here."""
        with profiling.annotate("serve/window_free", step=step) as span:
            freed = 0
            for i, slot in enumerate(self._slots):
                if slot is None:
                    continue
                held = len(slot.window_pages)
                slot.window_first, dropped = self._window_advance(
                    slot.window_pages, slot.window_first,
                    slot.length - self._window + 1, slot.length,
                )
                if dropped or len(slot.window_pages) != held:
                    self._set_window_row(
                        i, slot.window_pages, slot.window_first
                    )
                freed += dropped
            self._window_pages_freed += freed
            span.set_metadata(pages=freed)

    def run(self) -> list[Request]:
        """Drain: step until the queue and every slot are empty."""
        while self.busy:
            self.step()
        # drained, so off the clock and every page must be back
        self._audit_pools()
        return self._completed

    # ------------------------------------------------------- streaming

    def _surface(self, req: Request, tok: int, now: float) -> None:
        """Deliver one output token as it decodes (not at retire):
        stamp its wall-clock surface time and fire the ``on_token``
        callback. Called from prefill admission (the first token) and
        from every decode step."""
        if self.tracer is not None and req.token_times:
            # Feed the tracer's rolling ITL reservoir the same gap
            # loadgen's post-hoc np.diff will compute — EXCEPT across a
            # resume boundary, where the gap spans the kill (and two
            # clock epochs); loadgen excludes those too, so windowed
            # and post-hoc percentiles agree.
            if len(req.token_times) not in req.resume_boundaries:
                self.tracer.sample_itl(
                    (now - req.token_times[-1]) * 1e3, now
                )
        req.token_times.append(now)
        if self.on_token is not None:
            self.on_token(req, tok)

    def iter_tokens(self, req: Request):
        """Stream a submitted request's output tokens, driving the
        engine as needed: yields each token id as it surfaces and
        returns when the request completes. Other in-flight requests
        keep decoding in the same fixed-shape steps — streaming one
        request costs the batch nothing.

        Recompute-preemption moves produced tokens into the prompt, so
        the surfaced stream is reconstructed as
        ``prompt[orig_prompt_len:] + generated`` — already-yielded
        tokens never re-surface.
        """
        yielded = 0
        while True:
            produced = req.output_tokens
            if produced > yielded:
                ids = list(req.prompt[req.orig_prompt_len:]) + list(
                    req.generated
                )
                for tok in ids[yielded:produced]:
                    yield int(tok)
                yielded = produced
            if req.done_time is not None or not self.busy:
                return
            self.step()

    # ------------------------------------------------------- recovery

    def snapshot(self) -> ServeSnapshot:
        """Capture every unfinished request — killable-engine discipline
        (utils/memstore.py for training; docs/reliability.md).

        In-flight slots are recorded with the recompute-preemption
        transform applied to COPIES (prompt <- prompt+generated, budget
        reduced, generated cleared), ordered oldest-admission-first so a
        resume re-admits in the original priority order; queued requests
        follow verbatim. The live engine is not mutated — serving
        continues untouched after a snapshot."""

        def record(req: Request, *, in_flight: bool, replayed: int) -> dict:
            prompt = np.asarray(req.prompt, np.int32).copy()
            max_new = int(req.max_new_tokens)
            if replayed:
                prompt = np.concatenate(
                    [prompt, np.asarray(req.generated, np.int32)]
                )
                max_new -= replayed
            return {
                "req_id": int(req.req_id),
                "prompt": prompt,
                "max_new_tokens": max_new,
                "deadline_s": req.deadline_s,
                "max_queue_s": req.max_queue_s,
                "orig_prompt_len": int(req.orig_prompt_len),
                "orig_max_new_tokens": int(req.orig_max_new_tokens),
                "preemptions": int(req.preemptions),
                "arrival_time": req.arrival_time,
                "first_token_time": req.first_token_time,
                "token_times": list(req.token_times),
                "resume_boundaries": list(req.resume_boundaries),
                "replayed_tokens": replayed,
                "in_flight": in_flight,
            }

        # off the clock, and what is captured must rest on a whole pool
        self._audit_pools()
        active = sorted(
            (s for s in self._slots if s is not None),
            key=lambda s: s.admit_seq,
        )
        requests = [
            record(s.req, in_flight=True, replayed=len(s.req.generated))
            for s in active
        ]
        requests += [
            record(r, in_flight=False, replayed=0) for r in self._queue
        ]
        return ServeSnapshot(
            seed=self.cfg.seed, next_id=self._next_id, requests=requests
        )

    def resume(self, snap: ServeSnapshot) -> list[Request]:
        """Re-submit a snapshot's requests into this (idle) engine.

        The engine must share the snapshot's PRNG seed — the per-request
        sample streams are keyed off it, and replay is only
        token-identical on the same streams. Every in-flight request is
        replayed through the normal recompute path: its re-prefill
        samples output-token index ``output_tokens`` from the same
        (req_id, index) key the dead engine's decode would have used, so
        the resumed stream continues exactly where the kill landed.
        Returns the reconstructed Requests in submission order."""
        if self.busy:
            raise RuntimeError(
                "resume requires an idle engine: live requests would "
                "interleave with the snapshot's admission order"
            )
        if snap.seed != self.cfg.seed:
            raise ValueError(
                f"snapshot was taken under seed {snap.seed}, engine has "
                f"{self.cfg.seed}: per-request PRNG streams differ, "
                "replay would not be token-identical"
            )
        out = []
        for rec in snap.requests:
            req = Request(
                prompt=np.asarray(rec["prompt"], np.int32),
                max_new_tokens=int(rec["max_new_tokens"]),
                req_id=int(rec["req_id"]),
                arrival_time=rec["arrival_time"],
            )
            req.deadline_s = rec.get("deadline_s")
            req.max_queue_s = rec.get("max_queue_s")
            req.orig_prompt_len = int(rec["orig_prompt_len"])
            req.orig_max_new_tokens = int(rec["orig_max_new_tokens"])
            req.preemptions = int(rec["preemptions"])
            req.first_token_time = rec["first_token_time"]
            req.token_times = list(rec["token_times"])
            req.resume_boundaries = list(rec.get("resume_boundaries", []))
            if req.token_times:
                # The next surfaced token lands at this index — the gap
                # it opens spans the kill (and two clock epochs), so ITL
                # percentiles must skip it (loadgen._summarize).
                req.resume_boundaries.append(len(req.token_times))
            req.recovered = True
            req.replay_pending = bool(rec["in_flight"])
            self.submit(req)
            if rec["in_flight"]:
                self._recovered += 1
                self._emit({
                    "kind": "serve",
                    "event": "recovered",
                    "time": time.time(),
                    "id": req.req_id,
                    "replayed_tokens": int(rec["replayed_tokens"]),
                })
            out.append(req)
        self._next_id = max(self._next_id, int(snap.next_id))
        return out

    # ------------------------------------------------------- reporting

    def stats(self) -> dict[str, Any]:
        steps = max(1, self._step_count)
        return {
            "requests_done": len(self._completed),
            "decode_steps": self._step_count,
            "slot_occupancy": self._active_slot_steps
            / (steps * self.cfg.num_slots),
            "page_high_water": self.pool.high_water,
            "pages_allocatable": self.cfg.num_pages - 1,
            "preemptions": self._preemptions,
            "recovered_requests": self._recovered,
            "timed_out_requests": self._timed_out,
            "shed_requests": self._shed,
            "page_churn": self.pool.total_allocs + self.pool.total_frees,
            "trash_rows_written": self._trash_rows,
            "admissions": self._admissions,
            "admit_steps": self._admit_steps,
            # admissions / admit_fetches: admissions a blocking fetch
            "admit_fetches": self._admit_fetches,
            "max_admits_in_step": self._max_admits_in_step,
            "pages_grown": self._pages_grown,
            "prefill_chunks": self._prefill_chunks,
            # causal (query, key) pairs a layer the latent chunk walk
            # scored: len * offset + len * (len + 1) / 2 a chunk, over its
            # real rows (0 without the walk: "gather", or no latent)
            "chunk_attn_pairs": self._chunk_attn_pairs,
            # commit_pages / admissions: pages a one-shot commit writes,
            # ceil(bucket / page_size) (0 with prefill_chunk set)
            "commit_pages": self._commit_pages,
            # decode_puts / decode_steps: host-to-device puts a decode
            # step (one packed vector); whole-pool audits run (none on
            # the path of a step: ``_audit_pools``)
            "decode_puts": self._decode_puts,
            "pool_audits": self._pool_audits,
            # summed over decode steps and layers (``_step_counters``);
            # selected / scored is the sparsity served
            "selected_tokens": self._counts["selected_tokens"],
            "scored_tokens": self._counts["scored_tokens"],
            # of the experts whose matrices are here (a share: the held)
            "experts_hit": self._counts["experts_hit"],
            # mean over decode steps of the layers' mean of (most tokens
            # on one expert / mean tokens an expert)
            "expert_tokens_max_over_mean": self._counts["expert_ratio_milli"]
            / 1e3 / steps,
            # the page groups: live pages of each now, window pages given
            # back as the window passed them, and the keys the decode
            # steps attended on the layers of each kind (a model with
            # window layers sows them; 0 otherwise)
            "pages_live_full": self.pool.allocated_pages,
            "pages_live_window": (
                self.window_pool.allocated_pages if self.window_pool else 0
            ),
            "window_pages_freed": self._window_pages_freed,
            "full_tokens_read": self._counts["full_tokens_read"],
            "window_tokens_read": self._counts["window_tokens_read"],
            # a model with latent attention: latent rows the decode steps
            # attended, summed over active slots and sublayers; an expert
            # layer that holds a share or has zero-compute experts: its
            # (token, expert) pairs by where the expert is (the three sum
            # to top_k a token a layer). 0 otherwise
            "latent_tokens_read": self._counts["latent_tokens_read"],
            "held_expert_pairs": self._counts["held_expert_pairs"],
            "zero_expert_pairs": self._counts["zero_expert_pairs"],
            "absent_expert_pairs": self._counts["absent_expert_pairs"],
            # a share under group-limited choice: (token, layer) pairs
            # whose kept groups hold a held expert. 0 otherwise
            "held_group_tokens": self._counts["held_group_tokens"],
            # a model with lightning layers: (active slot, layer) state
            # rows the decode steps advanced; with block-sparse layers:
            # positions the decode steps' queries attended and positions
            # live, and compressed keys scored (past dense_len), each
            # summed over active slots, layers and KV groups. 0 otherwise
            "lightning_state_updates": self._counts["lightning_state_updates"],
            "sparse_selected_tokens": self._counts["sparse_selected_tokens"],
            "sparse_live_tokens": self._counts["sparse_live_tokens"],
            "sparse_scored_kernels": self._counts["sparse_scored_kernels"],
        }


# ----------------------------------------------------------- graftcheck


def make_serve_trace_entry(_impl: str = "gather", **overrides):
    """A graftcheck ``TracedStep`` around the engine's real jitted
    decode step: tiny paged transformer, the live argument shapes, the
    donation contract on the page pools. The audits (``lm-serve`` for
    the gather reference, ``lm-serve-paged`` for the Pallas
    paged-attention kernel) lower exactly what serving runs."""
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        TracedStep,
    )
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
        TransformerLM,
    )

    kw: dict[str, Any] = dict(
        vocab_size=64,
        num_layers=2,
        num_heads=2,
        d_model=32,
        d_ff=64,
        max_seq_len=64,
        attention_impl="dense",
        use_rope=True,
    )
    kw.update(overrides)
    model = TransformerLM(**kw)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    cfg = ServeConfig(
        num_slots=4, page_size=4, num_pages=17, max_pages_per_slot=8,
        paged_attention_impl=_impl,
    )
    eng = ServingEngine(model, params, cfg)
    args = (
        params, eng._pages, jnp.asarray(eng._probe_decode_arg()),
        jax.random.key(0),
    )
    return TracedStep(
        name="lm-serve" if _impl == "gather" else "lm-serve-paged",
        fn=eng._decode_step,
        args=args,
        axis_sizes={},
        sync=None,
        check_donation=True,
        detail={
            "num_slots": cfg.num_slots,
            "page_size": cfg.page_size,
            "num_pages": cfg.num_pages,
            "paged_attention_impl": eng.paged_attention_impl,
        },
    )


def make_paged_serve_trace_entry(**overrides):
    """``lm-serve`` with the Pallas paged-attention kernel in the decode
    step (``paged_attention_impl="kernel"``): TA003/TA005 account the
    kernel call and confirm no dead dense-gather ops ride along, and the
    donation audit checks the pool aliases survive the kernel path."""
    return make_serve_trace_entry(_impl="kernel", **overrides)


def _register_serve_trace_entries() -> None:
    from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.registry import (
        register_entrypoint,
    )

    register_entrypoint(
        "lm-serve", make_serve_trace_entry, tags=("lm", "serve")
    )
    register_entrypoint(
        "lm-serve-paged", make_paged_serve_trace_entry, tags=("lm", "serve")
    )


_register_serve_trace_entries()
