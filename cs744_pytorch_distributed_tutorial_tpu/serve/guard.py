"""graftguard — deadlines, admission control, overload shedding, and
supervised engine auto-recovery for the serving stack.

The engine (``serve/engine.py``) assumes a well-behaved world: every
submitted request eventually decodes, the queue is unbounded, and the
only failure it survives is a cooperative kill/resume. This module adds
the production guardrails, all host-side so the fixed-shape decode step
never retraces (GL002):

- **Per-request deadlines** (``ServeGuard.expire``): ``deadline_s``
  bounds arrival→retire wall time, ``max_queue_s`` bounds time queued
  before first admission. Swept at the top of every ``step()`` —
  equivalently, checked at admission (an expired queue head is removed
  before refill) and per decode step (an expired active slot retires
  and its pages free immediately; ``PagePool.free`` checks each page
  of the reclamation). Expired requests resolve terminally as
  ``timed_out`` — never silently dropped, never leaked.
- **Admission control + shedding** (``ServeGuard.admit``, called from
  ``submit()``): a bounded queue rejects at ``max_queue_depth``
  (status ``rejected``); policy ``"degrade"`` first trims
  ``max_new_tokens`` toward ``degrade_floor`` under pool pressure, so
  the engine sheds WORK before it sheds REQUESTS. Every shed emits a
  ``kind:"serve_shed"`` record with a machine-readable ``reason``
  (``queue_full`` / ``degrade_trim``). Because the per-request PRNG
  streams are keyed by (req_id, absolute token index), a degrade-
  trimmed request's output is a bitwise PREFIX of its untrimmed oracle
  output at any temperature.
- **Supervised auto-recovery** (``run_serve_with_recovery``): the serve
  mirror of ``utils/failure.py::run_with_recovery``. It drives a
  Poisson workload against the engine; a detected ``ServeFailure``
  (``DecodeNanError`` from poisoned logits, ``EngineCrashError`` from a
  dead step, ``HungStepError`` after the ``StepWatchdog`` climbs its
  warn→flight-dump→abort ladder) triggers: snapshot the dead engine's
  host state, exponential backoff, rebuild a fresh engine
  (``make_engine``), re-install the chaos monkey (its cumulative
  decode-step counter spans restarts, so popped faults never re-fire),
  ``resume()`` the snapshot, and continue the workload. In-flight
  requests replay token-identically (greedy bitwise; sampled via the
  per-request PRNG streams). Every transition emits ``recovery_*``
  events; a crash never surfaces to the client.

``docs/reliability.md`` ("Serving under failure and overload") is the
operator story; ``tests/test_serve_guard.py`` and the chaos-smoke CI
job pin all of it.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from cs744_pytorch_distributed_tutorial_tpu.serve.engine import Request
from cs744_pytorch_distributed_tutorial_tpu.serve.loadgen import (
    _emit_summary,
    _summarize,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
    DecodeNanError,
    EngineCrashError,
    HungStepError,
    ServeFailure,
    StepWatchdog,
    emit_event,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.logging import get_logger

__all__ = [
    "GuardConfig",
    "ServeGuard",
    "run_serve_with_recovery",
    "ServeFailure",
    "DecodeNanError",
    "EngineCrashError",
    "HungStepError",
]


@dataclass
class GuardConfig:
    """Admission-control and SLO policy for a ``ServeGuard``.

    All knobs default to "off" (None) — an all-default guard is a
    no-op, so wiring one unconditionally costs nothing.
    """

    # Default per-request budgets; a request's own ``deadline_s`` /
    # ``max_queue_s`` fields (set by the client) win over these.
    deadline_s: float | None = None
    max_queue_s: float | None = None
    # Bounded queue: submissions beyond this depth shed. None = unbounded.
    max_queue_depth: int | None = None
    # "reject": over-bound submissions terminally reject.
    # "degrade": ALSO trim max_new_tokens toward ``degrade_floor`` when
    # the pool is under pressure — shed work before shedding requests.
    shed_policy: str = "reject"
    degrade_floor: int = 8
    # Pool pressure = free pages below this fraction of the allocatable
    # pool (num_pages - 1).
    pressure_free_frac: float = 0.25

    def __post_init__(self) -> None:
        if self.shed_policy not in ("reject", "degrade"):
            raise ValueError(
                f'shed_policy must be "reject" or "degrade", got '
                f"{self.shed_policy!r}"
            )
        if self.degrade_floor < 1:
            raise ValueError(
                f"degrade_floor must be >= 1, got {self.degrade_floor}"
            )
        if not (0.0 <= self.pressure_free_frac <= 1.0):
            raise ValueError(
                f"pressure_free_frac must be in [0, 1], got "
                f"{self.pressure_free_frac}"
            )


@dataclass
class ServeGuard:
    """Admission control + deadline enforcement over a ``ServingEngine``.

    Pass one as ``ServingEngine(..., guard=ServeGuard(cfg))``. The
    engine calls ``admit`` from ``submit()`` and ``expire`` at the top
    of every ``step()``; both operate purely on host state and the
    engine's injectable ``clock``, so guarded runs are deterministic
    under a fake clock and the jitted decode step is untouched.

    ``shed_counts`` accumulates shed events by reason (terminal rejects
    AND non-terminal degrade trims) for tests and summaries.
    """

    cfg: GuardConfig = field(default_factory=GuardConfig)
    shed_counts: dict[str, int] = field(default_factory=dict)
    timed_out: int = 0

    def _count(self, reason: str) -> None:
        self.shed_counts[reason] = self.shed_counts.get(reason, 0) + 1

    # Called from ``ServingEngine.submit`` after id assignment, before
    # the capacity checks and the queue append.
    def admit(self, engine: Any, req: Request) -> bool:
        """Admission control for one submission. Returns False when the
        request was terminally shed (engine._shed_reject already ran);
        may mutate ``req`` (budget defaults, degrade trim) on the True
        path."""
        if req.recovered:
            # A resumed request was already admitted once (possibly on a
            # dead engine); shedding it now would break the recovery
            # contract that no admitted request is lost. Its budgets
            # came through the snapshot.
            return True
        cfg = self.cfg
        if req.deadline_s is None:
            req.deadline_s = cfg.deadline_s
        if req.max_queue_s is None:
            req.max_queue_s = cfg.max_queue_s
        if (
            cfg.max_queue_depth is not None
            and len(engine._queue) >= cfg.max_queue_depth
        ):
            self._count("queue_full")
            engine._shed_reject(
                req, "queue_full", queue_depth=len(engine._queue)
            )
            return False
        if cfg.shed_policy == "degrade":
            pool = engine.pool
            allocatable = pool.num_pages - 1
            pressured = pool.free_pages < cfg.pressure_free_frac * allocatable
            if pressured and req.max_new_tokens > cfg.degrade_floor:
                trimmed = int(req.max_new_tokens) - cfg.degrade_floor
                req.max_new_tokens = cfg.degrade_floor
                self._count("degrade_trim")
                engine._emit({
                    "kind": "serve_shed",
                    "time": time.time(),
                    "id": req.req_id,
                    "reason": "degrade_trim",
                    "terminal": False,
                    "tokens_shed": trimmed,
                    "free_pages": pool.free_pages,
                })
        return True

    # Called from the top of ``ServingEngine.step``.
    def expire(self, engine: Any) -> None:
        """Sweep queued and active requests against their budgets; every
        expiry resolves terminally as ``timed_out`` (queued requests
        just finish; active slots retire and free their pages)."""
        now = engine.clock()
        expired = [
            (r, self._expiry_reason(r, now, queued=True))
            for r in engine._queue
        ]
        for req, reason in expired:
            if reason is None:
                continue
            engine._queue.remove(req)
            self.timed_out += 1
            engine._expire_request(req, slot=None, reason=reason)
        for i, slot in enumerate(engine._slots):
            if slot is None:
                continue
            reason = self._expiry_reason(slot.req, now, queued=False)
            if reason is not None:
                self.timed_out += 1
                engine._expire_request(slot.req, slot=i, reason=reason)

    @staticmethod
    def _expiry_reason(
        req: Request, now: float, *, queued: bool
    ) -> str | None:
        if (
            req.deadline_s is not None
            and req.arrival_time is not None
            and now - req.arrival_time > req.deadline_s
        ):
            return "deadline"
        if (
            queued
            and req.max_queue_s is not None
            and req.first_token_time is None
            and now - req.submit_time > req.max_queue_s
        ):
            return "queue_wait"
        return None


def _merge_stats(total: dict[str, Any], part: dict[str, Any]) -> None:
    """Fold one engine generation's ``stats()`` into the running totals
    (sums for counters, max for high-water marks)."""
    for k, v in part.items():
        if k in ("page_high_water", "max_admits_in_step"):
            total[k] = max(total.get(k, 0), v)
        elif k in ("slot_occupancy", "pages_allocatable"):
            total[k] = v  # latest generation's view
        else:
            total[k] = total.get(k, 0) + v


def run_serve_with_recovery(
    make_engine: Callable[[], Any],
    workload: Any,
    *,
    monkey: Any = None,
    max_restarts: int = 2,
    backoff_s: float = 0.0,
    backoff_factor: float = 2.0,
    max_backoff_s: float = 60.0,
    sleep: Callable[[float], None] = time.sleep,
    step_timeout_s: float | None = None,
    telemetry: Any = None,
    sink: Any = None,
    warmup: bool = True,
    label: str = "continuous",
) -> dict[str, Any]:
    """Drive a Poisson ``Workload`` with supervised engine auto-recovery.

    The serving mirror of ``run_with_recovery``: the loop submits
    arrivals on the wall clock and steps the engine; a ``ServeFailure``
    — ``DecodeNanError`` (host-side token validation), ``EngineCrashError``
    (the step died), or ``HungStepError`` (the ``StepWatchdog``'s
    warn→dump→abort ladder exhausted on a wedged step) — triggers the
    restart ladder instead of surfacing to the client:

    1. ``recovery_restart`` event + exponential backoff
       (``backoff_s * backoff_factor**(n-1)``, capped at
       ``max_backoff_s``; ``sleep`` injectable),
    2. ``snapshot()`` the dead engine's host state (valid even after the
       crash — the engine raises before per-step bookkeeping mutates)
       and bank its completed requests,
    3. ``make_engine()`` a fresh engine, re-install ``monkey``
       (``ServeChaosMonkey`` — its cumulative decode-step counter spans
       restarts, so a popped fault never re-fires),
    4. ``resume()`` the snapshot: in-flight requests replay
       token-identically through the recompute path (greedy bitwise;
       sampled via the per-request PRNG streams),
    5. continue the workload where it left off.

    Past ``max_restarts`` the ladder gives up: ``recovery_giveup``
    (with the failure's full traceback string) and re-raise.

    ``step_timeout_s`` arms a per-engine ``StepWatchdog`` with the
    escalation ladder ``("warn", "dump", "abort")`` and the engine's
    flight recorder — a stalled decode step warns, dumps the flight
    tail, then (via the abort stage) marks the step hung; when the
    step finally returns the supervisor raises ``HungStepError`` into
    the ladder above. The first engine warms up its prefill buckets
    before the clock starts (as ``run_poisson`` does); replacement
    engines compile inline — that recompilation IS the recovery
    downtime and is honestly on the clock.

    Returns the ``serve_summary`` record (aggregated across engine
    generations, ``restarts`` included), emitted on ``sink`` with the
    same bench twins ``run_poisson`` emits.
    """
    log = get_logger()
    engine = make_engine()

    if warmup:
        # Same discipline as run_poisson: compile the decode step and
        # the prefill buckets this workload will touch, off the clock,
        # with sink/tracer/guard detached so warmup traffic never lands
        # in telemetry or admission counters. The monkey installs AFTER
        # warmup, so fault-schedule indices count MEASURED decode steps
        # only — index k means "the k-th live decode step", warmup or
        # not.
        saved = (engine.sink, engine.tracer, engine.guard)
        engine.sink = engine.tracer = engine.guard = None
        buckets = sorted({
            engine._bucket_for(len(p)) for p in workload.prompts
        })
        for b in buckets:
            # budget 2, not 1: the second token forces a decode step, so
            # the decode executable compiles off the clock too.
            engine.submit(Request(
                prompt=np.ones((min(b, engine.max_seq_len - 2),), np.int32),
                max_new_tokens=2,
            ))
        while engine.busy:
            engine.step()
        engine._completed.clear()
        engine._preemptions = 0
        engine._timed_out = 0
        engine._shed = 0
        engine._step_count = 0
        engine._active_slot_steps = 0
        engine._trash_rows = 0
        engine._admissions = engine._admit_steps = engine._commit_pages = 0
        engine._admit_fetches = engine._chunk_attn_pairs = 0
        engine._decode_puts = engine._pool_audits = 0
        engine._max_admits_in_step = engine._pages_grown = 0
        engine._decode_walls.clear()
        engine._event_ring.clear()
        engine.pool.high_water = 0
        engine.pool.total_allocs = 0
        engine.pool.total_frees = 0
        engine._next_id = 0
        engine.sink, engine.tracer, engine.guard = saved
        if engine.tracer is not None:
            engine.tracer.reset(engine.clock())

    if monkey is not None:
        monkey.install(engine)

    def _make_watchdog(eng: Any) -> tuple[Any, dict[str, bool]]:
        if step_timeout_s is None:
            return None, {"flag": False}
        hung = {"flag": False}

        def on_hang(elapsed_s: float) -> None:
            hung["flag"] = True

        wd = StepWatchdog(
            step_timeout_s,
            on_hang=on_hang,
            escalation=("warn", "dump", "abort"),
            flight_recorder=eng.make_flight_recorder(),
        )
        return wd, hung

    wd, hung = _make_watchdog(engine)
    totals: dict[str, Any] = {}
    finished: list[Request] = []
    restarts = 0
    prev_restarts = 0
    arrivals = workload.arrivals
    n = len(arrivals)
    i = 0
    t0 = engine.clock()
    try:
        while i < n or engine.busy:
            now = engine.clock() - t0
            while i < n and arrivals[i] <= now:
                engine.submit(Request(
                    prompt=workload.prompts[i],
                    max_new_tokens=int(workload.max_new_tokens[i]),
                    arrival_time=t0 + float(arrivals[i]),
                ))
                i += 1
            if not engine.busy:
                if i < n:
                    time.sleep(
                        min(0.001, max(0.0, float(arrivals[i]) - now))
                    )
                continue
            try:
                if wd is not None:
                    with wd.watch():
                        engine.step()
                else:
                    engine.step()
                if hung["flag"]:
                    hung["flag"] = False
                    raise HungStepError(elapsed_s=step_timeout_s or 0.0)
            except ServeFailure as e:
                restarts += 1
                if restarts > max_restarts:
                    import traceback as _tb

                    emit_event(
                        telemetry,
                        "recovery_giveup",
                        restarts=restarts - 1,
                        failure=repr(e),
                        traceback="".join(_tb.format_exception(e)),
                    )
                    log.critical(
                        "serve recovery giving up after %d restarts "
                        "(last failure: %s)", restarts - 1, e,
                    )
                    raise
                delay = 0.0
                if backoff_s > 0:
                    delay = min(
                        backoff_s * backoff_factor ** (restarts - 1),
                        max_backoff_s,
                    )
                emit_event(
                    telemetry,
                    "recovery_restart",
                    restart=restarts,
                    max_restarts=max_restarts,
                    failure=repr(e),
                    tier="engine",
                    backoff_s=delay,
                )
                log.error(
                    "serve failure (%s); engine restart %d/%d "
                    "(backoff %.1fs)", e, restarts, max_restarts, delay,
                )
                # The dead engine's host state is snapshot-consistent:
                # every ServeFailure raises before per-step bookkeeping.
                snap = engine.snapshot()
                if engine.tracer is not None:
                    # The tracer outlives the generation: seal its open
                    # spans at the crash instant so the next
                    # generation's spans never overlap them.
                    engine.tracer.on_crash(engine.clock())
                finished.extend(engine._completed)
                _merge_stats(totals, engine.stats())
                if wd is not None:
                    wd.close()
                if delay > 0:
                    sleep(delay)
                engine = make_engine()
                if monkey is not None:
                    monkey.install(engine)
                engine.resume(snap)
                wd, hung = _make_watchdog(engine)
    finally:
        if wd is not None:
            wd.close()
    if restarts > prev_restarts:
        emit_event(telemetry, "recovery_complete", restarts=restarts)
    engine.finalize_trace()
    reqs = finished + list(engine._completed)
    _merge_stats(totals, engine.stats())
    totals["requests_done"] = len(reqs)
    totals["restarts"] = restarts
    # Terminal accounting: every submitted request must have resolved to
    # exactly one terminal status — nothing unresolved, nothing doubled.
    ids = sorted(r.req_id for r in reqs)
    assert ids == sorted(set(ids)), (
        f"requests resolved more than once: "
        f"{sorted({x for x in ids if ids.count(x) > 1})}"
    )
    unresolved = [r.req_id for r in reqs if r.terminal_status is None]
    assert not unresolved, f"requests ended unresolved: {unresolved}"
    assert len(ids) == n, (
        f"submitted {n} requests but only {len(ids)} resolved"
    )
    makespan = max(
        (r.done_time for r in reqs if r.done_time is not None),
        default=t0,
    ) - t0
    record = _summarize(label, reqs, makespan, totals)
    _emit_summary(sink, record)
    return record
