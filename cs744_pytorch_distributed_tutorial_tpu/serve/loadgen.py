"""Poisson load generation and the batch-at-a-time baseline.

``make_poisson_workload`` draws a seeded open-loop trace (exponential
inter-arrivals, uniform prompt/output lengths); ``run_poisson`` replays
it against a ``ServingEngine`` on the wall clock and reports the serving
metrics the ISSUE names:

- **TTFT** (time to first token): first sampled token's host arrival
  minus the request's scheduled arrival — it INCLUDES queue time, which
  is the point (tail TTFT is where batch-at-a-time loses).
- **per-token decode latency**: (done - first token) / (output - 1).
- **ITL** (inter-token latency): gaps between consecutive streamed
  token deliveries (``Request.token_times``, populated by the engine's
  per-token surfacing) — the tail a streaming client sees, including
  prefill stalls of co-admitted requests and preemption gaps.
- **aggregate tokens/sec**: total generated tokens / makespan (first
  arrival to last completion).

The baseline (``run_batch_baseline``) replays the SAME trace through
``infer/generate.py``'s batch-at-a-time generator: requests batch in
arrival order, the batch pads every prompt to its longest and decodes
``max(output budgets)`` steps, and nothing streams out early — so a
request's TTFT is when its whole batch returns. That is the measured
definition, not a strawman: it is exactly what serving with the
training-style generator would do. Both emit ``kind:"serve_summary"``
records through the ``obs`` sinks; ``benchmarks/regress.py`` gates the
p99/tokens-per-sec envelope in CI (docs/serving.md).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any

import jax
import numpy as np

from cs744_pytorch_distributed_tutorial_tpu.serve.engine import (
    Request,
    ServingEngine,
)


@dataclass
class Workload:
    """A fully materialized open-loop trace (seeded, replayable)."""

    arrivals: np.ndarray  # [N] seconds from trace start, sorted
    prompts: list[np.ndarray]  # [N] int32 token vectors
    max_new_tokens: np.ndarray  # [N] int32

    def __len__(self) -> int:
        return len(self.prompts)


def make_poisson_workload(
    *,
    num_requests: int,
    rate_rps: float,
    prompt_len: tuple[int, int],
    output_len: tuple[int, int],
    vocab_size: int,
    seed: int = 0,
) -> Workload:
    """Poisson arrivals at ``rate_rps`` with uniform prompt/output
    lengths in the given inclusive ranges. Token ids avoid 0 (the
    conventional pad id)."""
    if num_requests < 1:
        raise ValueError(f"num_requests must be >= 1, got {num_requests}")
    if rate_rps <= 0:
        raise ValueError(f"rate_rps must be > 0, got {rate_rps}")
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=num_requests)
    gaps[0] = 0.0  # first request arrives at t=0 — makespan starts there
    arrivals = np.cumsum(gaps)
    plens = rng.integers(prompt_len[0], prompt_len[1] + 1, num_requests)
    olens = rng.integers(output_len[0], output_len[1] + 1, num_requests)
    prompts = [
        rng.integers(1, vocab_size, size=int(n)).astype(np.int32)
        for n in plens
    ]
    return Workload(
        arrivals=arrivals,
        prompts=prompts,
        max_new_tokens=olens.astype(np.int32),
    )


def _percentile(values: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(values), q)) if values else 0.0


def _summarize(
    label: str,
    reqs: list[Request],
    makespan: float,
    extra: dict[str, Any],
) -> dict[str, Any]:
    # Terminal-status accounting (serve/guard.py): every request that
    # leaves the system lands in exactly one bucket. Latency percentiles
    # are computed over requests that actually DELIVERED (completed /
    # recovered) — a rejected request has no latency, and a timed-out
    # one's truncated stream would flatter the tail.
    statuses = {"completed": 0, "rejected": 0, "timed_out": 0, "recovered": 0}
    for r in reqs:
        t = r.terminal_status
        if t in statuses:
            statuses[t] += 1
    delivered = [
        r
        for r in reqs
        if r.terminal_status in ("completed", "recovered")
        and r.first_token_time is not None
    ]
    ttfts = [
        (r.first_token_time - r.arrival_time) * 1e3 for r in delivered
    ]
    per_tok = [
        (r.done_time - r.first_token_time) * 1e3 / max(1, r.output_tokens - 1)
        for r in delivered
    ]
    # Inter-token latency: gaps between consecutive SURFACED tokens of
    # one request (streaming delivery — engine._surface). Measured, not
    # derived from the decode mean: the tail includes prefill stalls of
    # co-resident admissions and preemption gaps, which is what a
    # streaming client actually experiences. The batch baseline streams
    # nothing (token_times stays empty), so its ITL reports 0 — TTFT is
    # its honest latency metric.
    itls: list[float] = []
    for r in delivered:
        if len(r.token_times) > 1:
            diffs = np.diff(np.asarray(r.token_times))
            # A recovered request's token_times mix the dead process's
            # clock epoch with the resumed engine's: the diff across
            # each resume boundary "measures" the kill gap (or worse, a
            # negative monotonic-clock delta), not an inter-token
            # latency. Exclude exactly those gaps; every real gap —
            # including preemption stalls — still counts.
            skip = {
                b - 1
                for b in getattr(r, "resume_boundaries", ())
                if 1 <= b <= len(diffs)
            }
            itls.extend(
                float(d) * 1e3
                for i, d in enumerate(diffs)
                if i not in skip
            )
    total_tokens = sum(r.output_tokens for r in reqs)
    return {
        "kind": "serve_summary",
        "time": time.time(),
        "engine": label,
        "requests": len(reqs),
        "total_output_tokens": int(total_tokens),
        "makespan_s": round(makespan, 4),
        "ttft_p50_ms": round(_percentile(ttfts, 50), 3),
        "ttft_p99_ms": round(_percentile(ttfts, 99), 3),
        "decode_ms_per_token_p50": round(_percentile(per_tok, 50), 4),
        "itl_p50_ms": round(_percentile(itls, 50), 4),
        "itl_p99_ms": round(_percentile(itls, 99), 4),
        "tokens_per_sec": round(total_tokens / makespan, 2)
        if makespan > 0
        else 0.0,
        **statuses,
        **extra,
    }


def _emit_summary(sink: Any, record: dict[str, Any]) -> None:
    """Emit a serve_summary plus its bench-shaped twins (metric + value)
    so regress.py gates the serving envelope with its standard
    arithmetic — including the absolute budgets
    benchmarks/serve_smoke_budget.json arms. Shared by ``run_poisson``
    and ``serve/guard.py::run_serve_with_recovery``."""
    if sink is None:
        return
    sink.emit(record)
    for metric, value, unit in (
        ("serve_tokens_per_sec", record["tokens_per_sec"], "tokens/sec"),
        ("serve_ttft_p99_ms", record["ttft_p99_ms"], "ms"),
        ("serve_itl_p99_ms", record["itl_p99_ms"], "ms"),
        # chaos visibility: requests replayed from a ServeSnapshot
        # after a kill/resume (docs/reliability.md) — 0 on clean runs
        (
            "serve_recovered",
            record.get("recovered_requests", 0),
            "requests",
        ),
        # guard visibility (docs/reliability.md "Serving under failure
        # and overload"): terminal sheds and deadline expiries — 0 on
        # unguarded or under-capacity runs.
        ("serve_rejected", record.get("rejected", 0), "requests"),
        ("serve_timed_out", record.get("timed_out", 0), "requests"),
    ):
        sink.emit({
            "kind": "bench",
            "time": time.time(),
            "metric": metric,
            "value": value,
            "unit": unit,
        })


def run_poisson(
    engine: ServingEngine,
    workload: Workload,
    *,
    sink: Any = None,
    warmup: bool = True,
    watchdog: Any = None,
) -> dict[str, Any]:
    """Replay ``workload`` open-loop against the engine on the wall
    clock and return (and emit) the ``serve_summary`` record.

    ``warmup=True`` first runs one throwaway request per prefill bucket
    plus a decode step, so compile time does not pollute the measured
    TTFTs (and so the post-warmup 0-retrace contract covers the whole
    measured run). A ``StepWatchdog`` passed as ``watchdog`` arms
    around every measured engine step — wire its ``flight_recorder`` to
    ``engine.make_flight_recorder()`` so a wedged step dumps the serve
    event ring (docs/observability.md)."""
    clock = engine.clock
    if warmup:
        buckets = sorted({engine._bucket_for(len(p)) for p in workload.prompts})
        # no warmup records, no warmup spans, no warmup sheds (the
        # guard's admission counters must only see measured traffic)
        saved_sink, engine.sink = engine.sink, None
        saved_tracer, engine.tracer = engine.tracer, None
        saved_guard, engine.guard = engine.guard, None
        try:
            for b in buckets:
                plen = min(b, engine.max_seq_len - 1)
                engine.submit(
                    Request(
                        prompt=np.ones((plen,), np.int32), max_new_tokens=2
                    )
                )
            engine.run()
        finally:
            engine.sink = saved_sink
            engine.tracer = saved_tracer
            engine.guard = saved_guard
        # warmup requests must not count against the measurement
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
        engine.pool.high_water = engine.pool.allocated_pages
        engine.pool.total_allocs = 0
        engine.pool.total_frees = 0
        if engine.tracer is not None:
            engine.tracer.reset(clock())

    t0 = clock()
    n = len(workload)
    i = 0
    submitted: list[Request] = []
    while i < n or engine.busy:
        now = clock() - t0
        while i < n and workload.arrivals[i] <= now:
            submitted.append(engine.submit(
                Request(
                    prompt=workload.prompts[i],
                    max_new_tokens=int(workload.max_new_tokens[i]),
                    arrival_time=t0 + float(workload.arrivals[i]),
                )
            ))
            i += 1
        if engine.busy:
            if watchdog is not None:
                with watchdog.watch():
                    engine.step()
            else:
                engine.step()
        elif i < n:
            # idle until the next arrival (open loop — do not pull it in
            # early; the arrival process IS the experiment)
            time.sleep(
                min(0.002, max(0.0, float(workload.arrivals[i]) - now))
            )
    engine.finalize_trace()  # flush the final partial serve_window
    reqs = engine._completed[:]
    # Terminal accounting (serve/guard.py): every submitted request must
    # resolve to exactly one terminal status — a drained engine with an
    # unresolved (or doubly-resolved) request is a scheduler bug, not a
    # metrics footnote.
    unresolved = [r.req_id for r in submitted if r.terminal_status is None]
    assert not unresolved, f"requests ended unresolved: {unresolved}"
    ids = [r.req_id for r in reqs]
    assert len(ids) == len(set(ids)), (
        f"requests resolved more than once: "
        f"{sorted({x for x in ids if ids.count(x) > 1})}"
    )
    makespan = max(r.done_time for r in reqs) - t0 if reqs else 0.0
    record = _summarize(
        "continuous",
        reqs,
        makespan,
        {
            **engine.stats(),
            "num_slots": engine.cfg.num_slots,
            "page_size": engine.cfg.page_size,
            "num_pages": engine.cfg.num_pages,
            "kv_pool_tokens": engine.cfg.num_pages * engine.cfg.page_size,
        },
    )
    _emit_summary(sink, record)
    return record


def run_batch_baseline(
    model: Any,
    params: Any,
    workload: Workload,
    *,
    batch_size: int,
    temperature: float = 0.0,
    eos_id: int | None = None,
    sink: Any = None,
    warmup: bool = True,
) -> dict[str, Any]:
    """Replay the workload through batch-at-a-time ``make_generator``:
    requests group into arrival-order batches of ``batch_size``, a batch
    launches once its last member has arrived, every prompt right-pads
    to the batch's longest, and the loop runs the batch's LONGEST output
    budget. Tokens past a request's own budget are discarded (they were
    still computed — that is the waste being measured). TTFT for every
    request in a batch is the batch's return time.

    The generator's dense KV cache holds ``batch_size * max_seq_len``
    token rows; compare ``kv_cache_tokens`` in the summary against the
    engine's ``kv_pool_tokens`` for the equal-HBM framing."""
    from cs744_pytorch_distributed_tutorial_tpu.infer import make_generator

    if batch_size < 1:
        raise ValueError(f"batch_size must be >= 1, got {batch_size}")
    budget_max = int(np.max(workload.max_new_tokens))
    gen = make_generator(
        model,
        max_new_tokens=budget_max,
        temperature=temperature,
        eos_id=eos_id,
    )
    plen_max = max(len(p) for p in workload.prompts)
    if warmup:
        gen(
            params,
            np.ones((batch_size, plen_max), np.int32),
            jax.random.key(0),
        )[0].block_until_ready()

    clock = time.monotonic
    t0 = clock()
    reqs: list[Request] = []
    n = len(workload)
    for start in range(0, n, batch_size):
        idx = list(range(start, min(start + batch_size, n)))
        batch_arrival = t0 + float(workload.arrivals[idx[-1]])
        now = clock()
        if now < batch_arrival:
            time.sleep(batch_arrival - now)
        plen = max(len(workload.prompts[j]) for j in idx)
        prompt = np.zeros((batch_size, plen), np.int32)
        for row, j in enumerate(idx):
            p = workload.prompts[j]
            # right-padded: shorter prompts condition on pad tokens past
            # their true length — one more batch-at-a-time artifact the
            # per-request engine simply does not have
            prompt[row, : len(p)] = p
        launch = clock()
        out = np.asarray(gen(params, prompt, jax.random.key(start)))
        done = clock()
        for row, j in enumerate(idx):
            budget = int(workload.max_new_tokens[j])
            toks = out[row, :budget].tolist()
            if eos_id is not None and eos_id in toks:
                toks = toks[: toks.index(eos_id) + 1]
            r = Request(
                prompt=workload.prompts[j],
                max_new_tokens=budget,
                req_id=j,
                arrival_time=t0 + float(workload.arrivals[j]),
            )
            r.orig_prompt_len = len(workload.prompts[j])
            r.orig_max_new_tokens = budget
            r.generated = toks
            r.submit_time = launch
            # batch-at-a-time streams nothing: the first token a client
            # sees arrives when the whole batch returns
            r.first_token_time = done
            r.done_time = done
            reqs.append(r)
    makespan = max(r.done_time for r in reqs) - t0 if reqs else 0.0
    record = _summarize(
        "batch",
        reqs,
        makespan,
        {
            "batch_size": batch_size,
            "kv_cache_tokens": batch_size * model.max_seq_len,
        },
    )
    if sink is not None:
        sink.emit(record)
    return record
