"""graftserve: request-level tracing + windowed SLO telemetry for serve/.

The serving engine's end-of-request records say WHAT a request's TTFT
was; this module records WHY. Three layers, all host-side bookkeeping
over timestamps the engine already takes (zero device work, zero extra
syncs — the post-warmup 0-retrace contract holds with tracing on):

- **Span timeline** (:class:`ServeTracer`): every request's lifecycle as
  closed spans — ``queue`` -> ``prefill[bucket=K]`` (or ``recompute``
  after a LIFO preemption / ``resume-replay`` after a kill+resume) ->
  coalesced ``decode_run`` spans (one per contiguous residency in a
  slot, NOT one per token) -> ``retire``, with ``preempt`` instants in
  between. Exportable as Chrome/Perfetto trace-event JSON: one lane per
  decode slot, an async-span lane for queue waits, and counter tracks
  for the pool (live/free pages, active slots, queue depth).
  :func:`check_spans` is the consistency gate CI runs — no orphan,
  unclosed, or overlapping spans — and :func:`reconcile` cross-checks
  span arithmetic against the engine's recorded TTFT/stream times.
- **Windowed SLO tracker**: ``kind:"serve_window"`` records at a
  configurable cadence — rolling TTFT/ITL p50/p99 over ring reservoirs,
  queue depth, preemption rate, slot occupancy, per-bucket prefill
  counts, and the pool counters — so SLO health is observable MID-run,
  not only from the post-hoc ``serve_summary``. The ITL reservoir is
  fed from the same surfaced-token gaps ``loadgen._summarize`` diffs,
  so windowed and post-hoc percentiles agree on a drained run.
- **Program profile** (:func:`profile_serve_programs`): device time
  (``utils.profiling.capture_device_profile``), compiled
  ``cost_analysis`` flops/bytes, and roofline class for the decode step
  and every warmed prefill bucket, plus ``decode_host_exposed_ms``:
  mean live host wall per decode step minus the profiled program time,
  i.e. what the host scheduler costs the decode loop.

Spans survive LIFO preemption (``decode_run`` closes, a new ``queue``
span opens at the preempt instant) and kill/resume replay (the fresh
engine's tracer opens ``resume-replay`` admission spans); the engine
feeds the tracer the SAME floats it stamps into ``first_token_time`` /
``token_times``, so queue+prefill span sums reconcile with recorded
TTFT exactly (the <=1 ms acceptance bound is by construction).
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from typing import Any

import numpy as np

__all__ = [
    "PREFILL_KINDS",
    "ServeTracer",
    "check_spans",
    "reconcile",
    "load_trace_dir",
    "render_serve_report",
    "profile_serve_programs",
]

# Admission span kinds: how a request's KV got (re)built in its slot.
PREFILL_KINDS = frozenset({"prefill", "recompute", "resume-replay"})
_INTERVAL_KINDS = PREFILL_KINDS | {"queue", "decode_run"}
# "shed" is terminal like "retire", but for a request REJECTED at
# admission control (serve/guard.py) — it never queued, so its whole
# lifecycle is the one instant. "retire" instants carry a ``status``
# field when the disposition is not "completed" (e.g. "timed_out").
_INSTANT_KINDS = frozenset({"preempt", "retire", "shed"})

TRACE_NAME = "serve_trace.json"
SPANS_NAME = "serve_spans.jsonl"
WINDOWS_NAME = "serve_windows.jsonl"
REQUESTS_NAME = "serve_requests.jsonl"


def _pct(values: Any, q: float) -> float | None:
    vals = np.asarray(list(values), dtype=np.float64)
    return round(float(np.percentile(vals, q)), 3) if vals.size else None


class ServeTracer:
    """Host-side span + SLO-window recorder for one :class:`ServingEngine`.

    The engine calls the ``on_*`` hooks with its own clock stamps; the
    tracer never reads a clock of its own for span endpoints, so spans
    and the engine's latency bookkeeping share the exact same floats.
    ``reset()`` (called by ``run_poisson`` after warmup) drops warmup
    spans so the exported timeline covers only the measured run.

    ``window_every_s`` arms the SLO tracker: ``on_decode_step`` returns
    a flat ``kind:"serve_window"`` record once per cadence interval
    (the engine emits it through its sink); ``flush_window`` emits the
    final partial window at drain. TTFT/ITL percentiles are rolling
    over ``window_capacity``-deep ring reservoirs.
    """

    def __init__(
        self,
        num_slots: int,
        *,
        window_every_s: float | None = None,
        window_capacity: int = 4096,
    ) -> None:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if window_every_s is not None and window_every_s <= 0:
            raise ValueError(
                f"window_every_s must be > 0, got {window_every_s}"
            )
        if window_capacity < 1:
            raise ValueError(
                f"window_capacity must be >= 1, got {window_capacity}"
            )
        self.num_slots = int(num_slots)
        self.window_every_s = window_every_s
        self.window_capacity = int(window_capacity)
        self.reset()

    def reset(self, now: float | None = None) -> None:
        """Drop all recorded state; ``now`` (engine clock) restarts the
        window origin so ``t_s`` counts from the measured run's start."""
        self.spans: list[dict[str, Any]] = []
        self.windows: list[dict[str, Any]] = []
        self.requests: list[dict[str, Any]] = []
        self._open_queue: dict[int, dict[str, Any]] = {}
        self._open_run: dict[int, dict[str, Any]] = {}
        self._t0: float | None = now
        self._last_flush: float | None = now
        self._ttft: deque[float] = deque(maxlen=self.window_capacity)
        self._itl: deque[float] = deque(maxlen=self.window_capacity)
        # (t, live_pages, free_pages, active_slots, queue_depth) at
        # decode-step cadence — the Perfetto counter tracks.
        self._pool_series: deque[tuple] = deque(maxlen=65536)
        self._last_pool: dict[str, Any] = {}
        self._churn_base: int | None = None
        self._trash_base: int | None = None
        self._reset_window_counters()

    def _reset_window_counters(self) -> None:
        self._tokens_w = 0
        self._done_w = 0
        self._preempt_w = 0
        self._steps_w = 0
        self._occ_w = 0
        self._queue_max_w = 0
        self._timeout_w = 0
        self._shed_w = 0
        self._prefill_w: dict[int, int] = {}

    def _seen(self, t: float) -> None:
        if self._t0 is None or t < self._t0:
            self._t0 = float(t)
        if self._last_flush is None:
            self._last_flush = float(t)

    # ------------------------------------------------------ engine hooks

    def on_submit(self, req: Any, now: float) -> None:
        """External submission: open the queue span at the request's
        arrival stamp. A resumed request's preserved ``arrival_time``
        belongs to the dead process's clock epoch, so its queue span
        restarts at the resubmission instant instead."""
        self._seen(float(now))
        if getattr(req, "recovered", False) or req.arrival_time is None:
            t0 = float(now)
        else:
            t0 = min(float(req.arrival_time), float(now))
        self._seen(t0)
        self._open_queue[req.req_id] = {
            "name": "queue", "req": int(req.req_id), "slot": None,
            "t0": t0, "t1": None,
        }

    def on_requeue(self, req: Any, now: float) -> None:
        """Preemption re-queue: a fresh queue span from the preempt
        instant until the recompute admission."""
        self._seen(float(now))
        self._open_queue[req.req_id] = {
            "name": "queue", "req": int(req.req_id), "slot": None,
            "t0": float(now), "t1": None,
        }

    def on_admit(
        self,
        req: Any,
        *,
        slot: int,
        bucket: int,
        t0: float,
        t1: float,
        kind: str,
        replayed: int = 0,
    ) -> None:
        """Admission prefill ran in ``[t0, t1]``; close the queue span
        at ``t0`` (the same float, so queue+prefill tile exactly)."""
        self._seen(float(t0))
        q = self._open_queue.pop(req.req_id, None)
        if q is not None:
            q["t1"] = float(t0)
            self.spans.append(q)
        span = {
            "name": kind, "req": int(req.req_id), "slot": int(slot),
            "bucket": int(bucket), "t0": float(t0), "t1": float(t1),
        }
        if replayed:
            span["replayed"] = int(replayed)
        self.spans.append(span)
        self._prefill_w[int(bucket)] = self._prefill_w.get(int(bucket), 0) + 1
        self._tokens_w += 1  # prefill surfaces the first token

    def _close_run(self, slot: int) -> None:
        run = self._open_run.pop(slot, None)
        if run is not None:
            self.spans.append(run)

    def on_decode_step(
        self,
        t0: float,
        t1: float,
        slot_reqs: dict[int, int],
        pool: dict[str, Any],
        queue_depth: int,
    ) -> dict[str, Any] | None:
        """One fixed-shape decode step over ``slot_reqs`` (slot ->
        req_id) ran in ``[t0, t1]``. Extends each slot's coalesced
        ``decode_run`` span, samples the pool counter series, and
        returns a ``serve_window`` record when the cadence elapsed."""
        self._seen(float(t0))
        for slot, rid in slot_reqs.items():
            run = self._open_run.get(slot)
            if run is None or run["req"] != rid:
                self._close_run(slot)  # missed retire — defensive close
                run = {
                    "name": "decode_run", "req": int(rid), "slot": int(slot),
                    "t0": float(t0), "t1": float(t1), "tokens": 0,
                }
                self._open_run[slot] = run
            run["t1"] = float(t1)
            run["tokens"] += 1
        self._steps_w += 1
        self._occ_w += len(slot_reqs)
        self._tokens_w += len(slot_reqs)
        self._queue_max_w = max(self._queue_max_w, int(queue_depth))
        if self._churn_base is None:
            self._churn_base = int(pool.get("churn", 0))
            self._trash_base = int(pool.get("trash", 0))
        self._last_pool = dict(pool)
        self._pool_series.append((
            float(t1), int(pool.get("live", 0)), int(pool.get("free", 0)),
            len(slot_reqs), int(queue_depth),
        ))
        if self.window_every_s is None or self._last_flush is None:
            return None
        if (float(t1) - self._last_flush) < self.window_every_s:
            return None
        return self.flush_window(float(t1), queue_depth=int(queue_depth))

    def on_preempt(self, req: Any, slot: int, now: float, replayed: int) -> None:
        self._seen(float(now))
        self._close_run(slot)
        self.spans.append({
            "name": "preempt", "req": int(req.req_id), "slot": int(slot),
            "t0": float(now), "t1": float(now), "replayed": int(replayed),
        })
        self._preempt_w += 1

    def on_crash(self, now: float) -> None:
        """Engine death under supervised recovery (serve/guard.py):
        seal every open span at the crash instant. The tracer outlives
        the engine generation, so without this the next generation's
        first decode step would extend the dead slots' open runs to
        post-resume timestamps, overlapping the resumed requests' new
        queue spans."""
        self._seen(float(now))
        for slot in sorted(self._open_run):
            self._close_run(slot)  # t1 already stamped at the last step
        for q in self._open_queue.values():
            q["t1"] = float(now)
            self.spans.append(q)
        self._open_queue.clear()

    def on_shed(self, req: Any, now: float, reason: str) -> None:
        """Terminal rejection at admission control (serve/guard.py):
        the request never queued, so its whole lifecycle is this one
        ``shed`` instant."""
        self._seen(float(now))
        self.spans.append({
            "name": "shed", "req": int(req.req_id), "slot": None,
            "t0": float(now), "t1": float(now), "reason": str(reason),
        })
        self._shed_w += 1
        self.requests.append({
            "req": int(req.req_id),
            "status": "rejected",
            "reason": str(reason),
            "tokens": 0,
            "preemptions": 0,
            "recovered": False,
        })

    def on_retire(self, req: Any, slot: int | None, now: float) -> None:
        self._seen(float(now))
        if slot is not None:
            self._close_run(slot)
        q = self._open_queue.pop(req.req_id, None)
        if q is not None:  # finished while queued (budget spent at preempt)
            q["t1"] = float(now)
            self.spans.append(q)
        retire_span: dict[str, Any] = {
            "name": "retire", "req": int(req.req_id),
            "slot": None if slot is None else int(slot),
            "t0": float(now), "t1": float(now),
        }
        status = getattr(req, "status", None)
        if status not in (None, "completed"):
            retire_span["status"] = status
        if status == "timed_out":
            self._timeout_w += 1
        self.spans.append(retire_span)
        self._done_w += 1
        rec: dict[str, Any] = {
            "req": int(req.req_id),
            "tokens": int(req.output_tokens),
            "preemptions": int(req.preemptions),
            "recovered": bool(getattr(req, "recovered", False)),
        }
        if status not in (None, "completed"):
            rec["status"] = status
        if req.first_token_time is not None and req.arrival_time is not None:
            rec["ttft_ms"] = (req.first_token_time - req.arrival_time) * 1e3
        if len(req.token_times) > 1:
            rec["stream_ms"] = (
                req.token_times[-1] - req.token_times[0]
            ) * 1e3
        self.requests.append(rec)

    def sample_ttft(self, ms: float, now: float) -> None:
        self._seen(float(now))
        self._ttft.append(float(ms))

    def sample_itl(self, ms: float, now: float) -> None:
        self._seen(float(now))
        self._itl.append(float(ms))

    # ------------------------------------------------------ SLO windows

    def flush_window(
        self, now: float, *, queue_depth: int = 0
    ) -> dict[str, Any] | None:
        """Emit one flat ``serve_window`` record covering everything
        since the previous flush (rolling percentiles over the full
        reservoirs; counters are per-window). Returns None before any
        recorded activity."""
        if self._t0 is None:
            return None
        if self._last_flush is None:
            self._last_flush = self._t0
        width = max(1e-9, float(now) - self._last_flush)
        pool = self._last_pool
        churn = int(pool.get("churn", self._churn_base or 0))
        trash = int(pool.get("trash", self._trash_base or 0))
        rec: dict[str, Any] = {
            "kind": "serve_window",
            "time": time.time(),
            "t_s": round(float(now) - self._t0, 4),
            "window_s": round(width, 4),
            "ttft_p50_ms": _pct(self._ttft, 50),
            "ttft_p99_ms": _pct(self._ttft, 99),
            "itl_p50_ms": _pct(self._itl, 50),
            "itl_p99_ms": _pct(self._itl, 99),
            "ttft_samples": len(self._ttft),
            "itl_samples": len(self._itl),
            "tokens": self._tokens_w,
            "requests_done": self._done_w,
            "decode_steps": self._steps_w,
            "preemptions": self._preempt_w,
            "preempt_rate_per_s": round(self._preempt_w / width, 3),
            "timed_out": self._timeout_w,
            "shed": self._shed_w,
            "queue_depth": int(queue_depth),
            "queue_depth_max": self._queue_max_w,
            "slot_occupancy": round(
                self._occ_w / (self._steps_w * self.num_slots), 4
            ) if self._steps_w else 0.0,
            "live_pages": int(pool.get("live", 0)),
            "free_pages": int(pool.get("free", 0)),
            "page_high_water": int(pool.get("high_water", 0)),
            "page_churn": churn - (self._churn_base or 0),
            "trash_rows": trash - (self._trash_base or 0),
        }
        for bucket, count in sorted(self._prefill_w.items()):
            rec[f"prefill_bucket_{bucket}"] = count
        self.windows.append(rec)
        self._last_flush = float(now)
        self._churn_base = churn
        self._trash_base = trash
        self._reset_window_counters()
        return rec

    # ---------------------------------------------------------- export

    def all_spans(self) -> list[dict[str, Any]]:
        """Closed spans plus a snapshot of still-open decode runs (their
        ``t1`` tracks the latest step end, so they export valid)."""
        return self.spans + [dict(r) for r in self._open_run.values()]

    def to_chrome_trace(self) -> dict[str, Any]:
        """Chrome trace-event JSON (Perfetto-loadable): pid 1 is the
        engine; tid 0 carries the queue's async spans plus the pool
        counter tracks, tids 1..num_slots are the decode-slot lanes."""
        spans = self.all_spans()
        times = [s["t0"] for s in spans] + [t for t, *_ in self._pool_series]
        origin = min(times) if times else 0.0

        def us(t: float) -> float:
            return round((t - origin) * 1e6, 3)

        events: list[dict[str, Any]] = [
            {"ph": "M", "pid": 1, "tid": 0, "name": "process_name",
             "args": {"name": "graftserve"}},
            {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
             "args": {"name": "queue"}},
        ]
        for s in range(self.num_slots):
            events.append({
                "ph": "M", "pid": 1, "tid": s + 1, "name": "thread_name",
                "args": {"name": f"slot {s}"},
            })
        for sp in spans:
            name = sp["name"]
            if name == "queue":
                # Async (b/e) events: queue waits overlap arbitrarily,
                # which a single lane of X events cannot render.
                events.append({
                    "ph": "b", "cat": "queue", "id": sp["req"], "pid": 1,
                    "tid": 0, "name": "queue", "ts": us(sp["t0"]),
                    "args": {"req": sp["req"]},
                })
                if sp["t1"] is not None:
                    events.append({
                        "ph": "e", "cat": "queue", "id": sp["req"],
                        "pid": 1, "tid": 0, "name": "queue",
                        "ts": us(sp["t1"]),
                    })
            elif name in _INSTANT_KINDS:
                tid = 0 if sp.get("slot") is None else sp["slot"] + 1
                events.append({
                    "ph": "i", "s": "t", "pid": 1, "tid": tid,
                    "name": f"{name} r{sp['req']}", "ts": us(sp["t0"]),
                    "args": {"req": sp["req"]},
                })
            else:
                label = (
                    "decode_run" if name == "decode_run"
                    else f"{name}[bucket={sp.get('bucket')}]"
                )
                args = {
                    k: sp[k]
                    for k in ("req", "bucket", "tokens", "replayed")
                    if sp.get(k) is not None
                }
                events.append({
                    "ph": "X", "pid": 1, "tid": sp["slot"] + 1,
                    "name": label, "ts": us(sp["t0"]),
                    "dur": max(0.001, round((sp["t1"] - sp["t0"]) * 1e6, 3)),
                    "args": args,
                })
        for t, live, free, active, depth in self._pool_series:
            events.append({
                "ph": "C", "pid": 1, "tid": 0, "name": "kv_pages",
                "ts": us(t), "args": {"live": live, "free": free},
            })
            events.append({
                "ph": "C", "pid": 1, "tid": 0, "name": "slots_active",
                "ts": us(t), "args": {"active": active},
            })
            events.append({
                "ph": "C", "pid": 1, "tid": 0, "name": "queue_depth",
                "ts": us(t), "args": {"depth": depth},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def write(self, trace_dir: str) -> dict[str, str]:
        """Write the trace artifacts; returns name -> path."""
        os.makedirs(trace_dir, exist_ok=True)
        paths = {
            "trace": os.path.join(trace_dir, TRACE_NAME),
            "spans": os.path.join(trace_dir, SPANS_NAME),
            "windows": os.path.join(trace_dir, WINDOWS_NAME),
            "requests": os.path.join(trace_dir, REQUESTS_NAME),
        }
        with open(paths["trace"], "w", encoding="utf-8") as f:
            json.dump(self.to_chrome_trace(), f)
        for key, rows in (
            ("spans", self.all_spans()),
            ("windows", self.windows),
            ("requests", self.requests),
        ):
            with open(paths[key], "w", encoding="utf-8") as f:
                for row in rows:
                    f.write(json.dumps(row) + "\n")
        return paths


# ---------------------------------------------------------------------------
# Consistency checks — the CI gate over a written trace
# ---------------------------------------------------------------------------


def check_spans(
    spans: list[dict[str, Any]], *, require_retired: bool = True
) -> list[str]:
    """Structural audit of a span list; returns human-readable problem
    strings (empty = consistent). Checks: every span closed and
    well-ordered, per-request interval spans never overlap, lifecycles
    start with a queue span, every admission span follows a queue span,
    exactly one retire per request (none extends past it), and — with
    ``require_retired`` — no orphans (requests that never retired)."""
    problems: list[str] = []
    by_req: dict[int, list[dict[str, Any]]] = {}
    for sp in spans:
        by_req.setdefault(sp.get("req"), []).append(sp)
    for rid in sorted(by_req, key=lambda r: (r is None, r)):
        sps = by_req[rid]
        for sp in sps:
            if sp.get("t1") is None:
                problems.append(f"req {rid}: unclosed {sp['name']} span")
            elif sp["t1"] < sp["t0"] - 1e-9:
                problems.append(
                    f"req {rid}: {sp['name']} span ends before it starts"
                )
        closed = sorted(
            (s for s in sps
             if s["name"] in _INTERVAL_KINDS and s.get("t1") is not None),
            key=lambda s: (s["t0"], s["t1"]),
        )
        for a, b in zip(closed, closed[1:]):
            if b["t0"] < a["t1"] - 1e-6:
                problems.append(
                    f"req {rid}: {a['name']} and {b['name']} spans overlap"
                )
        if closed and closed[0]["name"] != "queue":
            problems.append(
                f"req {rid}: lifecycle starts with {closed[0]['name']}, "
                "expected queue"
            )
        for i, sp in enumerate(closed):
            if sp["name"] in PREFILL_KINDS and (
                i == 0 or closed[i - 1]["name"] != "queue"
            ):
                problems.append(
                    f"req {rid}: {sp['name']} not preceded by a queue span"
                )
        retires = [s for s in sps if s["name"] == "retire"]
        sheds = [s for s in sps if s["name"] == "shed"]
        if len(retires) > 1:
            problems.append(f"req {rid}: {len(retires)} retire instants")
        if sheds and (retires or closed):
            # Shed happens at admission control, before the request ever
            # queues — a shed lifecycle is exactly one instant.
            problems.append(
                f"req {rid}: shed request has other lifecycle spans"
            )
        if not retires:
            if require_retired and not sheds:
                problems.append(f"req {rid}: never retired (orphan spans)")
        else:
            if closed:
                last_end = max(s["t1"] for s in closed)
                if retires[0]["t0"] < last_end - 1e-6:
                    problems.append(
                        f"req {rid}: spans extend past the retire instant"
                    )
            if (
                not any(s["name"] in PREFILL_KINDS for s in closed)
                and retires[0].get("status") != "timed_out"
            ):
                # A queued-expiry retire legitimately has only a closed
                # queue span: the request never reached a slot.
                problems.append(
                    f"req {rid}: retired without an admission span"
                )
    return problems


def reconcile(
    spans: list[dict[str, Any]],
    requests: list[dict[str, Any]],
    *,
    tol_ms: float = 1.0,
) -> list[str]:
    """Cross-check span arithmetic against the engine-recorded latency
    numbers: per request, (first admission end - first queue start) must
    equal the recorded TTFT, and the post-first-token spans must fit
    inside the recorded token stream. Recovered requests are skipped —
    their preserved stamps belong to the dead process's clock epoch."""
    problems: list[str] = []
    by_req: dict[int, list[dict[str, Any]]] = {}
    for sp in spans:
        if sp["name"] in _INTERVAL_KINDS and sp.get("t1") is not None:
            by_req.setdefault(sp["req"], []).append(sp)
    for rec in requests:
        if rec.get("recovered"):
            continue
        if rec.get("status") in ("rejected", "timed_out"):
            # Shed at admission (no spans at all) or expired before the
            # first token (no admission span / no TTFT) — nothing to
            # reconcile against.
            continue
        rid = rec["req"]
        sps = sorted(by_req.get(rid, []), key=lambda s: s["t0"])
        queues = [s for s in sps if s["name"] == "queue"]
        admits = [s for s in sps if s["name"] in PREFILL_KINDS]
        if not queues or not admits:
            problems.append(f"req {rid}: no queue/admission span to reconcile")
            continue
        ttft = rec.get("ttft_ms")
        if ttft is not None:
            span_ttft = (admits[0]["t1"] - queues[0]["t0"]) * 1e3
            if abs(span_ttft - ttft) > tol_ms:
                problems.append(
                    f"req {rid}: queue+prefill spans sum to "
                    f"{span_ttft:.3f} ms but recorded TTFT is "
                    f"{ttft:.3f} ms"
                )
        stream = rec.get("stream_ms")
        if stream is not None:
            first_end = admits[0]["t1"]
            covered = sum(
                (s["t1"] - max(s["t0"], first_end)) * 1e3
                for s in sps
                if s["t1"] > first_end
            )
            if covered > stream + tol_ms:
                problems.append(
                    f"req {rid}: {covered:.3f} ms of post-first-token "
                    f"spans exceed the {stream:.3f} ms token stream"
                )
    return problems


# ---------------------------------------------------------------------------
# Trace-dir loading + report rendering (obs __main__ serve-report)
# ---------------------------------------------------------------------------


def _load_jsonl(path: str) -> list[dict[str, Any]]:
    rows: list[dict[str, Any]] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict):
                rows.append(row)
    return rows


def load_trace_dir(path: str) -> dict[str, list[dict[str, Any]]]:
    """Load a graftserve trace dir (or a bare spans JSONL) into
    ``{"spans": [...], "windows": [...], "requests": [...]}``."""
    if os.path.isdir(path):
        out = {}
        for key, name in (
            ("spans", SPANS_NAME),
            ("windows", WINDOWS_NAME),
            ("requests", REQUESTS_NAME),
        ):
            p = os.path.join(path, name)
            out[key] = _load_jsonl(p) if os.path.exists(p) else []
        if not out["spans"]:
            raise FileNotFoundError(f"{path}: no {SPANS_NAME}")
        return out
    return {"spans": _load_jsonl(path), "windows": [], "requests": []}


def render_serve_report(data: dict[str, list[dict[str, Any]]]) -> str:
    """One-screen text summary of a loaded trace dir."""
    spans = data.get("spans", [])
    windows = data.get("windows", [])
    requests = data.get("requests", [])
    counts: dict[str, int] = {}
    for sp in spans:
        counts[sp.get("name", "?")] = counts.get(sp.get("name", "?"), 0) + 1
    rows = [
        ("spans", str(len(spans))),
        ("span kinds", ", ".join(
            f"{k}={v}" for k, v in sorted(counts.items())
        ) or "-"),
        ("requests", str(len({s.get("req") for s in spans}))),
        ("retired", str(counts.get("retire", 0))),
        ("shed", str(counts.get("shed", 0))),
        ("timed out", str(sum(
            1 for r in requests if r.get("status") == "timed_out"
        ))),
        ("recovered", str(sum(1 for r in requests if r.get("recovered")))),
        ("windows", str(len(windows))),
    ]
    if windows:
        last = windows[-1]
        rows.append(("ttft p99 (last window)",
                     f"{last.get('ttft_p99_ms')} ms"))
        rows.append(("itl p99 (last window)",
                     f"{last.get('itl_p99_ms')} ms"))
        rows.append(("live pages (peak)", str(max(
            (w.get("live_pages", 0) for w in windows), default=0
        ))))
        rows.append(("queue depth (max)", str(max(
            (w.get("queue_depth_max", 0) for w in windows), default=0
        ))))
    width = max(len(name) for name, _ in rows)
    return "\n".join(f"{name:<{width}}  {val}" for name, val in rows)


# ---------------------------------------------------------------------------
# Program profile: device time + cost analysis for the programs
# ---------------------------------------------------------------------------


def profile_serve_programs(
    engine: Any, *, iters: int = 3
) -> list[dict[str, Any]]:
    """Attribute device time, compiled flops/bytes, and roofline class
    to the engine's decode step and every warmed prefill bucket.

    Run this AFTER the serving run (it re-executes the programs under a
    profiler trace and AOT-compiles for ``cost_analysis`` — both would
    pollute a CompileCounter-gated section). The engine's programs
    donate their pages argument, so each profiled run works on a fresh
    copy of the pools and rebinds between calls — the live engine state
    is never consumed.

    Returns flat ``kind:"serve_phase"`` records (one per program) plus
    one ``kind:"serve_phase_summary"`` carrying
    ``decode_host_exposed_ms``: mean host wall per LIVE decode step
    (engine-recorded) minus the profiled program time — the host
    scheduling overhead a decode token actually pays.
    """
    import jax
    import jax.numpy as jnp

    from ..utils.profiling import capture_device_profile, compiled_costs
    from .flops import roofline_classify

    device_kind = getattr(jax.devices()[0], "device_kind", None)

    def fresh_pages():
        # x + 0 allocates a new buffer with the same sharding — the
        # programs donate their pages argument, so profiling must not
        # hand them the engine's live pools.
        return jax.tree.map(lambda x: x + 0, engine._pages)

    # A ServeChaosMonkey wraps _decode_step in a plain function; unwrap
    # to the jitted original — for .lower(), and so profiling re-runs
    # never advance the monkey's fault counter. (A jitted function has a
    # ``__wrapped__`` of its own, the plain Python function: stop at the
    # first that can lower.)
    decode_step = engine._decode_step
    while not hasattr(decode_step, "lower"):
        decode_step = decode_step.__wrapped__

    key = engine._sample_root
    dec_args = (jnp.asarray(engine._probe_decode_arg()), key)

    def _runner(fn, args, state):
        def run():
            state["pages"], out = fn(engine.params, state["pages"], *args)
            return out
        return run

    records: list[dict[str, Any]] = []
    dec_state = {"pages": fresh_pages()}
    prof = capture_device_profile(
        _runner(decode_step, dec_args, dec_state), iters=iters
    )
    costs = compiled_costs(
        decode_step.lower(
            engine.params, dec_state["pages"], *dec_args
        ).compile()
    )
    records.append({
        "kind": "serve_phase",
        "time": time.time(),
        "phase": "decode",
        "impl": engine.paged_attention_impl,
        "clock": prof.clock,
        "device_ms": round(prof.device_ms, 4),
        "wall_ms": round(prof.wall_ms, 4),
        "flops": costs["flops"],
        "bytes_accessed": costs["bytes_accessed"],
        "roofline": roofline_classify(
            costs["flops"], costs["bytes_accessed"], device_kind
        ),
        "iters": iters,
    })
    decode_ms = prof.best_ms()
    for bucket in sorted(engine._prefill_cache):
        fn = engine._prefill_cache[bucket]
        plen = min(bucket, engine.max_seq_len - 1)
        pf_args = (
            engine._pack_program_arg(
                bucket, np.ones((plen,), np.int32), (plen, 0, 0), []
            ),
            key,
        )
        state = {"pages": fresh_pages()}
        prof_b = capture_device_profile(
            _runner(fn, pf_args, state), iters=iters
        )
        costs_b = compiled_costs(
            fn.lower(engine.params, state["pages"], *pf_args).compile()
        )
        records.append({
            "kind": "serve_phase",
            "time": time.time(),
            "phase": f"prefill[bucket={bucket}]",
            "impl": engine.paged_attention_impl,
            "bucket": bucket,
            "clock": prof_b.clock,
            "device_ms": round(prof_b.device_ms, 4),
            "wall_ms": round(prof_b.wall_ms, 4),
            "flops": costs_b["flops"],
            "bytes_accessed": costs_b["bytes_accessed"],
            "roofline": roofline_classify(
                costs_b["flops"], costs_b["bytes_accessed"], device_kind
            ),
            "iters": iters,
        })
    walls = [float(w) for w in engine._decode_walls]
    summary: dict[str, Any] = {
        "kind": "serve_phase_summary",
        "time": time.time(),
        "impl": engine.paged_attention_impl,
        "decode_step_ms": round(decode_ms, 4),
        "decode_clock": prof.clock,
        "decode_steps_observed": len(walls),
    }
    if walls:
        mean_wall_ms = sum(walls) / len(walls) * 1e3
        summary["decode_host_ms"] = round(mean_wall_ms, 4)
        summary["decode_host_exposed_ms"] = round(
            max(0.0, mean_wall_ms - decode_ms), 4
        )
    records.append(summary)
    return records
