"""graftserve (obs/serve_trace.py): the serving observability contracts.

What this file pins, in dependency order:

1. **Spans are consistent.** Every request's lifecycle closes — queue ->
   admission -> decode_run -> retire — with no orphan, unclosed, or
   overlapping spans, INCLUDING under LIFO recompute preemption and
   kill/resume replay (the two paths that re-open queue spans and
   re-admit under a different kind).
2. **Span arithmetic reconciles with the recorded metrics.** The tracer
   stores the engine's own clock stamps, so queue+prefill span sums
   equal the recorded TTFT exactly — ``reconcile`` is the CI gate's
   second half.
3. **The Chrome/Perfetto export is structurally valid.** X events carry
   durations on slot lanes, queue waits are paired async b/e events,
   counter tracks sample the pool.
4. **Windowed SLO percentiles agree with the post-hoc summary.** The
   tracer's reservoirs are fed the same floats ``loadgen._summarize``
   diffs, so the final window's p50/p99 match the ``serve_summary``.
5. **Tracing is free.** The decode CompileCounter stays at zero
   post-warmup with the tracer attached (GL002 stays executable), and
   ``profile_serve_programs`` — which DOES compile — leaves the live
   engine's state intact despite the donated pages argument.

Plus the serve-report CLI exit codes, the flight-recorder serve tail,
and the metrics_summary serve_window/serve_phase rows.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu.models import TransformerLM
from cs744_pytorch_distributed_tutorial_tpu.obs.serve_trace import (
    PREFILL_KINDS,
    ServeTracer,
    check_spans,
    load_trace_dir,
    profile_serve_programs,
    reconcile,
)
from cs744_pytorch_distributed_tutorial_tpu.serve import (
    Request,
    ServeConfig,
    ServingEngine,
    make_poisson_workload,
    run_poisson,
)

VOCAB = 61


class _ListSink:
    def __init__(self):
        self.records = []

    def emit(self, record):
        self.records.append(dict(record))


@pytest.fixture(scope="module")
def tiny_lm():
    model = TransformerLM(
        vocab_size=VOCAB,
        num_layers=2,
        num_heads=2,
        d_model=32,
        d_ff=64,
        max_seq_len=64,
        attention_impl="dense",
        use_rope=True,
    )
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def _submit(eng, cases, data_seed=13):
    rng = np.random.default_rng(data_seed)
    return [
        eng.submit(Request(
            prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
            max_new_tokens=budget,
        ))
        for plen, budget in cases
    ]


# Pool sized to force LIFO recompute preemption (mirrors
# test_engine_preemption_completes_everything).
TIGHT = dict(num_slots=3, page_size=4, num_pages=9, max_pages_per_slot=7)
TIGHT_CASES = [(6, 18), (10, 14), (8, 16), (5, 20), (12, 12)]


def test_spans_close_and_reconcile_under_preemption(tiny_lm):
    """A preemption-heavy run produces a fully consistent span set whose
    queue+prefill sums reconcile with the engine-recorded TTFTs — the
    exact audit CI's serve-smoke gate runs."""
    model, params = tiny_lm
    tracer = ServeTracer(TIGHT["num_slots"])
    eng = ServingEngine(
        model, params, ServeConfig(**TIGHT), tracer=tracer
    )
    _submit(eng, TIGHT_CASES)
    eng.run()
    assert eng.stats()["preemptions"] > 0, "pool was not tight enough"

    spans = tracer.all_spans()
    assert check_spans(spans) == []
    assert reconcile(spans, tracer.requests) == []
    names = {s["name"] for s in spans}
    assert "recompute" in names  # preemptions re-admit under a new kind
    preempts = [s for s in spans if s["name"] == "preempt"]
    assert len(preempts) == eng.stats()["preemptions"]
    retires = [s for s in spans if s["name"] == "retire"]
    assert len(retires) == len(TIGHT_CASES)
    assert len(tracer.requests) == len(TIGHT_CASES)
    # queue and admission tile exactly: same float at the boundary
    by_req = {}
    for s in spans:
        by_req.setdefault(s["req"], []).append(s)
    for rid, sps in by_req.items():
        queues = sorted(
            (s for s in sps if s["name"] == "queue"), key=lambda s: s["t0"]
        )
        admits = sorted(
            (s for s in sps if s["name"] in PREFILL_KINDS),
            key=lambda s: s["t0"],
        )
        assert len(queues) == len(admits), rid
        for q, a in zip(queues, admits):
            assert q["t1"] == a["t0"], rid


@pytest.mark.slow  # serve-smoke CI runs this file without the tier-1 filter
def test_spans_close_across_kill_resume(tiny_lm):
    """Kill mid-decode, resume on a fresh engine with its own tracer:
    the fresh timeline is consistent, in-flight requests re-admit as
    resume-replay spans with the replayed token count, and their request
    records carry the recovered flag (reconcile skips them — their
    arrival stamps belong to the dead process's clock epoch)."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=33,
                      max_pages_per_slot=8, seed=3)
    victim = ServingEngine(model, params, cfg)
    _submit(victim, [(3, 9), (7, 4), (12, 11), (5, 17)], data_seed=7)
    for _ in range(5):
        victim.step()
    assert victim.busy
    snap = victim.snapshot()
    in_flight = sum(1 for rec in snap.requests if rec["in_flight"])
    assert in_flight > 0
    del victim

    tracer = ServeTracer(cfg.num_slots)
    fresh = ServingEngine(model, params, cfg, tracer=tracer)
    fresh.resume(snap)
    fresh.run()

    spans = tracer.all_spans()
    assert check_spans(spans) == []
    assert reconcile(spans, tracer.requests) == []
    replays = [s for s in spans if s["name"] == "resume-replay"]
    assert len(replays) == in_flight
    assert all(s.get("replayed", 0) > 0 for s in replays)
    recovered = [r for r in tracer.requests if r["recovered"]]
    assert len(recovered) == len(snap.requests)


def test_tracer_rejects_mismatched_slot_count(tiny_lm):
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=8)
    with pytest.raises(ValueError, match="slots"):
        ServingEngine(model, params, cfg, tracer=ServeTracer(4))


def test_chrome_trace_is_structurally_valid(tiny_lm):
    """The export is JSON-serializable trace-event format: slot-lane X
    events with durations, paired async b/e queue events, instants,
    metadata naming every lane, and pool counter samples."""
    model, params = tiny_lm
    tracer = ServeTracer(TIGHT["num_slots"])
    eng = ServingEngine(
        model, params, ServeConfig(**TIGHT), tracer=tracer
    )
    _submit(eng, TIGHT_CASES)
    eng.run()

    trace = json.loads(json.dumps(tracer.to_chrome_trace()))
    events = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"

    meta = [e for e in events if e["ph"] == "M"]
    lane_names = {e["args"]["name"] for e in meta
                  if e["name"] == "thread_name"}
    assert "queue" in lane_names
    for s in range(TIGHT["num_slots"]):
        assert f"slot {s}" in lane_names

    xs = [e for e in events if e["ph"] == "X"]
    assert xs
    for e in xs:
        assert e["dur"] > 0
        assert 1 <= e["tid"] <= TIGHT["num_slots"]
        assert e["ts"] >= 0

    begins = [e for e in events if e["ph"] == "b"]
    ends = [e for e in events if e["ph"] == "e"]
    assert begins and sorted(e["id"] for e in begins) == sorted(
        e["id"] for e in ends
    )

    counters = {e["name"] for e in events if e["ph"] == "C"}
    assert {"kv_pages", "slots_active", "queue_depth"} <= counters
    instants = [e for e in events if e["ph"] == "i"]
    assert any(e["name"].startswith("retire") for e in instants)
    assert any(e["name"].startswith("preempt") for e in instants)


def test_windowed_percentiles_match_posthoc_summary(tiny_lm):
    """The tracer's TTFT/ITL reservoirs are fed the same floats
    ``loadgen._summarize`` percentiles, with the same resume-boundary
    exclusion — so the final flushed window agrees with the post-hoc
    serve_summary record."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=4, page_size=4, num_pages=33,
                      max_pages_per_slot=8)
    tracer = ServeTracer(cfg.num_slots, window_every_s=0.05)
    sink = _ListSink()
    eng = ServingEngine(model, params, cfg, sink=sink, tracer=tracer)
    wl = make_poisson_workload(
        num_requests=12, rate_rps=200.0, prompt_len=(3, 10),
        output_len=(4, 12), vocab_size=VOCAB, seed=5,
    )
    summary = run_poisson(eng, wl, sink=sink)

    assert tracer.windows, "no serve_window flushed"
    last = tracer.windows[-1]
    assert last["ttft_samples"] == len(wl)
    assert last["ttft_p50_ms"] == pytest.approx(
        summary["ttft_p50_ms"], abs=0.01
    )
    assert last["ttft_p99_ms"] == pytest.approx(
        summary["ttft_p99_ms"], abs=0.01
    )
    assert last["itl_p50_ms"] == pytest.approx(
        summary["itl_p50_ms"], abs=0.01
    )
    assert last["itl_p99_ms"] == pytest.approx(
        summary["itl_p99_ms"], abs=0.01
    )
    # the window stream reached the sink (flat records, sink-safe)
    emitted = [r for r in sink.records if r.get("kind") == "serve_window"]
    assert len(emitted) == len(tracer.windows)
    for rec in emitted:
        for v in rec.values():
            assert v is None or isinstance(v, (bool, int, float, str))
    # cadence: every window but the final drain flush spans >= the
    # configured interval
    for w in tracer.windows[:-1]:
        assert w["window_s"] >= tracer.window_every_s
    # per-bucket admission counts total one per admission (first
    # prefill per request + one recompute per preemption)
    admits = sum(
        v for w in tracer.windows for k, v in w.items()
        if k.startswith("prefill_bucket_")
    )
    assert admits == len(wl) + summary["preemptions"]


def test_zero_retraces_with_tracing_on(tiny_lm):
    """The tracer is pure host-side bookkeeping: the decode step still
    never recompiles across slot churn once warm (the GL002 contract
    must survive observability)."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.system import (
        CompileCounter,
    )

    model, params = tiny_lm
    cfg = ServeConfig(num_slots=3, page_size=4, num_pages=33,
                      max_pages_per_slot=8)
    tracer = ServeTracer(cfg.num_slots, window_every_s=0.01)
    eng = ServingEngine(
        model, params, cfg, sink=_ListSink(), tracer=tracer
    )
    rng = np.random.default_rng(11)

    def burst(sizes):
        for plen, budget in sizes:
            eng.submit(Request(
                prompt=rng.integers(1, VOCAB, size=plen).astype(np.int32),
                max_new_tokens=budget,
            ))
        eng.run()

    burst([(4, 3), (8, 5)])  # warmup: compiles prefill buckets + decode
    cc = CompileCounter()
    burst([(3, 8), (6, 2), (8, 7), (5, 3), (7, 12), (4, 2)])
    assert cc.count == 0, f"{cc.count} retraces with tracing on"
    assert check_spans(tracer.all_spans(), require_retired=False) == []


def test_check_spans_catches_synthetic_corruption():
    """The audit actually fires: unclosed spans, overlaps, missing
    queue provenance, orphans, and double retires all surface."""
    ok = [
        {"name": "queue", "req": 1, "slot": None, "t0": 0.0, "t1": 1.0},
        {"name": "prefill", "req": 1, "slot": 0, "bucket": 8,
         "t0": 1.0, "t1": 2.0},
        {"name": "decode_run", "req": 1, "slot": 0, "t0": 2.0, "t1": 3.0,
         "tokens": 4},
        {"name": "retire", "req": 1, "slot": 0, "t0": 3.0, "t1": 3.0},
    ]
    assert check_spans(ok) == []

    unclosed = [dict(ok[0], t1=None)] + ok[1:]
    assert any("unclosed" in p for p in check_spans(unclosed))

    overlap = ok[:2] + [
        {"name": "decode_run", "req": 1, "slot": 0, "t0": 1.5, "t1": 3.0,
         "tokens": 4},
        ok[3],
    ]
    assert any("overlap" in p for p in check_spans(overlap))

    no_queue = ok[1:]
    problems = check_spans(no_queue)
    assert any("queue" in p for p in problems)

    orphan = ok[:3]
    assert any("never retired" in p for p in check_spans(orphan))
    assert check_spans(orphan, require_retired=False) == []

    twice = ok + [dict(ok[3])]
    assert any("retire instants" in p for p in check_spans(twice))

    backwards = [dict(ok[0], t0=1.0, t1=0.0)] + ok[1:]
    assert any("ends before" in p for p in check_spans(backwards))


def test_reconcile_catches_ttft_drift():
    spans = [
        {"name": "queue", "req": 0, "slot": None, "t0": 0.0, "t1": 0.010},
        {"name": "prefill", "req": 0, "slot": 0, "bucket": 8,
         "t0": 0.010, "t1": 0.020},
    ]
    good = [{"req": 0, "tokens": 4, "preemptions": 0, "recovered": False,
             "ttft_ms": 20.0}]
    assert reconcile(spans, good) == []
    drifted = [dict(good[0], ttft_ms=35.0)]
    assert any("TTFT" in p for p in reconcile(spans, drifted))
    # recovered requests are exempt: cross-epoch stamps can't reconcile
    assert reconcile(spans, [dict(drifted[0], recovered=True)]) == []


@pytest.mark.slow  # serve-smoke CI runs this file without the tier-1 filter
def test_profile_serve_programs_attributes_and_preserves_state(tiny_lm):
    """Serve-side graftscope: one serve_phase record per program with
    flops/bytes/roofline, a summary with decode_host_exposed_ms, and —
    despite the donated pages argument — the live engine still serves
    correctly afterwards."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=8)
    eng = ServingEngine(model, params, cfg)
    reqs = _submit(eng, [(4, 6), (9, 5)], data_seed=17)
    eng.run()
    expect = [list(r.generated) for r in reqs]

    records = profile_serve_programs(eng, iters=2)
    phases = [r for r in records if r["kind"] == "serve_phase"]
    names = {r["phase"] for r in phases}
    assert "decode" in names
    assert names == {"decode"} | {
        f"prefill[bucket={b}]" for b in eng._prefill_cache
    }
    for r in phases:
        assert r["flops"] is None or r["flops"] >= 0
        assert r["clock"] in ("device", "wall")
        assert r["wall_ms"] > 0
        assert r["roofline"] in ("compute", "memory", "comms", "unknown")
    summaries = [r for r in records if r["kind"] == "serve_phase_summary"]
    assert len(summaries) == 1
    s = summaries[0]
    assert s["decode_steps_observed"] > 0
    assert s["decode_host_exposed_ms"] >= 0
    assert s["decode_host_ms"] >= s["decode_host_exposed_ms"]

    # donation safety: the profiled copies absorbed the donations; the
    # engine's own pools still produce identical streams
    again = _submit(eng, [(4, 6), (9, 5)], data_seed=17)
    eng.run()
    assert [list(r.generated) for r in again] == expect


def test_write_and_serve_report_cli(tiny_lm, tmp_path, capsys):
    """tracer.write() + the obs serve-report subcommand: a clean trace
    passes --check (exit 0); a corrupted span file fails (exit 1)."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.__main__ import main

    model, params = tiny_lm
    tracer = ServeTracer(TIGHT["num_slots"], window_every_s=0.01)
    eng = ServingEngine(
        model, params, ServeConfig(**TIGHT), sink=_ListSink(),
        tracer=tracer,
    )
    _submit(eng, TIGHT_CASES)
    eng.run()
    eng.finalize_trace()
    good = tmp_path / "trace"
    paths = tracer.write(str(good))
    with open(paths["trace"], encoding="utf-8") as f:
        assert json.load(f)["traceEvents"]

    data = load_trace_dir(str(good))
    assert data["spans"] and data["requests"] and data["windows"]
    assert main(["serve-report", str(good), "--check"]) == 0
    out = capsys.readouterr().out
    assert "serve-trace check: OK" in out
    assert "span kinds" in out

    # corrupt: drop every retire span -> orphan lifecycles
    spans_file = good / "serve_spans.jsonl"
    rows = [json.loads(line) for line in
            spans_file.read_text().splitlines() if line.strip()]
    spans_file.write_text("\n".join(
        json.dumps(r) for r in rows if r["name"] != "retire"
    ) + "\n")
    assert main(["serve-report", str(good), "--check"]) == 1
    assert "never retired" in capsys.readouterr().err

    with pytest.raises(FileNotFoundError):
        load_trace_dir(str(tmp_path / "empty"))


def test_flight_recorder_dumps_serve_tail(tiny_lm):
    """make_flight_recorder(): a dump carries the scheduler header
    (queue depth, pool counters) and replays the serve event ring as
    flight_serve records through the engine's own sink."""
    model, params = tiny_lm
    sink = _ListSink()
    cfg = ServeConfig(**TIGHT)
    eng = ServingEngine(model, params, cfg, sink=sink)
    _submit(eng, TIGHT_CASES)
    eng.run()
    fr = eng.make_flight_recorder(hbm=False)
    fr.dump("test")

    dumps = [r for r in sink.records
             if r.get("kind") == "event" and r.get("event") == "flight_dump"]
    assert len(dumps) == 1
    header = dumps[0]
    assert header["reason"] == "test"
    assert header["queue_depth"] == 0
    assert header["preemptions"] == eng.stats()["preemptions"]
    assert header["page_high_water"] == eng.pool.high_water
    assert header["page_churn"] > 0
    assert header["trash_rows_written"] > 0
    tails = [r for r in sink.records if r.get("event") == "flight_serve"]
    assert tails
    # ring records re-keyed: engine "event" -> "serve_event", no "kind"
    # collision with the wrapper
    assert all("serve_event" in r for r in tails)
    assert any(r["serve_event"] == "request" for r in tails)


def test_pool_counts_churn(tiny_lm):
    """PagePool cumulative alloc/free counters feed page_churn; a
    drained run's allocs equal its frees."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=2, page_size=4, num_pages=17,
                      max_pages_per_slot=8)
    eng = ServingEngine(model, params, cfg)
    _submit(eng, [(4, 6), (9, 8), (6, 10)], data_seed=17)
    eng.run()
    assert eng.pool.total_allocs > 0
    assert eng.pool.total_allocs == eng.pool.total_frees
    stats = eng.stats()
    assert stats["page_churn"] == (
        eng.pool.total_allocs + eng.pool.total_frees
    )
    assert stats["trash_rows_written"] == eng._trash_rows > 0


@pytest.mark.parametrize("chunk", [None, 8], ids=["one-shot", "chunked"])
def test_commit_counts_trash_and_pages(tiny_lm, chunk):
    """The commit's counters on known buckets. One slot, so every decode
    step is full and writes no trash row: ``trash_rows_written`` is the
    prompts' alone. A one-shot commit writes ceil(bucket / page_size)
    whole pages (``commit_pages``), the slot's and the rest to trash:
    prompts 3, 9, 6 at pages of 4 are buckets 8, 16, 8, so 2 + 4 + 2
    pages, 1 + 3 + 2 of them the slot's, 4 * (1 + 1 + 0) trash rows. A
    chunked prompt writes rows, and only its padding past its pages is
    trash: chunks of 8 write 8, 16, 8 rows into 4, 12, 8 rows of pages,
    and no commit page."""
    model, params = tiny_lm
    cfg = ServeConfig(num_slots=1, page_size=4, num_pages=17,
                      max_pages_per_slot=8, prefill_chunk=chunk)
    eng = ServingEngine(model, params, cfg)
    _submit(eng, [(3, 4), (9, 3), (6, 5)], data_seed=19)
    eng.run()
    stats = eng.stats()
    assert stats["admissions"] == 3 and stats["preemptions"] == 0
    assert stats["trash_rows_written"] == 8
    assert stats["commit_pages"] == (0 if chunk else 8)


def test_metrics_summary_renders_serve_window_rows(tmp_path, capsys):
    """summarize() aggregates serve_window records and serve_phase rows
    next to the existing serve rows, and main() renders them."""
    import importlib.util
    from pathlib import Path

    spec = importlib.util.spec_from_file_location(
        "metrics_summary",
        Path(__file__).resolve().parents[1]
        / "benchmarks" / "metrics_summary.py",
    )
    ms = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ms)

    records = [
        {"kind": "serve_window", "t_s": 0.25, "window_s": 0.25,
         "ttft_p99_ms": 12.0, "itl_p99_ms": 4.0, "live_pages": 30,
         "queue_depth_max": 5, "preempt_rate_per_s": 8.0},
        {"kind": "serve_window", "t_s": 0.5, "window_s": 0.25,
         "ttft_p99_ms": 9.0, "itl_p99_ms": 3.0, "live_pages": 12,
         "queue_depth_max": 1, "preempt_rate_per_s": 0.0},
        {"kind": "serve_phase", "phase": "decode", "clock": "wall",
         "wall_ms": 1.5, "flops": 1e6, "bytes_accessed": 2e6,
         "roofline": "memory"},
        {"kind": "serve_phase_summary", "decode_host_exposed_ms": 0.4},
    ]
    summary = ms.summarize(records)
    sw = summary["serve_windows"]
    assert sw["count"] == 2
    assert sw["span_s"] == 0.5
    assert sw["ttft_p99_ms_last"] == 9.0
    assert sw["ttft_p99_ms_max"] == 12.0
    assert sw["itl_p99_ms_last"] == 3.0
    assert sw["live_pages_peak"] == 30
    assert sw["queue_depth_max"] == 5
    assert sw["preempt_rate_per_s_max"] == 8.0
    assert summary["serve_decode_host_exposed_ms"] == 0.4
    assert summary["phases"]["serve decode"]["ms"] == 1.5

    path = tmp_path / "metrics.jsonl"
    path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
    assert ms.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "serve windows" in out
    assert "serve decode host exposed" in out
    assert "phase serve decode" in out

    # absent records -> no rows, no crash
    empty = ms.summarize([{"kind": "step", "loss": 1.0}])
    assert empty["serve_windows"] is None
    assert empty["serve_decode_host_exposed_ms"] is None
