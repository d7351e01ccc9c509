"""CLI for the continuous-batching serving engine (serve/).

Runs the Poisson load benchmark against a paged-KV ``ServingEngine``
and (optionally) the batch-at-a-time baseline at equal HBM budget,
emitting ``kind:"serve"`` / ``kind:"serve_summary"`` records to stdout
and ``--metrics-dir`` (docs/serving.md):

    # engine vs batch-at-a-time generate, equal KV-token budget,
    # exit 1 unless the engine wins p99 TTFT AND tokens/sec:
    python -m cs744_pytorch_distributed_tutorial_tpu.serve_cli \
        --requests 32 --rate 16 --compare-baseline --gate

    # greedy paged-vs-dense parity audit over the workload's prompts:
    python -m cs744_pytorch_distributed_tutorial_tpu.serve_cli \
        --requests 8 --parity-check

    # graftserve: Perfetto span timeline + windowed SLO records +
    # device-time attribution of the decode/prefill programs
    # (docs/observability.md; obs serve-report renders/checks it):
    python -m cs744_pytorch_distributed_tutorial_tpu.serve_cli \
        --requests 24 --trace-dir /tmp/serve_trace --window-every 0.25

    # graftguard: overload the engine 3x past sustainable, shed at the
    # door, expire stale requests, and ride out injected decode faults
    # under the supervised restart ladder (docs/reliability.md):
    python -m cs744_pytorch_distributed_tutorial_tpu.serve_cli \
        --requests 64 --rate 48 --deadline-s 30 --max-queue-depth 16 \
        --shed-policy degrade --chaos 40:decode_nan,90:engine_crash

Params are randomly initialized — serving latency/throughput and the
parity contract are weight-independent, so the CLI does not train.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="cs744-tpu-serve",
        description="Continuous-batching LM serving: Poisson load benchmark",
    )
    # model (decode-configured TransformerLM, random params)
    p.add_argument("--vocab-size", type=int, default=256)
    p.add_argument("--num-layers", type=int, default=4)
    p.add_argument("--num-heads", type=int, default=8)
    p.add_argument("--num-kv-heads", type=int, default=None)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--d-ff", type=int, default=1024)
    p.add_argument("--max-seq-len", type=int, default=512)
    p.add_argument("--use-rope", action="store_true")
    p.add_argument("--quant-kv", action="store_true",
                   help="int8 KV pages (ops/quant.py::quantize_kv)")
    p.add_argument("--model-config", "--keye-config", dest="model_config",
                   default=None, metavar="CONFIG.JSON",
                   help="build the model from a published config.json, the "
                        "builder picked by its model_type (models/"
                        "hf_interop.py::model_config_from_hf: KeyeVL2 = gated "
                        "experts, q/k norm, the sparse-attention indexer; "
                        "mellum = gated experts, q/k norm, sliding-window "
                        "and full layers mixed, YaRN on the full ones; "
                        "longcat_flash = latent attention (MLA) over one "
                        "paged latent pool a sublayer, the two-attention "
                        "shortcut-MoE layer, zero-compute experts, a chip's "
                        "share of the routed ones; deepseek_v2 = latent "
                        "attention with YaRN, one pool a layer, a leading "
                        "dense layer, group-limited routing and shared "
                        "experts; minicpm_sala = lightning-attention layers "
                        "with a state row a slot beside block-sparse layers "
                        "over paged K and V and compressed keys) in "
                        "place of the size flags above; --max-seq-len still "
                        "caps a request. Random params, as ever; no "
                        "--parity-check (the dense-cache generator has no "
                        "indexer, no window and no latent); window, latent, "
                        "lightning and block-sparse layers need "
                        "--prefill-chunk")
    p.add_argument("--held-experts", type=int, default=None, metavar="N",
                   help="with --model-config longcat_flash or "
                        "deepseek_v2: serve one "
                        "chip's share of the experts, routed experts "
                        "0..N-1 of the config's n_routed_experts; the "
                        "router keeps its width, the other routed "
                        "experts' terms are left out (docs/serving.md)")
    # engine geometry
    p.add_argument("--num-slots", type=int, default=8,
                   help="decode slots B in the fixed-shape jitted step")
    p.add_argument("--page-size", type=int, default=16,
                   help="tokens per KV page")
    p.add_argument("--num-pages", type=int, default=64,
                   help="pool pages per layer (page 0 reserved as trash)")
    p.add_argument("--max-pages-per-slot", type=int, default=16,
                   help="page-table width P: caps one request's KV")
    p.add_argument("--prefill-chunk", type=int, default=None,
                   help="prefill by chunks of this many tokens through "
                        "one compiled program (docs/serving.md); default: "
                        "one pass a prompt, a program a length bucket")
    p.add_argument("--paged-attention-impl", default="auto",
                   choices=("auto", "gather", "kernel"),
                   help="decode attention: Pallas live-pages kernel or "
                        "the gather+einsum reference (auto: kernel on "
                        "TPU, gather elsewhere)")
    # sampling
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--top-p", type=float, default=None)
    p.add_argument("--eos-id", type=int, default=None)
    # workload
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--rate", type=float, default=16.0,
                   help="Poisson arrival rate, requests/sec")
    p.add_argument("--prompt-len", type=int, nargs=2, default=(8, 48),
                   metavar=("MIN", "MAX"))
    p.add_argument("--output-len", type=int, nargs=2, default=(8, 64),
                   metavar=("MIN", "MAX"))
    p.add_argument("--seed", type=int, default=0)
    # modes
    p.add_argument("--compare-baseline", action="store_true",
                   help="also replay through batch-at-a-time generate at "
                        "EQUAL KV HBM (batch = pool tokens / max_seq_len)")
    p.add_argument("--gate", action="store_true",
                   help="exit 1 unless the engine beats the baseline on "
                        "both aggregate tokens/sec and p99 TTFT "
                        "(implies --compare-baseline)")
    p.add_argument("--parity-check", action="store_true",
                   help="greedy engine output must match make_generator "
                        "token-for-token on every workload prompt; exit 1 "
                        "on any mismatch")
    p.add_argument("--metrics-dir", default=None,
                   help="also write records to METRICS_DIR/metrics.jsonl")
    # graftserve observability (obs/serve_trace.py, docs/observability.md)
    p.add_argument("--trace-dir", default=None,
                   help="write graftserve artifacts here: the Perfetto "
                        "trace (serve_trace.json), span/window/request "
                        "JSONL, and serve_phases.json (device-time + "
                        "roofline attribution of the decode/prefill "
                        "programs)")
    p.add_argument("--window-every", type=float, default=None, metavar="S",
                   help="emit kind:'serve_window' SLO records every S "
                        "seconds of the measured run (rolling TTFT/ITL "
                        "p50/p99, queue depth, preemption rate, pool "
                        "counters); defaults to 0.25 when --trace-dir "
                        "is set")
    p.add_argument("--profile-dir", default=None,
                   help="capture the JAX profiler's trace of the serving "
                        "run here (view in TensorBoard profile / "
                        "ui.perfetto.dev): the engine's serve/* phase "
                        "spans beside the device's lines, on one clock")
    # graftguard: deadlines + admission control (serve/guard.py);
    # setting any of these attaches a ServeGuard to the engine
    p.add_argument("--deadline-s", type=float, default=None,
                   help="default end-to-end deadline per request; "
                        "expiry retires it as timed_out and frees its "
                        "pages")
    p.add_argument("--max-queue-s", type=float, default=None,
                   help="max time a request may wait for its FIRST "
                        "token while queued")
    p.add_argument("--max-queue-depth", type=int, default=None,
                   help="bounded admission queue: arrivals beyond this "
                        "depth are shed at the door")
    p.add_argument("--shed-policy", default=None,
                   choices=("reject", "degrade"),
                   help="overload response: reject new arrivals, or "
                        "degrade (trim max_new_tokens to the floor "
                        "under page-pool pressure; outputs stay oracle "
                        "prefixes)")
    p.add_argument("--degrade-floor", type=int, default=8,
                   help="min max_new_tokens a degrade trim leaves")
    # chaos + supervised auto-recovery (utils/chaos.py, serve/guard.py)
    p.add_argument("--chaos", default=None, metavar="IDX:KIND,...",
                   help="inject serve faults at measured decode-step "
                        "indices, e.g. '40:decode_nan,90:engine_crash'; "
                        "kinds: decode_nan, slow_step, engine_crash. "
                        "Implies the supervised recovery loop")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="engine restarts before recovery gives up")
    p.add_argument("--restart-backoff-s", type=float, default=0.0,
                   help="base exponential-backoff delay between "
                        "restarts")
    p.add_argument("--step-timeout-s", type=float, default=None,
                   help="watchdog deadline per decode step: a hung "
                        "step escalates warn -> flight dump -> engine "
                        "restart. Implies the supervised recovery loop")
    return p


def _parse_chaos(spec: str) -> dict[int, str]:
    """``"40:decode_nan,90:engine_crash"`` -> ``{40: ..., 90: ...}``."""
    faults: dict[int, str] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        idx, sep, kind = part.partition(":")
        if not sep:
            raise ValueError(f"chaos spec {part!r} is not IDX:KIND")
        faults[int(idx)] = kind
    return faults


def _make_sink(metrics_dir: str | None):
    from cs744_pytorch_distributed_tutorial_tpu.obs.sinks import (
        JsonlSink,
        MultiSink,
        StreamSink,
    )

    sinks = [StreamSink(sys.stdout)]
    if metrics_dir:
        import os

        os.makedirs(metrics_dir, exist_ok=True)
        sinks.append(JsonlSink(os.path.join(metrics_dir, "metrics.jsonl")))
    return MultiSink(sinks)


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    from cs744_pytorch_distributed_tutorial_tpu.utils.compile_cache import (
        configure_compile_cache,
    )

    configure_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import (
        TransformerLM,
    )
    from cs744_pytorch_distributed_tutorial_tpu.serve import (
        GuardConfig,
        Request,
        ServeConfig,
        ServeGuard,
        ServingEngine,
        make_poisson_workload,
        run_batch_baseline,
        run_poisson,
        run_serve_with_recovery,
    )
    from cs744_pytorch_distributed_tutorial_tpu.utils import profiling

    if args.model_config:
        import json

        from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import (
            model_config_from_hf,
        )

        how_deployed = (
            {} if args.held_experts is None
            else {"held_experts": range(args.held_experts)}
        )
        with open(args.model_config, encoding="utf-8") as f:
            model = TransformerLM(
                **model_config_from_hf(
                    json.load(f), max_seq_len=args.max_seq_len, **how_deployed
                )
            )
        args.vocab_size = model.vocab_size
    else:
        model = TransformerLM(
            vocab_size=args.vocab_size,
            num_layers=args.num_layers,
            num_heads=args.num_heads,
            num_kv_heads=args.num_kv_heads,
            d_model=args.d_model,
            d_ff=args.d_ff,
            max_seq_len=args.max_seq_len,
            attention_impl="dense",
            use_rope=args.use_rope,
            quant_kv_cache=args.quant_kv,
        )
    params = model.init(
        jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    cfg = ServeConfig(
        num_slots=args.num_slots,
        page_size=args.page_size,
        num_pages=args.num_pages,
        max_pages_per_slot=args.max_pages_per_slot,
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        eos_id=args.eos_id,
        seed=args.seed,
        paged_attention_impl=args.paged_attention_impl,
        prefill_chunk=args.prefill_chunk,
    )
    workload = make_poisson_workload(
        num_requests=args.requests,
        rate_rps=args.rate,
        prompt_len=tuple(args.prompt_len),
        output_len=tuple(args.output_len),
        vocab_size=args.vocab_size,
        seed=args.seed,
    )
    sink = _make_sink(args.metrics_dir)
    failed = False
    try:
        if args.parity_check:
            from cs744_pytorch_distributed_tutorial_tpu.infer import (
                make_generator,
            )

            # An exact-token audit needs matmuls that cannot flip an
            # argmax by rounding: at the TPU default a float32 dot is one
            # bf16 pass, and the kernel and the generator round it in
            # different places (on a v5e, 3 of 8 random-weight requests
            # at 12L/768d diverged at the default; none at "highest").
            with jax.default_matmul_precision("highest"):
                engine = ServingEngine(model, params, cfg, sink=None)
                for i, prompt in enumerate(workload.prompts):
                    engine.submit(Request(
                        prompt=prompt,
                        max_new_tokens=int(workload.max_new_tokens[i]),
                    ))
                by_id = {r.req_id: r for r in engine.run()}
                gens: dict[int, object] = {}
                mismatches = 0
                for i, prompt in enumerate(workload.prompts):
                    n = int(workload.max_new_tokens[i])
                    if n not in gens:
                        gens[n] = make_generator(
                            model, max_new_tokens=n, temperature=0.0,
                            eos_id=cfg.eos_id,
                        )
                    ref = np.asarray(
                        gens[n](params, prompt[None, :], jax.random.key(0))
                    )[0].tolist()
                    if cfg.eos_id is not None and cfg.eos_id in ref:
                        ref = ref[: ref.index(cfg.eos_id) + 1]
                    if by_id[i].generated != ref:
                        mismatches += 1
            sink.emit({
                "kind": "serve",
                "event": "parity",
                "requests": len(workload),
                "mismatches": mismatches,
                "parity_ok": mismatches == 0,
            })
            failed |= mismatches > 0

        tracer = None
        window_every = args.window_every
        if args.trace_dir and window_every is None:
            window_every = 0.25
        if args.trace_dir or window_every is not None:
            from cs744_pytorch_distributed_tutorial_tpu.obs.serve_trace import (
                ServeTracer,
            )

            tracer = ServeTracer(
                args.num_slots, window_every_s=window_every
            )
        guard = None
        if any(v is not None for v in (
            args.deadline_s, args.max_queue_s,
            args.max_queue_depth, args.shed_policy,
        )):
            guard = ServeGuard(cfg=GuardConfig(
                deadline_s=args.deadline_s,
                max_queue_s=args.max_queue_s,
                max_queue_depth=args.max_queue_depth,
                shed_policy=args.shed_policy or "reject",
                degrade_floor=args.degrade_floor,
            ))

        # The profiler's capture spans the serving run, its warm-up
        # included (the warm-up's serve/step spans and the compiles come
        # first on the timeline).
        with (
            profiling.trace(args.profile_dir)
            if args.profile_dir else contextlib.nullcontext()
        ):
            if args.chaos or args.step_timeout_s is not None:
                # Supervised recovery loop: the supervisor owns the flight
                # recorder (one per engine generation, armed by its step
                # watchdog) and restarts the engine from its snapshot on
                # any ServeFailure.
                from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
                    SERVE_FAULT_KINDS,
                    FaultSchedule,
                    ServeChaosMonkey,
                )

                monkey = None
                if args.chaos:
                    faults = _parse_chaos(args.chaos)
                    bad = sorted(
                        set(faults.values()) - set(SERVE_FAULT_KINDS)
                    )
                    if bad:
                        raise SystemExit(
                            f"--chaos kinds {bad} not in {SERVE_FAULT_KINDS}"
                        )
                    monkey = ServeChaosMonkey(
                        FaultSchedule(faults), telemetry=sink
                    )

                engines: list = []

                def make_engine():
                    eng = ServingEngine(
                        model, params, cfg,
                        sink=sink, tracer=tracer, guard=guard,
                    )
                    engines.append(eng)
                    return eng

                serve_rec = run_serve_with_recovery(
                    make_engine, workload,
                    monkey=monkey,
                    max_restarts=args.max_restarts,
                    backoff_s=args.restart_backoff_s,
                    step_timeout_s=args.step_timeout_s,
                    telemetry=sink,
                    sink=sink,
                )
                engine = engines[-1]
            else:
                engine = ServingEngine(
                    model, params, cfg, sink=sink, tracer=tracer, guard=guard,
                )
                # Flight recorder over the serving loop: SIGTERM/uncaught-
                # crash dumps the serve event ring tail + pool high-water
                # through the sink — same discipline the training engines
                # get.
                flight = engine.make_flight_recorder()
                flight.install()
                try:
                    serve_rec = run_poisson(engine, workload, sink=sink)
                finally:
                    flight.uninstall()

        if args.trace_dir:
            import os

            from cs744_pytorch_distributed_tutorial_tpu.obs.serve_trace import (
                profile_serve_programs,
            )

            tracer.write(args.trace_dir)
            # Post-run on purpose: profiling re-runs + AOT-compiles the
            # programs, which must stay outside the measured (0-retrace)
            # section.
            phase_recs = profile_serve_programs(engine)
            for rec in phase_recs:
                sink.emit(rec)
            with open(
                os.path.join(args.trace_dir, "serve_phases.json"),
                "w", encoding="utf-8",
            ) as f:
                json.dump(phase_recs, f, indent=1)

        if args.compare_baseline or args.gate:
            pool_tokens = cfg.num_pages * cfg.page_size
            batch = max(1, pool_tokens // args.max_seq_len)
            base_rec = run_batch_baseline(
                model, params, workload,
                batch_size=batch,
                temperature=args.temperature,
                eos_id=args.eos_id,
                sink=sink,
            )
            comparison = {
                "kind": "serve",
                "event": "comparison",
                "baseline_batch": batch,
                "engine_kv_tokens": pool_tokens,
                "baseline_kv_tokens": batch * args.max_seq_len,
                "tokens_per_sec_ratio": round(
                    serve_rec["tokens_per_sec"]
                    / max(1e-9, base_rec["tokens_per_sec"]), 3
                ),
                "ttft_p99_ratio": round(
                    serve_rec["ttft_p99_ms"]
                    / max(1e-9, base_rec["ttft_p99_ms"]), 3
                ),
                "engine_wins": (
                    serve_rec["tokens_per_sec"] > base_rec["tokens_per_sec"]
                    and serve_rec["ttft_p99_ms"] < base_rec["ttft_p99_ms"]
                ),
            }
            sink.emit(comparison)
            if args.gate and not comparison["engine_wins"]:
                print(
                    json.dumps({
                        "gate": "serve",
                        "error": "continuous batching did not beat the "
                                 "batch-at-a-time baseline on both "
                                 "tokens/sec and p99 TTFT",
                    }),
                    file=sys.stderr,
                )
                failed = True
    finally:
        sink.close()
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
