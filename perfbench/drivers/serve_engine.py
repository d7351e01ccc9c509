"""Driver for ``ServingEngine.submit()`` / ``step()``: a closed loop of
clients, each sending its next request when its last one finishes.

Set-up makes the weights from the seed in the type they are served in,
warms the prompt buckets the traffic uses and the decode step, then
starts the clients. To start near the steady state, each client's first
request asks only for a random remainder of its answer (as if it were
part-way through), so the requests do not all finish together; the loop
then runs until ``warm_requests`` have finished. The window opens and
closes after an ``engine.step()`` has returned its tokens (the engine
fetches them each step, so that is a fence).
"""

from __future__ import annotations

import gc
import time
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W, work


def build_model(cfg):
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import TransformerLM

    return TransformerLM(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
        d_model=cfg["n_embd"], d_ff=cfg["n_inner"], max_seq_len=cfg["n_positions"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attention_impl="dense",
        norm_eps=cfg["layer_norm_epsilon"], tie_embeddings=cfg["tie_word_embeddings"],
    )


def bucket_for(n: int) -> int:
    """The engine's prompt buckets are powers of two from 8."""
    b = 8
    while b < n:
        b *= 2
    return b


def served_gap(cfg, flat, requests, quant=None) -> tuple[float, int]:
    """Widest gap by which a served token's logit lies below the
    reference's best, over the answers of ``requests`` (teacher-forced:
    one reference pass over each prompt with its served tokens). With
    ``quant`` the tokens judged are those the lower precision puts first
    at each position (the control), not the served ones."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import gpt2

    fwd = jax.jit(lambda p, x: gpt2.forward(p, x, cfg))
    fwd_q = jax.jit(lambda p, x: gpt2.forward(p, x, cfg, quant)) if quant else None
    worst, n = 0.0, 0
    for prompt, answer in requests:
        toks = np.concatenate([prompt, np.asarray(answer, np.int32)])
        length = bucket_for(len(toks))
        x = np.zeros((1, length), np.int32)
        x[0, : len(toks)] = toks
        logits = fwd(flat, jnp.asarray(x))[0]
        lo, hi = len(prompt) - 1, len(toks) - 1
        rows = logits[lo:hi]
        if fwd_q is not None:
            judged = jnp.argmax(fwd_q(flat, jnp.asarray(x))[0][lo:hi], axis=-1)
        else:
            judged = jnp.asarray(toks[lo + 1: hi + 1])
        gaps = jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, judged[:, None], axis=-1)[:, 0]
        worst = max(worst, float(jnp.max(gaps)))
        n += hi - lo
    return worst, n


def pick_checked(done, k: int, seed: int):
    """A sample of the finished requests, drawn from the seed, with the
    longest in it."""
    done = sorted(done, key=lambda r: r.req_id)
    longest = max(done, key=lambda r: (len(r.generated), r.req_id))
    rng = np.random.default_rng(seed)
    rest = [r for r in done if r is not longest]
    idx = rng.permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + [rest[i] for i in idx]


def run(run) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.serve.engine import (
        Request,
        ServeConfig,
        ServingEngine,
    )

    tr, cfg = run.traffic, run.config
    seed = W.seed31(run.seed)
    model = build_model(cfg)
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    params = W.fill_tree(template, W.make_weights("gpt2", cfg, run.seed, cfg["compute_dtype"]))
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=tr["num_slots"], page_size=tr["page_size"], num_pages=tr["num_pages"],
            max_pages_per_slot=tr["max_pages_per_slot"], temperature=tr["temperature"], seed=seed,
        ),
    )
    prompts, answers = T.request_pool(tr, run.seed)
    run.log(f"engine built; pool of {len(prompts)} requests")

    # ---- warm every program the traffic uses ------------------------------
    rng = np.random.default_rng(seed + 1)
    buckets = sorted({bucket_for(len(p)) for p in prompts})
    for b in buckets:
        n = min(b, int(tr["max_total_len"]) - 2)
        engine.submit(Request(
            prompt=rng.integers(0, int(tr["token_id_below"]), n, dtype=np.int32), max_new_tokens=2,
        ))
    engine.run()
    run.log(f"warmed prefill buckets {buckets} and the decode step; compile {run.compiles.seconds:.1f} s")

    # ---- the clients -------------------------------------------------------
    sent: list[Any] = []
    next_idx = 0

    def send(first: bool = False):
        nonlocal next_idx
        i = next_idx % len(prompts)
        next_idx += 1
        want = answers[i]
        if first:
            want = max(1, int(np.ceil(rng.uniform() * want)))
        req = engine.submit(Request(prompt=prompts[i], max_new_tokens=want))
        sent.append(req)
        return req

    for _ in range(int(tr["clients"])):
        send(first=True)
    finished = 0
    while finished < int(tr["warm_requests"]):
        for _ in engine.step():
            finished += 1
            send()
    run.log(f"warm: {finished} requests finished, {engine.stats()['decode_steps']} engine steps")

    # ---- the window --------------------------------------------------------
    compiles0, stats0 = run.compiles.count, engine.stats()
    step_stamps = [time.monotonic()]
    t0 = step_stamps[0]
    while step_stamps[-1] - t0 < run.seconds:
        for _ in engine.step():
            send()
        step_stamps.append(time.monotonic())
    t1 = step_stamps[-1]
    compiles_in_window = run.compiles.count - compiles0
    stats1 = engine.stats()
    in_window = [r for r in sent if t0 <= r.submit_time < t1]
    # A traced run goes on under the profiler for a few seconds more, the
    # clients still sending, so no tracing falls inside the window.
    traced_stamps: list[float] = []
    if run.trace and not run.rehearse:
        jax.profiler.start_trace(str(run.trace_dir))
        traced_stamps.append(time.monotonic())
        while traced_stamps[-1] - traced_stamps[0] < float(tr["trace_seconds"]):
            with jax.profiler.TraceAnnotation("perfbench/engine_step"):
                done = engine.step()
            for _ in done:
                send()
            traced_stamps.append(time.monotonic())
        jax.profiler.stop_trace()
    # Past the window only until each of its requests has its first token;
    # what surfaces now adds nothing to the window's token count.
    drain_deadline = time.monotonic() + 60.0
    while any(r.first_token_time is None and r.status is None for r in in_window):
        if time.monotonic() > drain_deadline:
            break
        for _ in engine.step():
            send()
    tokens = sum(1 for r in sent for t in r.token_times if t0 < t <= t1)
    rate = tokens / (t1 - t0)
    ttft = [
        (r.first_token_time - r.submit_time) * 1e3
        for r in in_window if r.first_token_time is not None and r.status in (None, "completed")
    ]
    failed = len(in_window) - len(ttft)
    worst = max(ttft) if ttft else float("inf")
    ttft_all = ttft + [worst] * failed
    itl = [
        (b - a) * 1e3 for r in sent
        for a, b in zip(r.token_times, r.token_times[1:]) if t0 < b <= t1
    ]
    series = T.series_summary(step_stamps, compiles_in_window, "engine steps")
    run.log(f"window {t1 - t0:.2f} s, {rate:.1f} tokens/s, {len(in_window)} requests, series {series}")
    run.log("ttft ms " + ", ".join(f"p{q} {T.percentile(ttft_all, q):.1f}" for q in (50, 90, 95, 99)))

    # ---- peak memory, free the engine, then the reference ------------------
    done = [r for r in sent if r.status == "completed" and r.done_time <= time.monotonic()
            and len(r.generated) == r.max_new_tokens and r.done_time > t0]
    checked = [
        (np.asarray(r.prompt[: r.orig_prompt_len]), list(r.generated))
        for r in pick_checked(done, int(tr["check_requests"]), seed)
    ]
    steps_w = stats1["decode_steps"] - stats0["decode_steps"]
    occupancy = (
        (stats1["slot_occupancy"] * stats1["decode_steps"] - stats0["slot_occupancy"] * stats0["decode_steps"])
        / max(steps_w, 1)
    )
    # KV rows live at a sample of the window's steps: prompt plus the
    # tokens surfaced so far, over the requests then in a slot.
    def live_tokens_at(stamps):
        return [
            sum(r.orig_prompt_len + int(np.searchsorted(r.token_times, s, side="right"))
                for r in sent if r.first_token_time is not None
                and r.first_token_time <= s and (r.done_time is None or r.done_time > s))
            for s in stamps[:: max(len(stamps) // 50, 1)]
        ]

    live_tokens = live_tokens_at(step_stamps)
    prefilled = [r.orig_prompt_len for r in sent
                 if r.first_token_time is not None and t0 < r.first_token_time <= t1]
    attention_flops = (
        sum(2.0 * cfg["n_layer"] * n * n * cfg["n_embd"] for n in prefilled)  # causal prefill
        + steps_w * work.paged_decode_attn_flops(float(np.mean(live_tokens)), cfg)
    )
    run.read_memory_peak()
    n_sent = len(in_window)
    del engine, params, model
    gc.collect()

    flat = W.make_weights("gpt2", cfg, run.seed, cfg["compute_dtype"])
    gap, n_tokens = served_gap(cfg, flat, checked)
    values = {"served_logit_gap": gap, "tokens_compared": n_tokens, "requests_failed": failed}
    if check.probing():
        for q in ("fp8", "int8"):
            values[f"control_{q}.served_logit_gap"] = served_gap(cfg, flat, checked, q)[0]
        # the fault of a token altered where it is produced: one served
        # token of one answer replaced by its neighbour in the vocabulary
        prompt, answer = checked[-1]
        altered = list(answer)
        altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % int(tr["token_id_below"])
        values["fault_token_altered.served_logit_gap"] = served_gap(cfg, flat, [(prompt, altered)])[0]
    verdict = check.judge(
        values,
        run.limits(),
    )
    run.log(f"reference done over {len(checked)} requests, {n_tokens} tokens: gap {gap}")

    return {
        "end_to_end": {
            "serve_tokens_per_s": rate,
            "serve_ttft_p95_ms": T.percentile(ttft_all, 95),
        },
        "window_start_mono": t0,
        "window_s": t1 - t0,
        "series": series,
        "attempted": n_sent,
        "failed": failed,
        "check": verdict,
        "counts": {
            "requests_in_window": n_sent, "tokens_in_window": tokens, "engine_steps": steps_w,
            "slot_occupancy": occupancy, "mean_live_tokens": float(np.mean(live_tokens)),
            "mean_live_tokens_traced": float(np.mean(live_tokens_at(traced_stamps))) if traced_stamps else None,
            "prompt_tokens_in_window": int(sum(prefilled)), "attention_flops_in_window": attention_flops,
        },
        "spans": {
            "ttft_ms": ttft_all, "itl_ms": itl,
            "engine_step_s": list(np.diff(step_stamps)),
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "config": cfg, "traffic": tr,
    }
