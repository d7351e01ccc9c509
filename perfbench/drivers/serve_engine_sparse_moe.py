"""Driver for ``ServingEngine`` under a sparse-attention mixture of
experts (the ``keye`` family): the closed loop of ``serve_engine``, with
long prompts prefilled by chunks.

It fills the same fields of the run as ``serve_engine`` does
(``counts.slot_occupancy``, ``spans.itl_ms``, the
``perfbench/engine_step`` span around each traced ``step()``,
``compile_s``, ``compiles_in_window``), so every metric without a list
of cells that moves ``serve_tokens_per_s`` or ``setup_s`` reads here
unedited; ``config`` carries, beside the configuration's own keys, the
GPT-2-style keys under which the accepted ``mfu.serve`` counts this
model's ACTIVE parameters (``work_sparse_moe.dense_equivalent``). New
here: the engine's counters of the window and of the traced steps
(tokens the selection kept, tokens the indexer scored, experts that
received a token), which the new per-layer metrics read.

Set-up makes the weights from the seed in the type they are served in
(``weights_keye.py``), warms the one chunk program and the decode step,
then starts the clients part-way through their answers: the k-th of the
n clients first asks for (k + 1) / n of its answer, so that the slots'
ends are spread evenly over a round.

**The schedule of sizes is the same for every seed** (``sized_pool``):
the seed gives the token ids and the weights, ``lengths_seed`` of the
traffic file the order in which the 32 length classes come, round after
round, and the clients' first shares are not drawn. ``serve_engine``
takes the order from the run's seed, and can: its window serves some
thirteen hundred requests. This cell's window serves twenty to thirty,
under one round of 32, each a second of prefill that stalls every slot,
so WHICH classes fall inside the window, and in which phase the slots
are when it opens, is the amount of work: a model of this loop (fixed
chunk and step times, 48 seeds) spreads the rate by 9-11% under a
seeded order, 5% under any balanced one, 0 under a fixed one (PERF.md,
PR 27). The seed is to change the inputs, not the amount of work. The
reference
(``reference/keye.py``) teacher-forces a sample of the finished
requests, the longest among them; ``correct`` is decided by the gaps of
the served tokens' logits below the reference's best: the widest, and
their mean (``gap_numbers`` says why two).
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W, weights_keye as WK, work_sparse_moe as wsm
from perfbench.drivers.serve_engine import pick_checked

# (name, keywords of reference.keye.forward) of the control and of the
# faults a probing run reads beside the program's own number
PROBES = (
    ("control_fp8", {"quant": "fp8"}),
    ("fault_window", {"fault": "window"}),
    ("fault_topk_half", {"fault": "topk_half"}),
    ("fault_drop_expert", {"fault": "drop_expert"}),
)


def build_model(cfg, max_len: int):
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import keye_model_config
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import TransformerLM

    return TransformerLM(
        **keye_model_config(cfg, max_seq_len=max_len), dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def sized_pool(tr, seed: int):
    """The requests a run may send: sizes and their order as
    ``traffic.request_pool`` gives them for the traffic file's
    ``lengths_seed`` (round after round of all the length classes, each
    round in an order of its own), token ids from the run's ``seed``."""
    sizes, answers = T.request_pool(tr, int(tr["lengths_seed"]))
    lengths = np.array([len(p) for p in sizes])
    ids = np.random.default_rng(seed).integers(
        0, int(tr["token_id_below"]), int(lengths.sum()), dtype=np.int32
    )
    return np.split(ids, np.cumsum(lengths)[:-1]), answers


def answer_logits(cfg, flat, requests, **variant) -> list[Any]:
    """The reference's logits at the positions that produced each
    request's answer (teacher-forced: one pass over the prompt with its
    served tokens), [answer tokens, vocabulary] a request; with a
    ``variant``, the control's or a planted fault's."""
    from perfbench.reference import keye

    out = []
    for prompt, answer in requests:
        toks = np.concatenate([prompt, np.asarray(answer, np.int32)])
        out.append(keye.forward(flat, toks, cfg, at=np.arange(len(prompt) - 1, len(toks) - 1), **variant))
    return out


def gaps_below_best(truth, judged) -> np.ndarray:
    """For every answer token, the gap by which the judged token's
    logit lies below the reference's best."""
    import jax.numpy as jnp

    return np.concatenate([
        np.asarray(jnp.max(rows, axis=-1) - jnp.take_along_axis(rows, jnp.asarray(tok)[:, None], axis=-1)[:, 0])
        for rows, tok in zip(truth, judged)
    ])


def served_gaps(cfg, flat, requests) -> np.ndarray:
    """The served tokens' gaps, over the answers of ``requests``."""
    return gaps_below_best(answer_logits(cfg, flat, requests), [np.asarray(a, np.int32) for _, a in requests])


def gap_numbers(gaps: np.ndarray, prefix: str = "") -> dict[str, float]:
    """The two numbers that are held to limits, and two that are shown.
    ``served_logit_gap`` is the widest gap: one wrong token anywhere
    reads there. ``served_logit_gap_mean`` is the mean over the answer
    tokens: in bfloat16 a token's eighth expert is a near-tie that
    rounding flips in some tenth of the (token, layer) pairs, each flip
    moves that token's logits a little and a rare one much, so the widest
    gap of a sound run lies among the faults' (PERF.md, PR 27: all 128
    experts a token, and it falls from 0.31 to 0.013), while a fault that
    touches every token (a window in the selection's place, an expert
    left out, float8) moves the whole distribution. Of its mean, 90th
    and 99th percentile the mean parts sound from wrong widest on the
    chip (2.3x, 1.9x, 1.3x) and is the steadiest over seeds."""
    return {
        f"{prefix}served_logit_gap": float(gaps.max()),
        f"{prefix}served_logit_gap_p99": float(np.percentile(gaps, 99)),
        f"{prefix}served_logit_gap_p90": float(np.percentile(gaps, 90)),
        f"{prefix}served_logit_gap_mean": float(gaps.mean()),
    }


def counter_delta(after, before) -> dict[str, float]:
    """The engine's counters between two readings of ``stats()``."""
    steps = after["decode_steps"] - before["decode_steps"]
    out = {
        k: after[k] - before[k]
        for k in ("selected_tokens", "scored_tokens", "experts_hit", "prefill_chunks", "admissions")
    }
    out["decode_steps"] = steps
    out["expert_tokens_max_over_mean"] = (
        after["expert_tokens_max_over_mean"] * after["decode_steps"]
        - before["expert_tokens_max_over_mean"] * before["decode_steps"]
    ) / max(steps, 1)
    return out


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
    dims = wsm.dims(cfg)
    model = build_model(cfg, int(tr["max_total_len"]))
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    flat = WK.make_weights(cfg, run.seed, cfg["compute_dtype"])
    params = W.fill_tree(template, flat)
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=tr["num_slots"], page_size=tr["page_size"], num_pages=tr["num_pages"],
            max_pages_per_slot=tr["max_pages_per_slot"], temperature=tr["temperature"], seed=seed,
            prefill_chunk=tr["prefill_chunk"],
        ),
    )
    prompts, answers = sized_pool(tr, run.seed)
    run.log(f"engine built; pool of {len(prompts)} requests")

    # ---- warm the chunk program and the decode step -------------------------
    rng = np.random.default_rng(seed + 1)
    engine.submit(Request(
        prompt=rng.integers(0, int(tr["token_id_below"]), int(tr["prefill_chunk"]) + 3, dtype=np.int32),
        max_new_tokens=3,
    ))
    engine.run()
    run.log(f"warmed the chunk program and the decode step; compile {run.compiles.seconds:.1f} s")

    # ---- the clients --------------------------------------------------------
    sent: list[Any] = []
    next_idx = 0

    def send(share: float = 1.0):
        nonlocal next_idx
        i = next_idx % len(prompts)
        next_idx += 1
        want = max(1, int(np.ceil(share * answers[i])))
        req = engine.submit(Request(prompt=prompts[i], max_new_tokens=want))
        sent.append(req)
        return req

    n_clients = int(tr["clients"])
    for k in range(n_clients):
        send(share=(k + 1) / n_clients)
    finished = 0
    while finished < int(tr["warm_requests"]):
        for _ in engine.step():
            finished += 1
            send()
    run.log(f"warm: {finished} requests finished, {engine.stats()['decode_steps']} engine steps")

    # ---- the window ---------------------------------------------------------
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
    traced = None
    if run.trace and not run.rehearse:
        before = engine.stats()
        jax.profiler.start_trace(str(run.trace_dir))
        start = time.monotonic()
        while time.monotonic() - start < float(tr["trace_seconds"]):
            with jax.profiler.TraceAnnotation("perfbench/engine_step"):
                done = engine.step()
            for _ in done:
                send()
        jax.profiler.stop_trace()
        after = engine.stats()
        traced = counter_delta(after, before)
        traced["token_expert_pairs"] = dims["layers"] * dims["k"] * (
            after["slot_occupancy"] * after["decode_steps"] - before["slot_occupancy"] * before["decode_steps"]
        ) * int(tr["num_slots"])
    # Past the window only until each of its requests has its first token;
    # what surfaces now adds nothing to the window's token count.
    drain_deadline = time.monotonic() + 120.0
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
    ttft_all = ttft + [max(ttft) if ttft else float("inf")] * failed
    itl = [
        (b - a) * 1e3 for r in sent
        for a, b in zip(r.token_times, r.token_times[1:]) if t0 < b <= t1
    ]
    series = T.series_summary(step_stamps, compiles_in_window, "engine steps")
    run.log(f"window {t1 - t0:.2f} s, {rate:.1f} tokens/s, {len(in_window)} requests, series {series}")
    run.log("ttft ms " + ", ".join(f"p{q} {T.percentile(ttft_all, q):.1f}" for q in (50, 90, 95, 99)))
    run.log("itl ms " + ", ".join(f"p{q} {T.percentile(itl, q):.1f}" for q in (50, 95, 99)))

    # ---- peak memory, free the engine, then the reference -------------------
    done = [r for r in sent if r.status == "completed" and len(r.generated) == r.max_new_tokens
            and r.done_time > t0 and r.preemptions == 0]
    checked = [
        (np.asarray(r.prompt[: r.orig_prompt_len]), list(r.generated))
        for r in pick_checked(done, int(tr["check_requests"]), seed)
    ]
    window = counter_delta(stats1, stats0)
    steps_w = window["decode_steps"]
    occupancy = (
        (stats1["slot_occupancy"] * stats1["decode_steps"] - stats0["slot_occupancy"] * stats0["decode_steps"])
        / max(steps_w, 1)
    )
    prefilled = [r.orig_prompt_len for r in sent
                 if r.first_token_time is not None and t0 < r.first_token_time <= t1]
    # Attention and indexer work of the window: decode from the engine's
    # counters (summed over layers), prefill from the prompts' lengths.
    pre_sel, pre_scored = (sum(x) for x in zip(*(wsm.prefill_selection(n, cfg) for n in prefilled))) if prefilled else (0.0, 0.0)
    attention_flops = (
        wsm.attention_flops(window["selected_tokens"] + dims["layers"] * pre_sel, cfg)
        + wsm.indexer_flops(window["scored_tokens"] + dims["layers"] * pre_scored, cfg)
    )
    run.read_memory_peak()
    n_sent = len(in_window)
    preemptions = stats1["preemptions"]
    del engine, params, model
    gc.collect()

    truth = answer_logits(cfg, flat, checked)
    served = [np.asarray(answer, np.int32) for _, answer in checked]
    gaps = gaps_below_best(truth, served)
    values = {**gap_numbers(gaps), "tokens_compared": len(gaps), "requests_failed": failed}
    run.log(f"reference done over {len(checked)} requests, {len(gaps)} tokens: {gap_numbers(gaps)}")
    if check.probing():
        import jax.numpy as jnp

        wanted = os.environ["PERFBENCH_PROBE"].split(",")
        n_probed = 2  # the longest and one more: a probe is a whole pass a request
        for name, variant in PROBES:
            if wanted == ["1"] or name in wanted:
                # the tokens the variant puts first, judged by the true logits
                judged = [jnp.argmax(rows, axis=-1) for rows in answer_logits(cfg, flat, checked[:n_probed], **variant)]
                values.update(gap_numbers(gaps_below_best(truth, judged), f"{name}."))
                run.log(f"probe {name}: {({k: v for k, v in values.items() if k.startswith(name)})}")
        # the fault of a token altered where it is produced: one served
        # token of one answer replaced by its neighbour in the vocabulary
        # (its logit read at its own position, the others' unchanged)
        altered = served[0].copy()
        altered[len(altered) // 2] = (altered[len(altered) // 2] + 1) % int(tr["token_id_below"])
        values["fault_token_altered.served_logit_gap"] = float(gaps_below_best(truth[:1], [altered]).max())
    verdict = check.judge(values, run.limits())

    return {
        "end_to_end": {"serve_tokens_per_s": rate},
        "window_start_mono": t0,
        "window_s": t1 - t0,
        "series": series,
        "attempted": n_sent,
        "failed": failed,
        "check": verdict,
        "counts": {
            "requests_in_window": n_sent, "tokens_in_window": tokens, "engine_steps": steps_w,
            "slot_occupancy": occupancy, "prompt_tokens_in_window": int(sum(prefilled)),
            "attention_flops_in_window": attention_flops, "preemptions": preemptions,
            "selected_share": window["selected_tokens"] / max(window["scored_tokens"], 1),
            "window": window, "traced": traced,
        },
        "spans": {
            "ttft_ms": ttft_all, "itl_ms": itl,
            "engine_step_s": list(np.diff(step_stamps)),
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "config": {**cfg, **wsm.dense_equivalent(cfg)}, "traffic": tr,
    }
