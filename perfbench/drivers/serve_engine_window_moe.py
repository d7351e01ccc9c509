"""Driver for ``ServingEngine`` under a mixture of experts whose layers
differ by kind, sliding-window and full attention mixed (the ``mellum``
family): the closed loop of ``serve_engine_sparse_moe``, short and long
prompts in one queue, every prompt prefilled by chunks, the KV cache in
two page groups.

It fills the same fields of the run as ``serve_engine`` does
(``counts.slot_occupancy``, ``spans.itl_ms``, the
``perfbench/engine_step`` span around each traced ``step()``,
``compile_s``, ``compiles_in_window``), so every metric without a list
of cells that moves ``serve_tokens_per_s`` or ``setup_s`` reads here
unedited; ``config`` carries, beside the configuration's own keys, the
GPT-2-style keys under which the accepted ``mfu.serve`` counts this
model's ACTIVE parameters (``work_window_moe.dense_equivalent``), and
``attention_flops_in_window`` counts the keys inside each layer's reach
(``min(context, sliding_window)`` on a sliding layer). New here: the
engine's counters of the window and of the traced steps (keys attended
on the full and on the sliding layers, experts that received a token,
window pages given back) and the live pages of the two groups sampled
at every engine step, which the new per-layer metrics read.

Set-up, the fixed schedule of sizes (``sized_pool``: the seed gives the
token ids and the weights, ``lengths_seed`` the order of the 32 length
classes) and the clients' staggered first answers are the long cell's,
for its reasons (``serve_engine_sparse_moe``): a window finishes some
some sixty of these requests, and which classes fall inside it is
the amount of work. The reference (``reference/mellum2.py``)
teacher-forces a sample of the finished requests, the one with the
longest prompt among them (the deepest context the run served: YaRN's
divided frequencies have turned furthest there);
``correct`` is decided as the long cell's is, by the gaps of the served
tokens' logits below the reference's best: the widest, and their mean.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W, weights_mellum2 as WM, work_window_moe as wwm
from perfbench.drivers.serve_engine_sparse_moe import gap_numbers, gaps_below_best, sized_pool

# (name, keywords of reference.mellum2.forward) of the control and of the
# faults a probing run reads beside the program's own number
PROBES = (
    ("control_fp8", {"quant": "fp8"}),
    ("fault_window_as_full", {"fault": "window_as_full"}),
    ("fault_window_short", {"fault": "window_short"}),
    ("fault_window_long", {"fault": "window_long"}),
    ("fault_rope_default", {"fault": "rope_default"}),
    ("fault_drop_expert", {"fault": "drop_expert"}),
)
COUNTERS = (
    "full_tokens_read", "window_tokens_read", "experts_hit", "prefill_chunks", "admissions",
    "window_pages_freed",
)


def build_model(cfg, max_len: int):
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import mellum_model_config
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import TransformerLM

    return TransformerLM(
        **mellum_model_config(cfg, max_seq_len=max_len), dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def answer_logits(cfg, flat, requests, **variant) -> list[Any]:
    """The reference's logits at the positions that produced each
    request's answer (teacher-forced: one pass over the prompt with its
    served tokens), [answer tokens, vocabulary] a request; with a
    ``variant``, the control's or a planted fault's."""
    from perfbench.reference import mellum2

    out = []
    for prompt, answer in requests:
        toks = np.concatenate([prompt, np.asarray(answer, np.int32)])
        out.append(mellum2.forward(flat, toks, cfg, at=np.arange(len(prompt) - 1, len(toks) - 1), **variant))
    return out


def pick_checked(done, k: int, seed: int):
    """A sample of the finished requests, drawn from the seed, with the
    one of the longest prompt in it (first)."""
    done = sorted(done, key=lambda r: r.req_id)
    longest = max(done, key=lambda r: (r.orig_prompt_len, r.req_id))
    rest = [r for r in done if r is not longest]
    idx = np.random.default_rng(seed).permutation(len(rest))[: max(k - 1, 0)]
    return [longest] + [rest[i] for i in idx]


def counter_delta(after, before) -> dict[str, float]:
    """The engine's counters between two readings of ``stats()``."""
    steps = after["decode_steps"] - before["decode_steps"]
    out = {k: after[k] - before[k] for k in COUNTERS}
    out["decode_steps"] = steps
    out["occupancy_steps"] = (
        after["slot_occupancy"] * after["decode_steps"] - before["slot_occupancy"] * before["decode_steps"]
    )
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
    dims = wwm.dims(cfg)
    model = build_model(cfg, int(tr["max_total_len"]))
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    flat = WM.make_weights(cfg, run.seed, cfg["compute_dtype"])
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
    run.log(
        f"engine built; pool of {len(prompts)} requests; window group {engine.window_pool.num_pages} pages, "
        f"{engine.window_table_width} a slot"
    )

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
    live_full = live_window = 0  # pages of each group, summed over the steps
    while step_stamps[-1] - t0 < run.seconds:
        for _ in engine.step():
            send()
        live_full += engine.pool.allocated_pages
        live_window += engine.window_pool.allocated_pages
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
        traced = counter_delta(engine.stats(), before)
        traced["token_expert_pairs"] = dims["layers"] * dims["k"] * traced["occupancy_steps"] * int(tr["num_slots"])
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
    prefilled = [r.orig_prompt_len for r in sent
                 if r.first_token_time is not None and t0 < r.first_token_time <= t1]
    attention_flops = wwm.attention_flops_in_window(
        window["full_tokens_read"], window["window_tokens_read"], prefilled, cfg
    )
    run.read_memory_peak()
    n_sent = len(in_window)
    preemptions = stats1["preemptions"]
    pools = {"full": engine.pool.num_pages, "window": engine.window_pool.num_pages}
    del engine, params, model
    gc.collect()

    truth = answer_logits(cfg, flat, checked)
    served = [np.asarray(answer, np.int32) for _, answer in checked]
    gaps = gaps_below_best(truth, served)
    values = {
        **gap_numbers(gaps), "tokens_compared": len(gaps), "requests_failed": failed,
        "longest_prompt_checked": max(len(p) for p, _ in checked),
    }
    run.log(f"reference done over {len(checked)} requests (prompts {[len(p) for p, _ in checked]}), "
            f"{len(gaps)} tokens: {gap_numbers(gaps)}")
    if check.probing():
        wanted = os.environ["PERFBENCH_PROBE"].split(",")
        n_probed = 2  # the one of the longest prompt and one more: a probe is a whole pass a request
        for name, variant in PROBES:
            if wanted == ["1"] or name in wanted:
                # the tokens the variant puts first, judged by the true logits
                judged = [jnp.argmax(rows, axis=-1) for rows in answer_logits(cfg, flat, checked[:n_probed], **variant)]
                values.update(gap_numbers(gaps_below_best(truth, judged), f"{name}."))
                run.log(f"probe {name}: {({k: v for k, v in values.items() if k.startswith(name)})}")
        # the fault of a token altered where it is produced: one served
        # token of one answer replaced by its neighbour in the vocabulary
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
            "slot_occupancy": window["occupancy_steps"] / max(steps_w, 1),
            "prompt_tokens_in_window": int(sum(prefilled)),
            "attention_flops_in_window": attention_flops, "preemptions": preemptions,
            "kv_live_share": wwm.kv_live_share(live_full, live_window, cfg),
            "pages_live_full_mean": live_full / max(len(step_stamps) - 1, 1),
            "pages_live_window_mean": live_window / max(len(step_stamps) - 1, 1),
            "pool_pages": pools, "window": window, "traced": traced,
        },
        "spans": {
            "ttft_ms": ttft_all, "itl_ms": itl,
            "engine_step_s": list(np.diff(step_stamps)),
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        # sa_config: the keys work_sparse_moe.dims reads for any count (this
        # model has no indexer), so the accepted counted_roofline counts
        # the experts' work here
        "config": {**cfg, **wwm.dense_equivalent(cfg), "sa_config": wwm.NO_INDEXER}, "traffic": tr,
    }
