"""Driver for ``ServingEngine`` under DeepSeek-V2 (the ``deepseek_v2``
family): plain pre-norm blocks over latent attention, one paged pool of
latents a layer, a leading dense layer, group-limited routing over one
chip's share of the routed experts beside two shared experts; long
documents, every prompt prefilled by chunks. The loop is the accepted
``serve_engine_latent_moe``'s (copied from it, not imported: that
driver's loop is one function that builds LongCat's model and reads
LongCat's configuration keys), its helpers imported from it and the
drivers it names.

It fills the same fields of the run as the accepted drivers do
(``counts.slot_occupancy``, ``spans.itl_ms``, the ``perfbench/engine_step``
span around each traced ``step()``, ``compile_s``,
``compiles_in_window``), so every metric without a list of cells that
moves ``serve_tokens_per_s`` or ``setup_s`` reads here unedited;
``config`` carries, beside the configuration's own keys, the GPT-2-style
keys under which the accepted ``mfu.serve`` counts the parameters a
token really multiplies HERE (``work_deepseek_v2.dense_equivalent``: the
dense layer, each MoE layer's attention, shared experts and router, the
held experts it chose by the window's own counters, the head's slice),
and the keys under which the accepted ``counted_roofline`` counts the
held experts' work; ``attention_flops_in_window`` is the absorbed
products over ``latent_tokens_read`` and the prompts' causal passes. New
here: ``held_group_tokens``, the (token, MoE layer) pairs of the decode
steps whose kept groups include the held group, and its share.

The reference (``reference/deepseek_v2.py``) teacher-forces a sample of
the finished requests, the one with the longest prompt among them;
``correct`` is decided as the chunked cells' is, by the gaps of the
served tokens' logits below the reference's best: the widest, and their
mean.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W, weights_deepseek_v2 as WD, work_deepseek_v2 as wd2
from perfbench.drivers.serve_engine_sparse_moe import gap_numbers, gaps_below_best, sized_pool
from perfbench.drivers.serve_engine_window_moe import pick_checked

# (name, keywords of reference.deepseek_v2.forward) of the control and of
# the faults a probing run reads beside the program's own number
PROBES = (
    ("control_fp8", {"quant": "fp8"}),
    ("fault_no_groups", {"fault": "no_groups"}),
    ("fault_yarn_on_cos_sin", {"fault": "yarn_on_cos_sin"}),
    ("fault_no_shared_experts", {"fault": "no_shared_experts"}),
    ("fault_drop_expert", {"fault": "drop_expert"}),
)
COUNTERS = (
    "latent_tokens_read", "held_expert_pairs", "zero_expert_pairs", "absent_expert_pairs", "experts_hit",
    "held_group_tokens", "prefill_chunks", "admissions",
)


def build_model(cfg, max_len: int):
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import deepseek_v2_model_config
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import TransformerLM

    published, held = wd2.as_published(cfg)
    return TransformerLM(
        **deepseek_v2_model_config(published, max_seq_len=max_len, held_experts=held),
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def answer_logits(cfg, flat, requests, **variant) -> list[Any]:
    """The reference's logits at the positions that produced each
    request's answer (teacher-forced: one pass over the prompt with its
    served tokens), [answer tokens, vocabulary] a request; with a
    ``variant``, the control's or a planted fault's."""
    from perfbench.reference import deepseek_v2

    out = []
    for prompt, answer in requests:
        toks = np.concatenate([prompt, np.asarray(answer, np.int32)])
        out.append(deepseek_v2.forward(flat, toks, cfg, at=np.arange(len(prompt) - 1, len(toks) - 1), **variant))
    return out


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
    # what the accepted counted_roofline reads: the pairs whose products ran
    out["token_expert_pairs"] = out["held_expert_pairs"]
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
    dims = wd2.dims(cfg)
    model = build_model(cfg, int(tr["max_total_len"]))
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    flat = WD.make_weights(cfg, run.seed, cfg["compute_dtype"])
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
    pools = jax.tree_util.tree_leaves(engine._pages)
    run.log(
        f"engine built; pool of {len(prompts)} requests; {len(pools)} latent pools of "
        f"{pools[0].shape} {pools[0].dtype}, {sum(p.nbytes for p in pools) / 1e9:.2f} GB; weights "
        f"{sum(w.nbytes for w in flat.values()) / 1e9:.2f} GB"
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
        traced = counter_delta(engine.stats(), before)
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
    slot_steps = window["occupancy_steps"] * int(tr["num_slots"])  # tokens the decode steps produced
    moe_tokens = slot_steps * dims["moe_layers"]  # (token, MoE layer) pairs the decode steps routed
    held_per_token = window["held_expert_pairs"] / moe_tokens if moe_tokens else None
    prefilled = [r.orig_prompt_len for r in sent
                 if r.first_token_time is not None and t0 < r.first_token_time <= t1]
    attention_flops = wd2.attention_flops_in_window(window["latent_tokens_read"], prefilled, cfg)
    run.read_memory_peak()
    n_sent = len(in_window)
    preemptions = stats1["preemptions"]
    del engine, params, model, pools
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
        n_probed = 1  # the one of the longest prompt: a probe is a whole pass over up to 17k tokens
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
            # of the (token, MoE layer) pairs of the window's decode
            # steps, those whose kept groups include the held group
            # (3/8 under an even router)
            "held_group_share": window["held_group_tokens"] / max(moe_tokens, 1),
            # held experts that received a token, a decode step an MoE
            # layer, over the held experts there are
            "held_expert_hit_share": window["experts_hit"] / max(steps_w * dims["moe_layers"] * dims["held"], 1),
            "held_experts_per_token": held_per_token,
            "window": window, "traced": traced,
        },
        "spans": {
            "ttft_ms": ttft_all, "itl_ms": itl,
            "engine_step_s": list(np.diff(step_stamps)),
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "config": {**cfg, **wd2.as_sparse_moe_config(cfg), **wd2.dense_equivalent(cfg, held_per_token)},
        "traffic": tr,
    }
