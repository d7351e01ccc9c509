"""Driver for ``ServingEngine`` under MiniCPM-SALA (the ``minicpm_sala``
family): lightning-attention layers that keep a float32 state row a
slot, block-sparse layers over paged K and V with a compressed key a
page; long documents and long answers, every prompt prefilled by chunks.
The loop is the accepted ``serve_engine_deepseek_v2``'s (copied from it,
not imported: that driver's loop is one function that builds
DeepSeek-V2's model and reads its configuration's keys), its helpers
imported from the drivers it names.

It fills the same fields of the run as the accepted drivers do
(``counts.slot_occupancy``, ``spans.itl_ms``, the ``perfbench/engine_step``
span around each traced ``step()``, ``compile_s``,
``compiles_in_window``), so every metric without a list of cells that
moves ``serve_tokens_per_s`` or ``setup_s`` reads here unedited;
``config`` carries, beside the configuration's own keys, the GPT-2-style
keys under which the accepted ``mfu.serve`` counts the parameters a
token multiplies (``work_minicpm_sala.dense_equivalent``), and
``attention_flops_in_window`` is the lightning updates, the chosen
positions and the compressed keys scored by the engine's counters, and
each prompt's chunks. New here, under ``counts.traced``: the counters of
the traced steps and the chunks dispatched in them (``chunk_rows``,
``chunk_pairs``, ``chunks``), which ``readers/sala_roofline.py`` reads.

The engine decodes by its step that keeps the logits it sampled from on
the device (``ServingEngine.keep_logits``: one more output, not
fetched), from the warm-up on. After the window and the drain the
clients stop, and the engine runs ``logit_steps`` more steps while the
driver fetches the logits of a sample of the requests in flight (the
one with the longest prompt among them): logits the served cache gave,
its pages, compressed keys and state rows as the timed path left them.
The reference (``reference/minicpm_sala.py``) teacher-forces those
requests over their prompts and every token served to them. ``correct``
holds the served tokens to it as the chunked cells do, by the gaps of
their logits below the reference's best (the widest and the mean), and
holds the fetched logits to the reference's at the same positions, by
the widest and the mean absolute difference (``logit_devs``).

A traced run also keeps, under ``counts.scopes``, the named scopes of
the compiled decode and chunk programs' instructions
(``scope_map``), which ``readers/_scoped.py`` joins to the trace's
ops.
"""

from __future__ import annotations

import gc
import os
import time
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W, weights_minicpm_sala as WS, work_minicpm_sala as wms
from perfbench.drivers.serve_engine_sparse_moe import gap_numbers, gaps_below_best, sized_pool
from perfbench.drivers.serve_engine_window_moe import pick_checked

# the named scopes of the new layers' ops (the first that an op's
# ``op_name`` holds, as a path component, names it)
SCOPES = (
    "attn_sparse_select", "attn_sparse_chunk", "attn_sparse", "attn_lightning_chunk", "attn_lightning",
)

# (name, keywords of reference.minicpm_sala.forward) of the control and
# of the faults a probing run reads beside the program's own number
PROBES = (
    ("control_fp8", {"quant": "fp8"}),
    ("fault_recent_blocks", {"fault": "recent_blocks"}),
    ("fault_no_decay", {"fault": "no_decay"}),
    ("fault_bf16_state", {"fault": "bf16_state"}),
    ("fault_no_window", {"fault": "no_window"}),
    ("fault_no_output_gate", {"fault": "no_output_gate"}),
    ("fault_no_mup", {"fault": "no_mup"}),
)
COUNTERS = (
    "lightning_state_updates", "sparse_selected_tokens", "sparse_live_tokens",
    "sparse_scored_kernels", "prefill_chunks", "admissions",
)


def build_model(cfg, max_len: int):
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.models.hf_interop import minicpm_sala_model_config
    from cs744_pytorch_distributed_tutorial_tpu.models.transformer import TransformerLM

    published, kept = wms.as_published(cfg)
    return TransformerLM(
        **minicpm_sala_model_config(published, max_seq_len=max_len, layer_ids=kept),
        dtype=jnp.dtype(cfg["compute_dtype"]),
    )


def answer_logits(cfg, flat, requests, **variant) -> list[Any]:
    """The reference's logits at the positions that produced each
    request's answer (teacher-forced: one pass over the prompt with its
    served tokens), [answer tokens, vocabulary] a request; with a
    ``variant``, the control's or a planted fault's."""
    from perfbench.reference import minicpm_sala

    out = []
    for prompt, answer in requests:
        toks = np.concatenate([prompt, np.asarray(answer, np.int32)])
        out.append(minicpm_sala.forward(flat, toks, cfg, at=np.arange(len(prompt) - 1, len(toks) - 1), **variant))
    return out


def counter_delta(after, before) -> dict[str, float]:
    """The engine's counters between two readings of ``stats()``."""
    out = {k: after[k] - before[k] for k in COUNTERS}
    out["decode_steps"] = after["decode_steps"] - before["decode_steps"]
    out["occupancy_steps"] = (
        after["slot_occupancy"] * after["decode_steps"] - before["slot_occupancy"] * before["decode_steps"]
    )
    return out


def logit_devs(truth, kept, prefix: str = "") -> dict[str, float]:
    """The widest and the mean absolute difference between the logits
    ``kept`` ([(answer index, logits row)] a request) and the
    reference's rows ``truth`` at the same answer tokens."""
    d = np.stack([
        np.abs(np.asarray(rows[m]) - row) for rows, pairs in zip(truth, kept) for m, row in pairs
    ])
    return {f"{prefix}decode_logit_dev": float(d.max()), f"{prefix}decode_logit_dev_mean": float(d.mean())}


def served_logits(engine, requests, steps: int) -> list[list[tuple[int, Any]]]:
    """Step ``engine`` ``steps`` times, no request sent, and keep for
    each of ``requests`` (in a slot throughout) every step's logits
    with the index of the answer token sampled from them."""
    kept: list[list[tuple[int, Any]]] = [[] for _ in requests]
    for _ in range(steps):
        engine.step()
        rows = engine.last_logit_rows
        got = np.asarray(engine.last_logits[np.asarray([rows[r.req_id] for r in requests])])
        for pairs, r, row in zip(kept, requests, got):
            pairs.append((len(r.generated) - 1, row))
    return kept


def scope_map(engine) -> dict[str, dict[str, str]]:
    """{compiled module: {instruction: scope}} of the engine's decode
    and chunk programs, for the instructions under one of ``SCOPES``
    (a fusion takes its largest matmul's scope, else its root's). The
    programs have run: JAX's cache answers."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.serve import layout
    from cs744_pytorch_distributed_tutorial_tpu.utils import profiling

    def scope_of(op_name: str, opcode: str) -> str:
        return next((sc for sc in SCOPES if f"/{sc}/" in op_name), "")

    sharding = jax.tree_util.tree_leaves(engine.params)[0].sharding
    out = {}
    for compiled in layout.compile_programs(engine, 0, sharding).values():
        module, scopes = profiling.phase_map(compiled.as_text(), scope_of, SCOPES + ("",))
        out[module] = {k: v for k, v in scopes.items() if v}
    return out


def chunk_work(prompts, chunk: int) -> dict[str, float]:
    """Rows, causal pairs inside chunks, and chunks of ``prompts``
    prefilled by chunks of ``chunk``."""
    sizes = [min(chunk, n - o) for n in prompts for o in range(0, n, chunk)]
    return {
        "chunk_rows": float(sum(sizes)), "chunk_pairs": float(sum(m * (m + 1) / 2 for m in sizes)),
        "chunks": float(len(sizes)),
    }


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
    chunk = int(tr["prefill_chunk"])
    model = build_model(cfg, int(tr["max_total_len"]))
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    flat = WS.make_weights(cfg, run.seed, cfg["compute_dtype"])
    params = W.fill_tree(template, flat)
    engine = ServingEngine(
        model, params,
        ServeConfig(
            num_slots=tr["num_slots"], page_size=tr["page_size"], num_pages=tr["num_pages"],
            max_pages_per_slot=tr["max_pages_per_slot"], temperature=tr["temperature"], seed=seed,
            prefill_chunk=chunk,
        ),
    )
    engine.keep_logits()
    prompts, answers = sized_pool(tr, run.seed)
    pools = jax.tree_util.tree_leaves(engine._pages)
    run.log(
        f"engine built; pool of {len(prompts)} requests; {len(pools)} pools and state rows, "
        f"{sum(p.nbytes for p in pools) / 1e9:.2f} GB; weights "
        f"{sum(w.nbytes for w in flat.values()) / 1e9:.2f} GB"
    )

    # ---- warm the chunk program and the decode step -------------------------
    rng = np.random.default_rng(seed + 1)
    engine.submit(Request(
        prompt=rng.integers(0, int(tr["token_id_below"]), chunk + 3, dtype=np.int32),
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
        end = time.monotonic()
        traced = counter_delta(engine.stats(), before)
        # the chunks the traced steps dispatched (an admission's chunks
        # all run inside the step that admits it)
        traced.update(chunk_work(
            [r.prompt.size for r in sent if r.first_token_time is not None and start <= r.first_token_time <= end],
            chunk,
        ))
    # Past the window only until each of its requests has its first token;
    # what surfaces now adds nothing to the window's token count.
    drain_deadline = time.monotonic() + 120.0
    while any(r.first_token_time is None and r.status is None for r in in_window):
        if time.monotonic() > drain_deadline:
            break
        for _ in engine.step():
            send()
    scopes = scope_map(engine) if traced is not None else {}
    # ---- the logits of requests in flight, the clients stopped -------------
    steps = int(tr["logit_steps"])

    def in_flight():
        return [r for r in sent if r.status is None and r.first_token_time is not None
                and r.preemptions == 0 and r.max_new_tokens - len(r.generated) > steps]

    while not in_flight():
        for _ in engine.step():
            send()
    picked = pick_checked(in_flight(), int(tr["check_requests"]), seed)
    kept = served_logits(engine, picked, steps)
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
    checked = [(np.asarray(r.prompt[: r.orig_prompt_len]), list(r.generated)) for r in picked]
    window = counter_delta(stats1, stats0)
    steps_w = window["decode_steps"]
    prefilled = [r.orig_prompt_len for r in sent
                 if r.first_token_time is not None and t0 < r.first_token_time <= t1]
    attention_flops = wms.attention_flops_in_window(window, prefilled, chunk, cfg)
    run.read_memory_peak()
    n_sent = len(in_window)
    preemptions = stats1["preemptions"]
    del engine, params, model, pools
    gc.collect()

    truth = answer_logits(cfg, flat, checked)
    served = [np.asarray(answer, np.int32) for _, answer in checked]
    gaps = gaps_below_best(truth, served)
    values = {
        **gap_numbers(gaps), **logit_devs(truth, kept), "tokens_compared": len(gaps),
        "requests_failed": failed, "longest_prompt_checked": max(len(p) for p, _ in checked),
    }
    run.log(f"reference done over {len(checked)} requests (prompts {[len(p) for p, _ in checked]}), "
            f"{len(gaps)} tokens: {gap_numbers(gaps)}, logits {logit_devs(truth, kept)}")
    if check.probing():
        wanted = os.environ["PERFBENCH_PROBE"].split(",")
        n_probed = 1  # the one of the longest prompt: a probe is a whole pass over up to 69k tokens
        for name, variant in PROBES:
            if wanted == ["1"] or name in wanted:
                rows = answer_logits(cfg, flat, checked[:n_probed], **variant)
                # the tokens the variant puts first, judged by the true
                # logits; its logits where the engine's were kept
                judged = [jnp.argmax(r, axis=-1) for r in rows]
                values.update(gap_numbers(gaps_below_best(truth, judged), f"{name}."))
                kept_v = [[(m, np.asarray(r[m])) for m, _ in pairs] for r, pairs in zip(rows, kept)]
                values.update(logit_devs(truth, kept_v, f"{name}."))
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
            # of the positions live at the decode steps' block-sparse
            # queries (a layer and a KV group each), those they attended
            "sparse_selected_share": window["sparse_selected_tokens"] / max(window["sparse_live_tokens"], 1),
            "window": window, "traced": traced, "scopes": scopes,
        },
        "spans": {
            "ttft_ms": ttft_all, "itl_ms": itl,
            "engine_step_s": list(np.diff(step_stamps)),
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "config": {**cfg, **wms.dense_equivalent(cfg)},
        "traffic": tr,
    }
