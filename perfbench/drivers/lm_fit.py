"""Driver for ``LMTrainer.fit()``: the entry ``lm_cli`` calls.

One trainer object serves the whole run. The benchmark's weights reach it
through the resume path (``memstore=``), its first three steps run as
``fit()`` calls that the reference follows, and the same object, with the
state those steps left, runs the window's ``fit()``. Times come from the
program's own fenced records (``sync_exit_mono`` in ``metrics.jsonl``).
"""

from __future__ import annotations

import gc
import json
import math
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W


class Handoff:
    """Stands where the program's in-memory snapshot tier stands: gives
    ``fit()`` the state to resume from and receives the state it ends
    with. The first state is the benchmark's own weights from the seed."""

    def __init__(self, flat_weights):
        self.flat = flat_weights
        self.state = None
        self.on_save = None

    def latest_step(self):
        return 0 if self.state is None else int(self.state.step)

    def restore_latest(self, template, adapt=None):
        import jax
        import jax.numpy as jnp

        # fit() has just run the program's eager init() for this template: a
        # whole forward pass at the batch's shape. What of it sits in
        # reference cycles is freed now, not when the collector happens by.
        gc.collect()
        if self.state is not None:
            return self.state
        flat, self.flat = self.flat, None

        def pick(kp, leaf):
            name = W.path_of(kp)
            if name.startswith("params/"):
                w = flat[name[len("params/"):]]
                if tuple(w.shape) != tuple(leaf.shape):
                    raise ValueError(f"{name}: made {w.shape}, program wants {leaf.shape}")
                return jax.device_put(w.astype(leaf.dtype), leaf.sharding)
            return jax.device_put(jnp.zeros(leaf.shape, leaf.dtype), leaf.sharding)

        n_params = sum(
            1 for kp, _ in jax.tree_util.tree_flatten_with_path(template)[0]
            if W.path_of(kp).startswith("params/")
        )
        if n_params != len(flat):
            raise ValueError(f"program has {n_params} parameter leaves, benchmark made {len(flat)}")
        return jax.tree_util.tree_map_with_path(pick, template)

    def save(self, state, *, step=None):
        self.state = state
        if self.on_save is not None:
            self.on_save(state)
        return int(state.step)


def _read_records(path):
    steps, system = {}, []
    with open(path, encoding="utf-8") as f:
        for ln in f:
            r = json.loads(ln)
            if r.get("kind") == "step":
                steps[int(r["step"])] = r
            elif r.get("kind") == "system":
                system.append(r)
    return steps, system


# The program's own seed stays fixed. It keys only what the benchmark
# replaces (init()'s weights) or switches off (dropout), but the step
# builder closes over it as a constant, so a new value is a new program
# and a whole compile (70 s on the chip, PR 24). Weights and tokens are
# the benchmark's, from --seed.
PROGRAM_SEED = 0


def lm_config(run, n_dev: int, metrics_dir: str):
    from cs744_pytorch_distributed_tutorial_tpu.train.lm import LMConfig

    c, tr, opt = run.config, run.traffic, run.config["optimizer"]
    kw: dict[str, Any] = {}
    if run.trace:
        # The traced steps follow the window inside the same fit(); where
        # they start is set once the window's length is known.
        kw = dict(profile_dir=str(run.trace_dir), profile_start_step=10**9,
                  profile_num_steps=int(tr["trace_steps"]))
    return LMConfig(
        vocab_size=c["vocab_size"], num_layers=c["n_layer"], num_heads=c["n_head"],
        d_model=c["n_embd"], d_ff=c["n_inner"], max_seq_len=c["n_positions"],
        attention_impl=c["attention_impl_train"], compute_dtype=c["compute_dtype"],
        data_parallel=n_dev, global_batch_size=tr["global_batch_per_chip"] * n_dev,
        seq_len=tr["seq_len"], learning_rate=opt["learning_rate"], seed=PROGRAM_SEED,
        optimizer=opt["name"], momentum=opt["b1"], weight_decay=opt["weight_decay"],
        tie_embeddings=c["tie_word_embeddings"],
        metrics_dir=metrics_dir, metrics_every=1, **kw,
    )


def reference_numbers(run, tokens, b: int, quant=None, half_batch=False):
    """The plain reference over the first steps: losses, first gradient's
    norms, norms of the change. ``quant`` makes it the control;
    ``half_batch`` plants the fault of half the rows left out."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import gpt2

    cfg, opt = run.config, run.config["optimizer"]
    p0 = W.make_weights("gpt2", cfg, run.seed, "float32")
    params = {k: jnp.copy(v) for k, v in p0.items()}
    state = gpt2.adamw_init(params)
    losses, grad_norms = [], None
    rows = 2 if run.rehearse else 4
    for k in range(int(run.traffic["check_steps"])):
        batch = jnp.asarray(tokens[k * b:(k + 1) * b])
        if half_batch:
            batch = batch[: b // 2]
        loss, grads = gpt2.loss_and_grads(params, batch[:, :-1], batch[:, 1:], cfg, quant, rows)
        losses.append(float(loss))
        if k == 0:
            grad_norms = check.leaf_norms(grads)
        params, state = gpt2.adamw_step(params, grads, state, opt)
        del grads
    update_norms = check.leaf_norms({k: params[k] - p0[k] for k in p0})
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": update_norms}


def run(run) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.train.lm import LMTrainer

    tr, cfg = run.traffic, run.config
    # A rehearsal takes as many of the cell's chips as the machine shows
    # devices (one, unless the CPU backend was told to show more).
    n_dev = run.chips if not run.rehearse else min(run.chips, len(jax.devices()))
    b, seq = int(tr["global_batch_per_chip"]) * n_dev, int(tr["seq_len"])
    n_check, discard = int(tr["check_steps"]), int(tr["discard_steps"])
    first_window_step = n_check + discard
    hint = float(tr["step_s_hint"])
    n_traced = int(tr["trace_steps"]) if run.trace else 0
    max_steps = first_window_step + int(math.ceil(run.seconds / (hint * 0.5))) + 1 + n_traced
    tokens = T.lm_tokens(run.seed, b * max_steps, seq, int(tr["token_id_below"]))
    metrics_dir = str(run.tmp / "records")

    handoff = Handoff(W.make_weights("gpt2", cfg, run.seed, "float32"))
    trainer = LMTrainer(lm_config(run, n_dev, metrics_dir), memstore=handoff)
    run.log("trainer built")

    # ---- the first steps, through fit(): what `correct` compares --------
    prog: dict[str, Any] = {"losses": []}
    b1 = float(cfg["optimizer"]["b1"])

    def first_gradient(state):
        mu = {
            k.split("/mu/", 1)[1]: v
            for k, v in W.flatten_tree(state.opt_state).items() if "/mu/" in "/" + k
        }
        prog["grad_norms"] = {k: v / (1.0 - b1) for k, v in check.leaf_norms(mu).items()}

    def change(state):
        # The weights given to fit() were donated with the state; make
        # them again from the seed (same jitted call, same values).
        p, p0 = W.flatten_tree(state.params), W.make_weights("gpt2", cfg, run.seed, "float32")
        prog["update_norms"] = check.leaf_norms({k: p[k] - p0[k] for k in p0})

    # Two short fit() calls: one step, so that the state it ends with shows
    # the first gradient, then the rest of the first steps. (One fit() with
    # the snapshot tier at a cadence of one step would save a set-up, but
    # its pending copy of the state, 1.95 GB beside the template fit()
    # holds, leaves the step's 10 GB of temporaries no room: on the chip,
    # RESOURCE_EXHAUSTED at the second step, PR 24.)
    handoff.on_save = first_gradient
    _, _, losses = trainer.fit(tokens, 1)
    prog["losses"] += losses
    run.log(f"step 1 done, loss {losses[-1]:.5f}")
    handoff.on_save = change
    _, _, losses = trainer.fit(tokens, n_check)
    prog["losses"] += losses
    handoff.on_save = None
    steps, _ = _read_records(f"{metrics_dir}/metrics.jsonl")
    cal = steps[n_check - 1]["sync_exit_mono"] - steps[n_check - 2]["sync_exit_mono"]
    step_s = cal if hint / 3 < cal < hint * 3 else hint
    n_window = min(max(int(math.ceil(run.seconds / step_s)), 2), max_steps - first_window_step - n_traced)
    total = first_window_step + n_window
    run.log(f"first {n_check} steps done; step {cal * 1e3:.1f} ms -> window of {n_window} steps")

    # ---- the window: one fit() holds the discarded steps and the window -
    compiles_before = run.compiles.count
    # In a traced run the same fit() goes on past the window's closing
    # fence for the traced steps, so no tracing falls inside the window.
    trainer.cfg.profile_start_step = total
    trainer.fit(tokens, total + n_traced)
    compiles_in_fit = run.compiles.count - compiles_before
    steps, system = _read_records(f"{metrics_dir}/metrics.jsonl")
    stamps = [steps[s]["sync_exit_mono"] for s in range(first_window_step - 1, total)]
    w0, w1 = stamps[0], stamps[-1]
    in_window = [r["compile_count"] for r in system
                 if first_window_step - 1 <= r.get("step", -1) < total]
    compiles_in_window = (in_window[-1] - in_window[0]) if len(in_window) > 1 else 0
    rate = n_window * b * seq / (w1 - w0) / n_dev
    series = T.series_summary(stamps, compiles_in_window)
    series["compiles_in_fit"] = compiles_in_fit
    run.log(f"window {w1 - w0:.2f} s, {rate:.1f} tokens/s/chip")

    # ---- peak memory, then free the program's state, then the reference -
    extra = 0
    if not run.rehearse:
        st = handoff.state
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        x, y = trainer.shard_batch(tokens[:b])
        ma = trainer.jitted_train_step.lower(
            jax.tree.map(sds, st.params), jax.tree.map(sds, st.opt_state), sds(x), sds(y),
            jax.ShapeDtypeStruct((), jnp.int32),
        ).compile().memory_analysis()
        extra = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        del x, y, st
    run.read_memory_peak(extra)
    handoff.state = None
    del trainer, handoff
    gc.collect()

    ref = reference_numbers(run, tokens, b)
    values, where = check.training_numbers(prog, ref)
    readings = {"program": prog, "reference": ref}
    for name, kw in check.probe_variants(n_dev):
        readings[name] = reference_numbers(run, tokens, b, **kw)
        other, _ = check.training_numbers(readings[name], ref)
        values.update({f"{name}.{k}": v for k, v in other.items()})
    check.dump_probe(run, readings)
    verdict = check.judge(values, run.limits(), where)
    run.log(f"reference done: {values} at {where}")

    input_fetch = [
        steps[s]["sync_enter_mono"] - steps[s - 1]["sync_exit_mono"]
        for s in range(first_window_step, total)
    ]
    return {
        "end_to_end": {"train_tokens_per_s_per_chip": rate},
        "window_start_mono": w0,
        "window_s": w1 - w0,
        "series": series,
        "attempted": n_window,
        "failed": 0,
        "check": verdict,
        "counts": {"window_steps": n_window, "tokens_per_step": b * seq, "losses": prog["losses"]},
        "spans": {
            "step_gaps_s": list(np.diff(stamps)),
            "host_between_fences_s": input_fetch,
        },
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "batch": b, "seq_len": seq, "steps_traced": int(tr["trace_steps"]),
        "config": cfg, "traffic": tr,
    }
