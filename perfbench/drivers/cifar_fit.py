"""Driver for ``Trainer.fit()``: the entry ``cli`` calls, through loader,
native batcher, prefetch and on-device augmentation.

One trainer object serves the whole run. It is given the benchmark's
weights as the ``state=`` a resumed run would pass, runs its first three
steps as short ``fit()`` calls that the reference follows, and then, with
the state those left, the window's ``fit()`` at the CLI's default record
cadence (``log_every=20``), so the loop that runs ahead of the device is
the one measured. Times are the program's own fenced records.
"""

from __future__ import annotations

import gc
import json
import math
from typing import Any

import numpy as np

from perfbench import check, traffic as T, weights as W


def epoch_order(n: int, seed: int, epoch: int = 0) -> np.ndarray:
    """The configuration's stated data order: a permutation that is a pure
    function of (seed, epoch), as DistributedSampler's."""
    return np.random.default_rng((seed, epoch)).permutation(n)


def _records(path) -> list[dict[str, list[dict]]]:
    """Step and system records, one group per fit() (each starts again at
    step 0; a system record follows the step record it was taken at)."""
    fits: list[dict[str, list[dict]]] = []
    with open(path, encoding="utf-8") as f:
        for ln in f:
            r = json.loads(ln)
            if r.get("kind") == "step" and r["step"] == 0:
                fits.append({"step": [], "system": []})
            if r.get("kind") in ("step", "system") and fits:
                fits[-1][r["kind"]].append(r)
    return fits


def make_state(trainer, flat):
    """The program's state container, filled with the benchmark's weights:
    running statistics at (0, 1), momentum at zero, step 0."""
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.train.state import init_state

    cfg = trainer.cfg
    sample = jnp.zeros((1, cfg.image_size, cfg.image_size, 3), jnp.float32)
    abstract = jax.eval_shape(
        lambda: init_state(trainer.model, trainer.tx, jax.random.key(0), sample, trainer.axis_size)
    )
    n_params = len(jax.tree_util.tree_leaves(abstract.params))
    if n_params != len(flat):
        raise ValueError(f"program has {n_params} parameter leaves, benchmark made {len(flat)}")

    def pick(kp, leaf):
        name = W.path_of(kp)
        if name.startswith("params/"):
            w = flat[name[len("params/"):]]
            if tuple(w.shape) != tuple(leaf.shape):
                raise ValueError(f"{name}: made {w.shape}, program wants {leaf.shape}")
            return w.astype(leaf.dtype)
        fill = jnp.ones if name.endswith("/var") else jnp.zeros
        return fill(leaf.shape, leaf.dtype)

    return trainer.place_state(jax.tree_util.tree_map_with_path(pick, abstract))


def reference_numbers(run, batches, n_dev: int, quant=None, half_batch=False, exchange=True):
    """The plain reference over the first steps. ``quant`` makes it the
    control; ``half_batch`` and ``exchange=False`` plant faults."""
    import jax
    import jax.numpy as jnp

    from perfbench.reference import resnet18

    cfg, opt = run.config, run.config["optimizer"]
    p0 = W.make_weights("resnet18", cfg, run.seed, "float32")
    params = dict(p0)
    trace = resnet18.sgd_init(params)
    key = jax.random.key(W.seed31(run.seed))
    # Each replica's share on a chip of its own, where the cell has several.
    devices = jax.local_devices()[:n_dev] if n_dev > 1 else None
    losses, grad_norms = [], None
    for k, (x, y) in enumerate(batches):
        if devices is None:
            x, y = jnp.asarray(x), jnp.asarray(y)
        replicas = n_dev
        if half_batch:
            per = x.shape[0] // n_dev
            keep = np.concatenate([np.arange(r * per, r * per + per // 2) for r in range(n_dev)])
            x, y = x[keep], y[keep]
        loss, grads = resnet18.loss_and_grads(
            params, x, y, jax.random.fold_in(key, k), cfg, replicas, quant, exchange, devices
        )
        losses.append(float(loss))
        if k == 0:
            grad_norms = check.leaf_norms(grads)
        params, trace = resnet18.sgd_step(params, grads, trace, opt)
        del grads
    update_norms = check.leaf_norms({k: params[k] - p0[k] for k in p0})
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": update_norms}


def run(run) -> dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data.cifar10 import CIFAR10Dataset
    from cs744_pytorch_distributed_tutorial_tpu.train.engine import Trainer

    tr, cfg, opt = run.traffic, run.config, run.config["optimizer"]
    # A rehearsal takes as many of the cell's chips as the machine shows
    # devices (one, unless the CPU backend was told to show more).
    n_dev = run.chips if not run.rehearse else min(run.chips, len(jax.devices()))
    B = int(tr["per_chip_batch"]) * n_dev
    n_check, discard, every = int(tr["check_steps"]), int(tr["discard_steps"]), int(tr["log_every"])
    hint = float(tr["step_s_hint"])
    max_chunks = int(math.ceil(run.seconds / (hint * 0.5) / every))
    # The first steps' rows now; the window's once its length is known.
    images, labels = T.cifar_rows(run.seed, B * n_check, cfg["image_size"], cfg["num_classes"])
    seed = W.seed31(run.seed)
    metrics_dir = str(run.tmp / "records")
    kw: dict[str, Any] = {}
    n_traced = int(tr["trace_steps"]) if run.trace else 0
    if run.trace:
        # The traced steps follow the window inside the same fit(); where
        # they start is set once the window's length is known.
        kw = dict(profile_dir=str(run.trace_dir), profile_start_step=10**9, profile_num_steps=n_traced)
    tcfg = TrainConfig(
        model=cfg["family"], num_classes=cfg["num_classes"], image_size=cfg["image_size"],
        compute_dtype=cfg["compute_dtype"], sync=cfg["sync"],
        sync_bn=cfg["sync_bn"], augment=cfg["augment"], global_batch_size=B,
        learning_rate=opt["learning_rate"], momentum=opt["momentum"], weight_decay=opt["weight_decay"],
        optimizer=opt["name"], seed=seed, epochs=1, num_devices=n_dev, synthetic_data=True,
        log_every=every, metrics_every=1, metrics_dir=metrics_dir, **kw,
    )
    trainer = Trainer(tcfg)
    replicated = NamedSharding(trainer.mesh, P())
    state = make_state(trainer, W.make_weights("resnet18", cfg, run.seed, "float32", replicated))
    run.log("trainer and state built")

    def dataset(lo_batch: int, n_batches: int):
        """One epoch of ``n_batches`` batches; its eval is 16 images long."""
        sl = slice(lo_batch * B, (lo_batch + n_batches) * B)
        return CIFAR10Dataset(images[sl], labels[sl], images[:16], labels[:16], synthetic=True)

    def fed(lo_batch: int, n_batches: int):
        """The batches fit() draws from dataset(lo_batch, n_batches)."""
        order = epoch_order(n_batches * B, seed) + lo_batch * B
        return [(images[order[i * B:(i + 1) * B]], labels[order[i * B:(i + 1) * B]]) for i in range(n_batches)]

    # ---- the first steps, through fit(): what `correct` compares --------
    wd = float(opt["weight_decay"])
    state, _ = trainer.fit(dataset(0, 1), state=state)
    p0 = W.make_weights("resnet18", cfg, run.seed, "float32", replicated)
    tr1 = {k.split("/trace/", 1)[1]: v for k, v in W.flatten_tree(state.opt_state).items() if "/trace/" in "/" + k}
    prog: dict[str, Any] = {
        # torch-style SGD: after one step the momentum holds g + wd * p0.
        "grad_norms": check.leaf_norms({k: tr1[k] - wd * p0[k] for k in p0})
    }
    del tr1
    state, _ = trainer.fit(dataset(1, n_check - 1), state=state)
    p = W.flatten_tree(state.params)
    prog["update_norms"] = check.leaf_norms({k: p[k] - p0[k] for k in p0})
    del p, p0
    fits = _records(f"{metrics_dir}/metrics.jsonl")
    prog["losses"] = [r["loss"] for f in fits for r in f["step"]]
    check_batches = fed(0, 1) + fed(1, n_check - 1)
    last = fits[1]["step"]
    cal = last[-1]["sync_exit_mono"] - last[-2]["sync_exit_mono"] if len(last) > 1 else hint
    step_s = cal if hint / 3 < cal < hint * 3 else hint
    chunks = min(max(int(math.ceil(run.seconds / step_s / every)), 1), max_chunks)
    n_window = chunks * every
    run.log(f"first {n_check} steps done, losses {prog['losses']}; step {cal * 1e3:.1f} ms -> window of {n_window} steps")

    # ---- the window: one fit() at the CLI's cadence ----------------------
    trainer.cfg.metrics_every = 0
    # In a traced run the same fit() goes on past the window's closing
    # fence for the traced steps, so no tracing falls inside the window.
    trainer.cfg.profile_start_step = discard + n_window + 1
    n_fit = discard + n_window + 1 + (n_traced + 1 if run.trace else 0)
    images, labels = T.cifar_rows([run.seed, 1], B * n_fit, cfg["image_size"], cfg["num_classes"])
    run.log(f"{B * n_fit} rows made for the window's fit() ({images.nbytes / 2**30:.2f} GiB)")
    compiles_before = run.compiles.count
    state, _ = trainer.fit(dataset(0, n_fit), state=state)
    compiles_in_fit = run.compiles.count - compiles_before
    fit = _records(f"{metrics_dir}/metrics.jsonl")[-1]
    recs = {r["step"]: r for r in fit["step"]}
    marks = list(range(discard, discard + n_window + 1, every))
    stamps = [recs[s]["sync_exit_mono"] for s in marks]
    w0, w1 = stamps[0], stamps[-1]
    sys_in = [r["compile_count"] for r in fit["system"] if marks[0] <= r.get("step", -1) <= marks[-1]]
    compiles_in_window = (sys_in[-1] - sys_in[0]) if len(sys_in) > 1 else compiles_in_fit
    rate = n_window * B / (w1 - w0) / n_dev
    series = T.series_summary(stamps, compiles_in_window, f"{every}-step chunks")
    series["compiles_in_fit"] = compiles_in_fit
    run.log(f"window {w1 - w0:.2f} s, {rate:.1f} samples/s/chip; series {series}")

    # ---- peak memory, then free the program's state, then the reference -
    extra = 0
    if not run.rehearse:
        sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
        from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import shard_global_batch

        x, y = shard_global_batch(trainer.mesh, images[:B], labels[:B])
        key = jax.device_put(jax.random.key(seed), replicated)
        ma = trainer.train_step.lower(jax.tree.map(sds, state), sds(x), sds(y), key).compile().memory_analysis()
        extra = int(ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        del x, y, key
    run.read_memory_peak(extra)
    del state, trainer, images, labels
    gc.collect()

    ref = reference_numbers(run, check_batches, n_dev)
    values, where = check.training_numbers(prog, ref)
    readings = {"program": prog, "reference": ref}
    for name, kw in check.probe_variants(n_dev):
        readings[name] = reference_numbers(run, check_batches, n_dev, **kw)
        other, _ = check.training_numbers(readings[name], ref)
        values.update({f"{name}.{k}": v for k, v in other.items()})
    check.dump_probe(run, readings)
    verdict = check.judge(values, run.limits(), where)
    run.log(f"reference done: {values} at {where}")

    return {
        "end_to_end": {"train_samples_per_s_per_chip": rate},
        "window_start_mono": w0,
        "window_s": w1 - w0,
        "series": series,
        "attempted": n_window,
        "failed": 0,
        "check": verdict,
        "counts": {"window_steps": n_window, "samples_per_step": B, "losses": prog["losses"]},
        "spans": {"chunk_gaps_s": list(np.diff(stamps))},
        "compile_s": run.compiles.seconds,
        "compiles_in_window": compiles_in_window,
        "batch": B, "steps_traced": int(tr["trace_steps"]),
        "config": cfg, "traffic": tr,
    }
