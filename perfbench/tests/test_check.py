"""``correct`` has been shown to fail: the control (the reference in the
nearest lower precision, put in the program's place) fails a limit, and
so does a run whose timed path is broken underneath, once for each fault
a cell can have. These drive the harness's whole run but its look for a
chip, at the rehearsal's size; the readings at the cells' own sizes, on
the chip, are in PERF.md."""

from pathlib import Path

import numpy as np
import pytest

from perfbench import check
from perfbench.tests.cells import DP4

ROOT = Path(__file__).resolve().parents[2]
TRAIN = ["resnet18-b4096-1chip", "gpt2s-train-t1024-1chip"]
SERVE = "gpt2s-serve-closed-1chip"


def failed(line):
    return sorted(k for k, n in line["check"].items() if not n["ok"])


# ---- the arithmetic of the comparison -------------------------------------

def test_norm_gap_is_of_the_norms_against_the_larger_of_leaf_and_median():
    ref = {"a": 1.0, "b": 10.0, "tiny": 1e-9}
    prog = {"a": 1.1, "b": 10.0, "tiny": 2e-9}
    worst, where = check.norm_gap(prog, ref)
    # "tiny" doubled, but against the median leaf (1.0) that is nothing
    assert where == "a" and worst == pytest.approx(0.1)
    assert check.moving_leaves({"a": 1.0, "b": 2.0, "c": 3.0, "dead": 1e-4}) == {"a", "b", "c"}
    assert check.loss_gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)


def test_judge_holds_each_number_to_its_own_limit():
    v = check.judge({"x": 0.5, "y": float("nan"), "shown": 9.0}, {"x": 1.0, "y": 1.0})
    assert v["correct"] is False and v["numbers"]["x"]["ok"] and not v["numbers"]["y"]["ok"]
    assert v["numbers"]["shown"] == {"value": 9.0, "limit": None, "ok": True}
    with pytest.raises(KeyError):
        check.judge({"x": 0.5}, {"missing": 1.0})


# ---- the control ------------------------------------------------------------

@pytest.mark.parametrize("cell", TRAIN + [SERVE])
def test_the_control_fails_where_the_program_passes(rehearse, cell):
    rc, line, _ = rehearse(cell, probe=True)
    assert rc == 0 and line["correct"] is True, failed(line)
    limits = {k: n["limit"] for k, n in line["check"].items() if n["limit"] is not None}
    control = {k.removeprefix("control_fp8."): n["value"]
               for k, n in line["check"].items() if k.startswith("control_fp8.")}
    over = [k for k, lim in limits.items() if k in control and control[k] > lim]
    assert over, f"float8 in the program's place passed every limit: {control} under {limits}"


# ---- the timed path broken underneath ---------------------------------------

def _keep_state(step):
    """The step runs, and its caller gets back the state it passed in."""
    import jax
    import jax.numpy as jnp

    def broken(*args, **kw):
        n_state = 2 if len(args) >= 5 or "tokens" in kw else 1  # LM: params, opt_state; CIFAR: state
        kept = jax.tree.map(jnp.copy, args[:n_state])  # the step donates its inputs
        out = step(*args, **kw)
        return (*kept, *out[n_state:])

    return broken


def _halve(x, replicas=1):
    """Each replica's second half of rows replaced by its first half: half
    of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    per = x.shape[0] // replicas
    idx = np.concatenate([np.tile(np.arange(r * per, r * per + per // 2), 2) for r in range(replicas)])
    return jnp.asarray(x)[idx] if not isinstance(x, np.ndarray) else x[idx]


def _break_lm(monkeypatch, fault):
    from cs744_pytorch_distributed_tutorial_tpu.train import lm

    init = lm.LMTrainer.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        if fault == "state_unchanged":
            self.train_step = _keep_state(self.train_step)
        elif fault == "half_batch":
            shard = self.shard_batch
            self.shard_batch = lambda tokens: shard(_halve(np.asarray(tokens)))

    monkeypatch.setattr(lm.LMTrainer, "__init__", patched)


def _break_cifar(monkeypatch, fault, replicas=1):
    from cs744_pytorch_distributed_tutorial_tpu.train import engine

    init = engine.Trainer.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self.train_step
        if fault == "state_unchanged":
            self.train_step = _keep_state(step)
        elif fault == "half_batch":
            self.train_step = lambda s, x, y, k: step(s, _halve(x, replicas), _halve(y, replicas), k)
        elif fault == "other_chips_rows_left_out":
            # sync=auto leaves the exchange to autodiff inside one compiled
            # program, so it cannot be cut from outside; what it carries
            # can: every chip is fed chip 0's rows, and the other chips'
            # rows never reach the update.
            def first_chip_only(x):
                per = x.shape[0] // replicas
                return x[np.tile(np.arange(per), replicas)]

            self.train_step = lambda s, x, y, k: step(s, first_chip_only(x), first_chip_only(y), k)

    monkeypatch.setattr(engine.Trainer, "__init__", patched)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_lm_fault_is_caught(rehearse, monkeypatch, fault):
    _break_lm(monkeypatch, fault)
    rc, line, _ = rehearse("gpt2s-train-t1024-1chip")
    assert rc == 0 and line["correct"] is False
    assert "update_norm_gap" in failed(line) or "grad_norm_gap" in failed(line)
    if fault == "state_unchanged":
        assert line["check"]["update_norm_gap"]["value"] == pytest.approx(1.0)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_cifar_fault_is_caught(rehearse, monkeypatch, fault):
    _break_cifar(monkeypatch, fault)
    rc, line, _ = rehearse("resnet18-b4096-1chip")
    assert rc == 0 and line["correct"] is False and failed(line)
    if fault == "state_unchanged":
        assert line["check"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_four_chip_fault_is_caught(rehearse, monkeypatch):
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    _break_cifar(monkeypatch, "other_chips_rows_left_out", replicas=4)
    rc, line, _ = rehearse(DP4)
    assert rc == 0 and line["correct"] is False, line["check"]


def test_altered_token_is_caught(rehearse, monkeypatch):
    from cs744_pytorch_distributed_tutorial_tpu.serve import engine

    build = engine.ServingEngine._build_decode_step
    calls = {"n": 0}

    def patched(self):
        step = build(self)

        def altered(*a, **kw):
            pages, toks = step(*a, **kw)
            calls["n"] += 1
            if calls["n"] % 3 == 0:  # every third step, every slot: the next id up
                toks = (toks + 1) % 500
            return pages, toks

        return altered

    monkeypatch.setattr(engine.ServingEngine, "_build_decode_step", patched)
    rc, line, _ = rehearse(SERVE)
    assert rc == 0 and line["correct"] is False and failed(line) == ["served_logit_gap"]
