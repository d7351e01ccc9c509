"""graftelastic: the multi-process elastic runtime
(``parallel/multihost.py`` + ``launch.py``).

Fast units pin each layer in isolation — the rendezvous store's
membership records, deterministic coordinator re-election, the
collective watchdog's bounded conversion of "blocked on a dead peer"
into ``ProcessLossError``, identity-label resolution (and the log
prefix built from it), and ``process_kill`` chaos targeting.

The slow tests are the acceptance e2es: a 4-process ``launch_local``
run survives SIGKILL of (i) a non-coordinator rank and (ii) the
coordinator itself — deterministic re-election, generation g+1 on the
shrunk world, disk resume, and a full loss trajectory matching an
uninterrupted shrunk-world oracle at rtol 1e-6. The multihost-smoke CI
job runs this file without the tier-1 ``-m 'not slow'`` filter.
"""

import logging
import os
import re
import signal
import subprocess
import sys
import time

import pytest

from cs744_pytorch_distributed_tutorial_tpu.parallel.multihost import (
    EXIT_PROCESS_LOSS,
    CollectiveWatchdog,
    RendezvousStore,
    WorkerContext,
    env_context,
    plan_next_generation,
    reset_runtime_labels,
    runtime_labels,
    set_runtime_labels,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
    ProcessLossError,
)


@pytest.fixture
def clean_labels():
    reset_runtime_labels()
    yield
    reset_runtime_labels()


# ------------------------------------------------------------- election
def test_reelection_plan_non_coordinator_death():
    world = {"generation": 0, "ranks": [0, 1, 2, 3], "coordinator_rank": 0}
    plan = plan_next_generation(world, dead=[2])
    assert plan == {
        "generation": 1,
        "ranks": [0, 1, 3],  # global ranks kept; process ids = position
        "coordinator_rank": 0,
        "parent_generation": 0,
        "dead": [2],
    }


def test_reelection_plan_coordinator_death_elects_lowest_survivor():
    world = {"generation": 0, "ranks": [0, 1, 2, 3], "coordinator_rank": 0}
    plan = plan_next_generation(world, dead=[0])
    assert plan["coordinator_rank"] == 1
    assert plan["ranks"] == [1, 2, 3]
    # Deterministic: every caller computes the identical plan.
    assert plan == plan_next_generation(world, dead=[0])
    # Cascading losses across generations keep the rule stable.
    again = plan_next_generation(plan, dead=[1])
    assert again["generation"] == 2
    assert again["coordinator_rank"] == 2
    assert again["ranks"] == [2, 3]


def test_reelection_plan_total_loss_has_no_coordinator():
    world = {"generation": 3, "ranks": [5, 7], "coordinator_rank": 5}
    plan = plan_next_generation(world, dead=[5, 7])
    assert plan["ranks"] == [] and plan["coordinator_rank"] is None


# ---------------------------------------------------------------- store
def test_rendezvous_store_world_heartbeat_death_roundtrip(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    assert store.latest_generation() is None
    spec = {"generation": 0, "ranks": [0, 1, 2], "coordinator_rank": 0}
    store.write_world(spec)
    store.write_world({"generation": 1, "ranks": [1, 2],
                       "coordinator_rank": 1})
    assert store.read_world(0) == spec
    assert store.latest_generation() == 1
    assert store.read_world(9) is None

    # Heartbeats: None before the first beat (startup grace is the
    # supervisor's concern), a small age right after one.
    assert store.heartbeat_age(0, 1) is None
    store.heartbeat(0, 1, step=4)
    age = store.heartbeat_age(0, 1)
    assert age is not None and 0 <= age < 5

    # Death notes merge across writes and are per-generation.
    store.mark_dead(0, [2])
    store.mark_dead(0, [0, 2])
    assert store.dead(0) == {0, 2}
    assert store.dead(1) == set()


def test_store_events_stamped_with_runtime_labels(tmp_path, clean_labels):
    store = RendezvousStore(str(tmp_path / "store"))
    set_runtime_labels(
        process_id=1, process_count=3, generation=2, global_rank=3
    )
    store.append_event("reelection", survivors=[1, 3])
    [ev] = store.events()
    assert ev["kind"] == "event" and ev["event"] == "reelection"
    assert ev["survivors"] == [1, 3]
    assert (ev["process_id"], ev["generation"], ev["global_rank"]) == (1, 2, 3)


# -------------------------------------------------------------- context
def test_worker_context_env_roundtrip():
    ctx = WorkerContext(
        store_dir="/tmp/s", generation=2, process_id=1, num_processes=3,
        coordinator="127.0.0.1:5000", global_rank=3,
    )
    assert env_context(ctx.env()) == ctx
    assert env_context({}) is None  # no contract -> single-process run


def test_runtime_labels_resolution_order(clean_labels, monkeypatch):
    # Default: single-process coordinates.
    assert runtime_labels() == {
        "process_id": 0, "process_count": 1, "generation": 0,
        "global_rank": 0,
    }
    # Supervisor environment.
    monkeypatch.setenv("GRAFT_ELASTIC_RANK", "1")
    monkeypatch.setenv("GRAFT_ELASTIC_WORLD", "3")
    monkeypatch.setenv("GRAFT_ELASTIC_GENERATION", "1")
    monkeypatch.setenv("GRAFT_ELASTIC_GLOBAL_RANK", "2")
    assert runtime_labels() == {
        "process_id": 1, "process_count": 3, "generation": 1,
        "global_rank": 2,
    }
    # Explicit labels (set at each elastic re-init) outrank the env.
    set_runtime_labels(
        process_id=0, process_count=2, generation=4, global_rank=3
    )
    assert runtime_labels() == {
        "process_id": 0, "process_count": 2, "generation": 4,
        "global_rank": 3,
    }


def test_log_prefix_re_resolves_per_record(clean_labels):
    """The satellite fix: ``[proc i/n]`` is computed per-record from
    ``runtime_labels`` — a survivor re-labelled at generation g+1 logs
    its NEW coordinates (with a gN suffix), not its birth ones."""
    from cs744_pytorch_distributed_tutorial_tpu.utils.logging import (
        _RankPrefixFilter,
    )

    filt = _RankPrefixFilter()

    def prefix():
        rec = logging.LogRecord(
            "graft", logging.INFO, __file__, 1, "msg", (), None
        )
        assert filt.filter(rec)
        return rec.rank_prefix

    set_runtime_labels(
        process_id=2, process_count=4, generation=0, global_rank=2
    )
    assert prefix() == "[proc 2/4] "  # generation 0: no suffix
    set_runtime_labels(
        process_id=1, process_count=3, generation=1, global_rank=2
    )
    assert prefix() == "[proc 1/3 g1] "  # re-resolved after re-init
    reset_runtime_labels()
    assert prefix() == ""  # single-process: stay quiet


# ------------------------------------------------------------- watchdog
def _ctx(tmp_path, *, generation=0, global_rank=0, world=2):
    return WorkerContext(
        store_dir=str(tmp_path / "store"), generation=generation,
        process_id=global_rank, num_processes=world,
        coordinator="127.0.0.1:1", global_rank=global_rank,
    )


def test_watchdog_converts_blocked_section_to_loss_within_deadline(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    store.write_world({"generation": 0, "ranks": [0, 1],
                       "coordinator_rank": 0})
    store.mark_dead(0, [1])
    losses = []
    wd = CollectiveWatchdog(
        store, _ctx(tmp_path), deadline_s=0.4, on_loss=losses.append,
        poll_s=0.05,
    )
    try:
        t0 = time.monotonic()
        with wd.watch():
            while not losses and time.monotonic() - t0 < 5:
                time.sleep(0.05)  # stand-in for "blocked in a psum"
        elapsed = time.monotonic() - t0
        # The acceptance bound: fired, and BOUNDED — after the deadline,
        # well before "indefinitely".
        assert wd.fired == 1
        assert 0.4 <= elapsed < 3.0, elapsed
        [err] = losses
        assert isinstance(err, ProcessLossError)
        assert err.generation == 0 and err.dead == (1,)
        events = [
            e for e in store.events() if e["event"] == "process_loss"
        ]
        assert len(events) == 1 and events[0]["dead"] == [1]
        assert events[0]["elapsed_s"] >= 0.4
    finally:
        wd.close()


def test_watchdog_without_dead_peer_rearms_instead_of_firing(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    store.write_world({"generation": 0, "ranks": [0, 1],
                       "coordinator_rank": 0})
    losses = []
    wd = CollectiveWatchdog(
        store, _ctx(tmp_path), deadline_s=0.2, on_loss=losses.append,
        poll_s=0.05, stale_after_s=60.0,
    )
    try:
        deadline = time.monotonic() + 0.8
        with wd.watch():
            while time.monotonic() < deadline:
                store.heartbeat(0, 1)  # peer is slow, not dead
                time.sleep(0.05)
        assert wd.fired == 0 and losses == []  # compile != process loss
    finally:
        wd.close()


def test_watchdog_death_evidence_notes_and_stale_heartbeats(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    store.write_world({"generation": 0, "ranks": [0, 1, 2, 3],
                       "coordinator_rank": 0})
    wd = CollectiveWatchdog(
        store, _ctx(tmp_path, world=4), deadline_s=30.0,
        on_loss=lambda e: None, stale_after_s=0.1, poll_s=5.0,
    )
    try:
        # Rank 3 never beat: still importing — NOT evidence of death.
        store.heartbeat(0, 2)
        assert wd.dead_peers() == []
        time.sleep(0.3)  # rank 2's beat goes stale
        store.mark_dead(0, [1])  # supervisor's death note
        assert wd.dead_peers() == [1, 2]
        # check() is the synchronous, catchable path between steps.
        with pytest.raises(ProcessLossError) as exc:
            wd.check()
        assert exc.value.dead == (1, 2)
    finally:
        wd.close()


def test_exit_code_constant_is_distinctive():
    # The supervisor classifies EXIT_PROCESS_LOSS as a survivor exit;
    # it must never collide with the codes it reads as death (-9) or
    # plain success.
    assert EXIT_PROCESS_LOSS not in (0, 1, -9, 128 + signal.SIGKILL)


# ------------------------------------------------------ chaos targeting
class _FakeTrainer:
    def __init__(self):
        self.steps = 0

    def train_step(self, *a, **k):
        self.steps += 1
        return ("state", {"loss": 1.0})


def test_process_kill_fires_only_on_matching_rank(monkeypatch):
    from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
        ChaosMonkey,
        FaultSchedule,
    )

    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append((pid, sig)))

    # A non-target rank steps straight through the scheduled call.
    bystander = _FakeTrainer()
    ChaosMonkey(
        FaultSchedule({2: {"kind": "process_kill", "rank": 0}}), rank=1
    ).install(bystander)
    for _ in range(4):
        bystander.train_step()
    assert bystander.steps == 4 and kills == []

    # The target rank SIGKILLs itself at exactly the scheduled call.
    victim = _FakeTrainer()
    monkey = ChaosMonkey(
        FaultSchedule({2: {"kind": "process_kill", "rank": 0}}), rank=0
    )
    monkey.install(victim)
    victim.train_step()
    victim.train_step()
    assert kills == []
    victim.train_step()  # call index 2
    assert kills == [(os.getpid(), signal.SIGKILL)]
    assert monkey.injected == [(2, "process_kill")]


def test_process_kill_first_call_keeps_absolute_step_keys(monkeypatch):
    """A re-exec'd survivor resuming at step K passes ``first_call=K``:
    schedule keys stay ABSOLUTE step indices, and a re-parsed spec
    whose target died in a previous generation can never re-fire."""
    from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
        ChaosMonkey,
        FaultSchedule,
    )

    kills = []
    monkeypatch.setattr(os, "kill", lambda pid, sig: kills.append(sig))

    # Same schedule, re-parsed at generation 1; the dead rank 2 is gone
    # and every survivor skips the spec at its original absolute index.
    survivor = _FakeTrainer()
    ChaosMonkey(
        FaultSchedule({4: {"kind": "process_kill", "rank": 2}}),
        rank=0, first_call=4,
    ).install(survivor)
    survivor.train_step()  # absolute call 4: target is dead, not us
    assert survivor.steps == 1 and kills == []

    # first_call offsets the index for a matching target too.
    victim = _FakeTrainer()
    ChaosMonkey(
        FaultSchedule({4: {"kind": "process_kill", "rank": 0}}),
        rank=0, first_call=4,
    ).install(victim)
    victim.train_step()
    assert kills == [signal.SIGKILL]


def test_process_kill_schedule_requires_target_rank():
    from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
        FaultSchedule,
    )

    with pytest.raises(ValueError, match="needs a target"):
        FaultSchedule({1: "process_kill"})
    sched = FaultSchedule.seeded(
        7, 20, rate=1.0, kinds=("process_kill",), kill_rank=3
    )
    assert len(sched) > 0
    assert all(s["rank"] == 3 for s in sched.faults.values())


# ------------------------------------------------- e2e: kill/re-election
_LOSS_RE = re.compile(
    r"\[graftelastic\] gen=(\d+) grank=(\d+) step=(\d+) loss=([0-9.]+)"
)


def _store_root(tmp_path, name):
    """CI artifact hook: multihost-smoke sets GRAFT_ELASTIC_TEST_STORE
    so the per-rank logs + events.jsonl land in an uploaded directory."""
    base = os.environ.get("GRAFT_ELASTIC_TEST_STORE")
    if base:
        return os.path.join(base, name)
    return str(tmp_path / name)


def _run_elastic(store, *, steps, kill):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",  # one CPU device per worker
        "PYTHONPATH": repo,
    }
    proc = subprocess.run(
        [
            sys.executable, "-m",
            "cs744_pytorch_distributed_tutorial_tpu.launch",
            "--nprocs", "4", "--store", store,
            "--steps", str(steps), "--kill", kill,
            "--collective-deadline-s", "6",
        ],
        env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (
        f"supervisor failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    return RendezvousStore(store)


def _logged_trajectory(store, steps):
    """Per-step losses from the per-rank logs: identical across ranks
    within a (generation, step); the newest generation wins a step."""
    by_step: dict[tuple[int, int], dict[int, float]] = {}
    logdir = os.path.join(store.root, "logs")
    for name in sorted(os.listdir(logdir)):
        with open(os.path.join(logdir, name), encoding="utf-8") as f:
            for m in _LOSS_RE.finditer(f.read()):
                gen, grank, step, loss = (
                    int(m[1]), int(m[2]), int(m[3]), float(m[4])
                )
                by_step.setdefault((gen, step), {})[grank] = loss
    for (gen, step), ranks in by_step.items():
        assert len(set(ranks.values())) == 1, (
            f"ranks disagree at gen {gen} step {step}: {ranks}"
        )
    best: dict[int, tuple[int, float]] = {}
    for (gen, step), ranks in by_step.items():
        if step not in best or gen > best[step][0]:
            best[step] = (gen, next(iter(ranks.values())))
    assert sorted(best) == list(range(steps)), sorted(best)
    return [best[s][1] for s in range(steps)]


def _shrunk_world_oracle(steps, world):
    """Uninterrupted single-process run at the SHRUNK world size, same
    recipe as the demo worker (launch.py) — the trajectory the resumed
    generations must match."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    mesh = make_mesh({"data": world}, devices=jax.devices()[:world])
    cfg = TrainConfig(
        model="tiny_cnn", sync="allreduce", sync_bn=True, augment=False,
        num_devices=world, global_batch_size=12, synthetic_data=True,
        synthetic_train_size=12, synthetic_test_size=8, seed=0,
        learning_rate=0.002,
    )
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(12, 8, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    out = []
    for _ in range(steps):
        state, m = tr.train_step(state, x, y, key)
        out.append(float(jax.device_get(m["loss"])))
    return out


def _check_elastic_run(store, *, steps, killed, kill_step, survivors,
                       coordinator):
    evs = store.events()

    deaths = [e for e in evs if e["event"] == "worker_death"]
    assert {e["dead_rank"] for e in deaths} == {killed}
    assert all(e["reason"] == "sigkill" for e in deaths)

    injects = [e for e in evs if e["event"] == "chaos_inject"]
    assert len(injects) == 1  # the re-parsed gen-1 spec never re-fires
    assert injects[0]["global_rank"] == killed
    assert injects[0]["call"] == kill_step

    [reelection] = [e for e in evs if e["event"] == "reelection"]
    assert reelection["survivors"] == survivors
    assert reelection["coordinator_rank"] == coordinator
    assert reelection["dead"] == [killed]
    assert reelection["generation"] == 1

    gens = [e for e in evs if e["event"] == "generation_start"]
    assert [(e["generation"], e["world_size"]) for e in gens] == [
        (0, 4), (1, 3)
    ]
    assert gens[1]["ranks"] == survivors

    resumes = [e for e in evs if e["event"] == "recovery_resume"]
    assert len(resumes) == len(survivors)  # every survivor restored
    assert all(
        (e["step"], e["tier"], e["generation"]) == (kill_step, "disk", 1)
        for e in resumes
    )
    assert [e for e in evs if e["event"] == "run_complete"]

    got = _logged_trajectory(store, steps)
    # Steps before the kill ran at world 4, after at world 3; the demo
    # recipe is world-size invariant, so the WHOLE stitched trajectory
    # must match an uninterrupted world-3 run.
    import numpy as np

    np.testing.assert_allclose(
        got, _shrunk_world_oracle(steps, world=3), rtol=1e-6
    )


@pytest.mark.slow  # multihost-smoke CI runs these without the tier-1 filter
def test_elastic_launch_survives_non_coordinator_kill(tmp_path):
    """4-process launch_local, SIGKILL of rank 2 at step 4: the
    survivors re-exec into generation 1 as world [0, 1, 3] (coordinator
    unchanged), resume from the step-4 disk checkpoint, and the stitched
    loss trajectory matches the uninterrupted shrunk-world oracle."""
    store = _run_elastic(
        _store_root(tmp_path, "kill_noncoord"), steps=7, kill="4:2"
    )
    _check_elastic_run(
        store, steps=7, killed=2, kill_step=4, survivors=[0, 1, 3],
        coordinator=0,
    )


@pytest.mark.slow  # multihost-smoke CI runs these without the tier-1 filter
def test_elastic_launch_survives_coordinator_kill_and_reelects(tmp_path):
    """The hard case: SIGKILL of rank 0 — the coordinator — at step 3.
    The lowest surviving global rank (1) is deterministically re-elected
    as generation 1's coordinator (process_id 0), and the run still
    completes with an oracle-matching trajectory."""
    store = _run_elastic(
        _store_root(tmp_path, "kill_coord"), steps=6, kill="3:0"
    )
    _check_elastic_run(
        store, steps=6, killed=0, kill_step=3, survivors=[1, 2, 3],
        coordinator=1,
    )
