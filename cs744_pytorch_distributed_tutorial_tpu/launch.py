"""graftelastic launcher: supervised elastic multi-process runs.

The reference's launch story is "start 4 processes by hand on 4
CloudLab nodes and hope none dies" (``init_process`` pins
``MASTER_ADDR``/``MASTER_PORT``; SURVEY §5.3). This CLI is the
replacement: a supervisor (``parallel/multihost.py::launch_local``)
that spawns N workers, watches heartbeats and exit codes, and re-execs
the survivors into generation g+1 — with a deterministically
re-elected coordinator — when a rank dies.

Supervise any worker command (it learns its coordinates from the
``GRAFT_ELASTIC_*`` environment, or its own ``--coordinator`` flags)::

    python -m cs744_pytorch_distributed_tutorial_tpu.launch \\
        --nprocs 4 --store /tmp/elastic -- \\
        python -m cs744_pytorch_distributed_tutorial_tpu.cli --plan 2b

Or run the built-in demo worker — a tiny-CNN data-parallel loop with
per-step durable checkpoints and a scheduled chaos kill — which is the
e2e harness for kill/re-election (tests/test_multihost.py, the
multihost-smoke CI job)::

    python -m cs744_pytorch_distributed_tutorial_tpu.launch \\
        --nprocs 4 --store /tmp/elastic --steps 8 --kill 4:2

``--kill STEP:RANK`` SIGKILLs the given GLOBAL rank at the given
cumulative step (rank 0 = the coordinator — killing it exercises
re-election). ``--slow RANK:MS`` stalls the given GLOBAL rank for MS
milliseconds before every step — a seeded straggler whose late
collective arrivals graftfleet's cross-rank skew attribution must pin.
The demo worker checkpoints every step, so the resumed generation's
loss trajectory is comparable (rtol 1e-6) against an uninterrupted run
at the shrunk world size — the acceptance bar for the elastic path.
Per-rank stdout lands in ``<store>/logs/``; the supervisor+worker
event timeline in ``<store>/events.jsonl``; each rank stamps its
step/collective boundaries into ``<store>/fleet/``, and the supervisor
merges everything into ``<store>/fleet_trace.json`` (Perfetto) +
``fleet_report.json`` at exit (``obs/fleet.py``; re-render or audit
any time with ``python -m …obs fleet-report <store> --check``).
"""

from __future__ import annotations

import argparse
import os
import sys

from cs744_pytorch_distributed_tutorial_tpu.parallel.multihost import (
    CollectiveWatchdog,
    RendezvousStore,
    attach,
    env_context,
    launch_local,
)
from cs744_pytorch_distributed_tutorial_tpu.utils.logging import get_logger


class _StoreTelemetry:
    """Adapter: ``emit_event``-shaped telemetry that appends to the
    rendezvous store's shared events.jsonl — chaos injections from any
    rank land on the same timeline as the supervisor's transitions, and
    the append is durable before a self-SIGKILL returns."""

    def __init__(self, store: RendezvousStore):
        self.store = store

    def emit_event(self, event: str, **fields) -> None:
        self.store.append_event(event, **fields)


def _parse_kill(spec: str) -> tuple[int, int]:
    try:
        step_s, rank_s = spec.split(":")
        return int(step_s), int(rank_s)
    except ValueError as e:
        raise SystemExit(f"--kill expects STEP:RANK, got {spec!r}") from e


def _parse_slow(spec: str) -> tuple[int, float]:
    try:
        rank_s, ms_s = spec.split(":")
        return int(rank_s), float(ms_s)
    except ValueError as e:
        raise SystemExit(f"--slow expects RANK:MS, got {spec!r}") from e


def _worker_train(args: argparse.Namespace) -> int:
    """The built-in demo worker: one elastic data-parallel tiny-CNN loop.

    Deliberately layout-invariant so the e2e's rtol 1e-6 bar is about
    ELASTICITY, not luck: ``sync_bn=True`` (global-batch BN statistics —
    identical math at any world size), ``augment=False``, one fixed
    synthetic global batch divisible by every world size it will see,
    and the trainer's own step-folded PRNG (resume at step K draws step
    K's key regardless of generation). World size is then a layout
    choice, and the resumed trajectory must match an uninterrupted run
    at the shrunk world bit-for-bit-ish.
    """
    ctx = env_context()
    if ctx is None:
        raise SystemExit(
            "--worker-train needs the GRAFT_ELASTIC_* environment "
            "(it is spawned by the supervisor, not run by hand)"
        )
    import jax

    store = RendezvousStore(ctx.store_dir)
    hb = attach(ctx)  # rendezvous + heartbeats + identity labels
    log = get_logger()

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.obs.fleet import (
        FleetStamper,
        stamp_pair,
    )
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer
    from cs744_pytorch_distributed_tutorial_tpu.utils.chaos import (
        ChaosMonkey,
        FaultSchedule,
    )
    from cs744_pytorch_distributed_tutorial_tpu.utils.checkpoint import (
        Checkpointer,
    )

    n_dev = jax.device_count()
    mesh = make_mesh({"data": n_dev})
    cfg = TrainConfig(
        model="tiny_cnn",
        sync="allreduce",
        sync_bn=True,
        augment=False,
        num_devices=n_dev,
        global_batch_size=args.global_batch,
        synthetic_data=True,
        synthetic_train_size=args.global_batch,
        synthetic_test_size=8,
        seed=0,
        # Modest LR: keeps the demo's losses O(1) for its whole run, so
        # the e2e's rtol-1e-6 cross-world parity bar measures ELASTIC
        # correctness, not float noise amplified by a near-zero loss
        # (reduction order differs across world sizes by ~1e-7 rel).
        learning_rate=args.lr,
    )
    trainer = Trainer(cfg, mesh=mesh)
    state = trainer.init()

    ckpt = Checkpointer(os.path.join(store.root, "ckpt"))
    start = 0
    if ckpt.latest_step() is not None:
        # After a re-exec only the disk tier survives (the in-memory
        # snapshot died with the old process) — restore-tier
        # arbitration is trivial here; docs/reliability.md has the
        # general table.
        state = trainer.place_state(ckpt.restore_latest(state))
        start = int(jax.device_get(state.step))
        store.append_event(
            "recovery_resume",
            step=start,
            tier="disk",
            world_size=ctx.num_processes,
        )
        log.info(
            "graftelastic demo: resumed from disk at step %d "
            "(generation %d, world %d)",
            start,
            ctx.generation,
            ctx.num_processes,
        )

    # Arrival stamping (obs/fleet.py): wrap train_step so sync_enter is
    # taken immediately before the step dispatches. Cross-process CPU
    # collectives block at DISPATCH (the psum rendezvous is inside the
    # train_step call, not behind the fetch), so this pre-dispatch
    # instant is the rank's true arrival at the collective — any chaos
    # stall installed OUTSIDE this wrapper delays it, and early ranks
    # spend the gap blocked inside the step waiting. The monkeys below
    # must wrap this, so install it first.
    arrival: dict[str, tuple[float, float]] = {}
    _unstamped_step = trainer.train_step

    def _stamped_step(*step_args, **step_kwargs):
        arrival["sync_enter"] = stamp_pair()
        return _unstamped_step(*step_args, **step_kwargs)

    trainer.train_step = _stamped_step

    if args.kill:
        kill_step, kill_rank = _parse_kill(args.kill)
        schedule = FaultSchedule(
            {kill_step: {"kind": "process_kill", "rank": kill_rank}}
        )
        # first_call=start keeps the schedule keyed by ABSOLUTE step
        # across generations; targeting the global rank makes a
        # re-parsed spec inert once that rank is dead.
        ChaosMonkey(
            schedule,
            telemetry=_StoreTelemetry(store),
            rank=ctx.global_rank,
            first_call=start,
        ).install(trainer)
    if args.slow:
        slow_rank, slow_ms = _parse_slow(args.slow)
        # A stall at EVERY step of the run: the schedule targets the
        # global rank, so survivors re-parsing it keep the same
        # straggler across generations. Installed after --kill's monkey
        # (wrapping it), so the stall precedes the kill check.
        ChaosMonkey(
            FaultSchedule(
                {
                    s: {
                        "kind": "slow_step",
                        "rank": slow_rank,
                        "stall_s": slow_ms / 1e3,
                    }
                    for s in range(args.steps)
                }
            ),
            telemetry=_StoreTelemetry(store),
            rank=ctx.global_rank,
            first_call=start,
        ).install(trainer)

    watchdog = CollectiveWatchdog(
        store, ctx, deadline_s=args.collective_deadline_s
    )
    # Per-rank fleet stamps (obs/fleet.py): step boundaries plus the
    # sync window around the blocking fetch. Dispatch is async, so
    # sync_enter is this rank's ARRIVAL at the collective — the stamp
    # graftfleet aligns across ranks to name the straggler. The demo
    # fetches every step anyway, so the stamps add no host syncs.
    stamper = FleetStamper(
        store.root, ctx.generation, ctx.global_rank, ctx.process_id
    )
    ds = synthetic_cifar10(args.global_batch, 8, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    for step in range(start, args.steps):
        watchdog.check()
        step_enter = stamp_pair()
        with watchdog.watch():
            # Step + fetch + durable save are ONE watched section: all
            # three can block on a dead peer (the psum, the result
            # fetch behind it, Orbax's cross-process commit barrier).
            state, metrics = trainer.train_step(state, x, y, key)
            loss = float(jax.device_get(metrics["loss"]))
            sync_exit = stamp_pair()
            ckpt.save(state, force=True, wait=True)
        step_exit = stamp_pair()
        stamper.stamp_step(
            step,
            step_enter=step_enter,
            sync_enter=arrival.get("sync_enter", step_enter),
            sync_exit=sync_exit,
            step_exit=step_exit,
        )
        hb.step = step
        print(
            f"[graftelastic] gen={ctx.generation} grank={ctx.global_rank} "
            f"step={step} loss={loss:.8f}",
            flush=True,
        )
    watchdog.close()
    stamper.close()
    ckpt.close()
    hb.stop()
    return 0


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m cs744_pytorch_distributed_tutorial_tpu.launch",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--nprocs", type=int, default=4,
                   help="workers in generation 0 (default 4)")
    p.add_argument("--store", required=False, default=None,
                   help="rendezvous store directory (shared filesystem); "
                        "required in supervisor mode")
    p.add_argument("--max-generations", type=int, default=4,
                   help="give up after this many re-exec generations")
    p.add_argument("--heartbeat-deadline-s", type=float, default=15.0,
                   help="a running rank whose heartbeat is older than "
                        "this is declared dead")
    p.add_argument("--startup-grace-s", type=float, default=180.0,
                   help="allowance for a rank's first heartbeat "
                        "(imports + rendezvous)")
    p.add_argument("--exit-grace-s", type=float, default=30.0,
                   help="teardown: how long survivors get to exit on "
                        "their own (via their collective watchdog) "
                        "before SIGTERM/SIGKILL escalation")
    p.add_argument("--platform", choices=("cpu", "inherit"), default="cpu",
                   help="'cpu' pins workers to one CPU device each "
                        "(CI/laptop); 'inherit' leaves the environment "
                        "alone (pod runs)")
    # Demo-worker knobs (also forwarded by the supervisor when no
    # explicit worker command is given after `--`).
    p.add_argument("--steps", type=int, default=8,
                   help="demo worker: total train steps")
    p.add_argument("--global-batch", type=int, default=12,
                   help="demo worker: fixed global batch — keep it "
                        "divisible by every world size the run may "
                        "shrink to")
    p.add_argument("--lr", type=float, default=0.002,
                   help="demo worker: SGD learning rate")
    p.add_argument("--kill", default=None, metavar="STEP:RANK",
                   help="demo worker: SIGKILL global rank RANK at "
                        "cumulative step STEP (0 = coordinator)")
    p.add_argument("--slow", default=None, metavar="RANK:MS",
                   help="demo worker: stall global rank RANK for MS "
                        "milliseconds before every step (seeded "
                        "straggler for fleet skew attribution)")
    p.add_argument("--collective-deadline-s", type=float, default=8.0,
                   help="demo worker: watchdog deadline for a step "
                        "blocked on a dead peer")
    p.add_argument("--worker-train", action="store_true",
                   help=argparse.SUPPRESS)  # internal: demo worker mode
    p.add_argument("cmd", nargs=argparse.REMAINDER,
                   help="worker command after `--` (default: the "
                        "built-in demo worker)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.worker_train:
        return _worker_train(args)
    if not args.store:
        raise SystemExit("supervisor mode requires --store DIR")

    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        cmd = [
            sys.executable,
            "-m",
            "cs744_pytorch_distributed_tutorial_tpu.launch",
            "--worker-train",
            "--steps", str(args.steps),
            "--global-batch", str(args.global_batch),
            "--lr", str(args.lr),
            "--collective-deadline-s", str(args.collective_deadline_s),
        ]
        if args.kill:
            cmd += ["--kill", args.kill]
        if args.slow:
            cmd += ["--slow", args.slow]

    env = None
    if args.platform == "cpu":
        # One CPU device per process: clear any virtual-device XLA flags.
        env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}

    run = launch_local(
        args.nprocs,
        cmd,
        store_dir=args.store,
        env=env,
        max_generations=args.max_generations,
        heartbeat_deadline_s=args.heartbeat_deadline_s,
        startup_grace_s=args.startup_grace_s,
        exit_grace_s=args.exit_grace_s,
    )
    log = get_logger()
    for world in run.generations:
        log.info(
            "generation %d: world %s exit codes %s dead %s",
            world["generation"],
            world["ranks"],
            world.get("exit_codes", {}),
            world.get("dead", []),
        )
    log.info(
        "graftelastic: %s after %d generation(s); events at %s",
        "completed" if run.success else "FAILED",
        len(run.generations),
        run.store.events_path,
    )
    # Merge everything the run left behind into the fleet artifacts
    # (Perfetto timeline + skew/incident report). Best-effort: a merge
    # failure must never change the run's exit code.
    try:
        from cs744_pytorch_distributed_tutorial_tpu.obs.fleet import (
            write_fleet_artifacts,
        )

        artifacts = write_fleet_artifacts(run.store.root)
        log.info(
            "graftfleet: merged timeline at %s (%d audit problem(s))",
            artifacts["trace"],
            len(artifacts["problems"]),
        )
    except Exception:
        log.warning("graftfleet: artifact merge failed", exc_info=True)
    return 0 if run.success else 1


if __name__ == "__main__":
    raise SystemExit(main())
