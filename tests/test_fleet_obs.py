"""graftfleet: cross-process timeline aggregation + incident audit
(``obs/fleet.py`` and the ``fleet-report`` CLI).

The units run on synthetic stores built with the same primitives a real
run uses (``RendezvousStore`` files + ``FleetStamper`` streams) but
with hand-picked clocks, so the alignment math is checked against known
answers — including ranks whose monotonic origins differ by hours and
whose wall clocks are skewed by seconds.

The slow test is the Issue-17 acceptance scenario end to end: a
4-process ``launch_local`` with a seeded 150 ms straggler on rank 3 AND
a coordinator SIGKILL at step 3. ``fleet-report --check`` must exit 0,
the merged Perfetto trace must show one lane per process across both
generations with the kill/death/re-election/re-exec instants in causal
order, and the skew attribution must pin rank 3 on every post-warmup
step.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys

import pytest

from cs744_pytorch_distributed_tutorial_tpu.obs.fleet import (
    ClockAligner,
    FleetStamper,
    collective_skew,
    fleet_check,
    load_fleet_dir,
    merge_timeline,
    render_fleet_report,
    write_fleet_artifacts,
)
from cs744_pytorch_distributed_tutorial_tpu.parallel.multihost import (
    RendezvousStore,
)

# ------------------------------------------------ synthetic store tools
T0 = 1_700_000_000.0  # global barrier-release instant (reference time)

# Per-rank clock frames: rank 0 is the reference (zero wall offset);
# rank 1's wall clock runs 0.25 s fast; rank 2's runs 3 s slow. The
# monotonic origins are wildly different on purpose — alignment must
# come from the barrier anchors, not from the raw values.
_OFF = {0: 0.0, 1: 0.25, 2: -3.0}
_MONO0 = {0: 100.0, 1: 50_000.0, 2: 7.5}


def _pair(rank: int, t: float) -> tuple[float, float]:
    """Rank-local (wall, mono) for global instant ``t``."""
    return t + _OFF[rank], _MONO0[rank] + (t - T0)


def _write_json(path: str, payload: dict) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)


def _anchor(root: str, gen: int, rank: int, t: float = T0) -> None:
    wall, mono = _pair(rank, t)
    _write_json(
        os.path.join(root, f"sync_g{gen:06d}_r{rank}.json"),
        {
            "generation": gen,
            "global_rank": rank,
            "wall": wall,
            "mono": mono,
            "host": f"host{rank}",
        },
    )


def _event_line(root: str, event: str, t: float, **fields) -> None:
    with open(os.path.join(root, "events.jsonl"), "a", encoding="utf-8") as f:
        f.write(
            json.dumps({"kind": "event", "event": event, "time": t, **fields})
            + "\n"
        )


def _synthetic_store(root: str, *, steps: int = 4, stall_s: float = 0.1):
    """One generation, 3 ranks, rank 2 seeded ``stall_s`` late at every
    sync_enter from step 1 on (step 0 is the compile warmup)."""
    store = RendezvousStore(root)
    store.write_world(
        {"generation": 0, "ranks": [0, 1, 2], "world_size": 3,
         "coordinator_rank": 0}
    )
    _event_line(
        root, "generation_start", T0, generation=0, world_size=3,
        ranks=[0, 1, 2],
    )
    for rank in (0, 1, 2):
        _anchor(root, 0, rank)
        with FleetStamper(root, 0, rank) as stamper:
            for step in range(steps):
                enter = T0 + 1.0 + step  # one step per second
                stall = stall_s if rank == 2 and step >= 1 else 0.0
                arrive = enter + 0.01 + stall
                # everyone leaves the collective when the straggler
                # arrives (plus wire time)
                leave = enter + 0.01 + (stall_s if step >= 1 else 0.0) + 0.005
                stamper.stamp_step(
                    step,
                    step_enter=_pair(rank, enter),
                    sync_enter=_pair(rank, arrive),
                    sync_exit=_pair(rank, leave),
                    step_exit=_pair(rank, leave + 0.001),
                )
    return store


# -------------------------------------------------------------- aligner
def test_clock_aligner_maps_skewed_frames_to_one_timeline():
    anchors = {
        0: {
            0: {"wall": T0, "mono": 100.0},
            1: {"wall": T0 + 0.25, "mono": 50_000.0},
        }
    }
    al = ClockAligner(anchors)
    assert al.reference_rank(0) == 0
    assert al.wall_offset(0, 1) == pytest.approx(0.25)
    # The same global instant T0+1, seen from each rank's own clocks,
    # aligns to the same reference time via the monotonic path:
    assert al.aligned(0, 0, mono=101.0) == pytest.approx(T0 + 1.0)
    assert al.aligned(0, 1, mono=50_001.0) == pytest.approx(T0 + 1.0)
    # Wall fallback (no mono recorded) subtracts the anchor offset:
    assert al.aligned(0, 1, wall=T0 + 1.25) == pytest.approx(T0 + 1.0)
    # Monotonic wins over a lying wall stamp when both are present:
    assert al.aligned(0, 1, wall=T0 + 999.0, mono=50_001.0) == pytest.approx(
        T0 + 1.0
    )
    # Unanchored (gen, rank) passes wall through and is tracked:
    assert al.aligned(0, 7, wall=123.0) == 123.0
    assert (0, 7) in al.unanchored


# ------------------------------------------------- stamper + ingestion
def test_fleet_stamper_round_trips_through_load_fleet_dir(tmp_path):
    root = str(tmp_path / "store")
    _synthetic_store(root, steps=2)
    data = load_fleet_dir(root)
    assert data.generations == [0]
    assert data.ranks == [0, 1, 2]
    stamps = [s for s in data.stamps if s.get("kind") == "fleet_stamp"]
    assert len(stamps) == 6  # 3 ranks x 2 steps
    rec = stamps[0]
    for key in ("step_enter", "sync_enter", "sync_exit", "step_exit"):
        assert isinstance(rec[f"{key}_wall"], float)
        assert isinstance(rec[f"{key}_mono"], float)
    assert set(data.barrier_stamps[0]) == {0, 1, 2}
    assert data.torn_lines == {}


def test_collective_skew_pins_seeded_straggler(tmp_path):
    root = str(tmp_path / "store")
    _synthetic_store(root, steps=4, stall_s=0.1)
    data = load_fleet_dir(root)
    rows = collective_skew(data)
    assert [r["step"] for r in rows] == [0, 1, 2, 3]
    assert rows[0]["warmup"] and not any(r["warmup"] for r in rows[1:])
    for row in rows[1:]:
        assert row["straggler"] == 2
        assert row["skew_ms"] == pytest.approx(100.0, abs=1.0)
        # early ranks are charged the wait; the straggler waits ~0
        assert row["collective_wait_ms"]["0"] == pytest.approx(100.0, abs=1.0)
        assert row["collective_wait_ms"]["2"] == pytest.approx(0.0, abs=1.0)
        assert row["full_coverage"]
    # and the audit finds nothing wrong with a healthy run
    assert fleet_check(data) == []


def test_merge_timeline_lane_per_process(tmp_path):
    root = str(tmp_path / "store")
    _synthetic_store(root, steps=2)
    data = load_fleet_dir(root)
    trace = merge_timeline(data, skew=collective_skew(data))
    events = trace["traceEvents"]
    lanes = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert lanes == {"fleet", "rank 0", "rank 1", "rank 2"}
    steps = [e for e in events if e.get("cat") == "step"]
    assert {e["pid"] for e in steps} == {1, 2, 3}
    gen_track = [e for e in events if e.get("cat") == "generation"]
    assert [e["args"]["generation"] for e in gen_track] == [0]
    # the collective spans of one step start at aligned arrival: the
    # straggler's span must start last on step 1
    coll = {
        e["pid"]: e["ts"]
        for e in events
        if e.get("cat") == "collective" and e["args"]["step"] == 1
    }
    assert max(coll, key=coll.get) == 3  # pid 3 == rank 2
    # rendered report names the straggler too
    text = render_fleet_report(
        data, collective_skew(data), [], ClockAligner(data.barrier_stamps)
    )
    assert "r2" in text


# ---------------------------------------------------------------- audit
def test_fleet_check_flags_orphan_generation(tmp_path):
    root = str(tmp_path / "orphan")
    store = RendezvousStore(root)
    # generation 1 appears with no parent world and no re-election
    store.write_world(
        {"generation": 1, "ranks": [0, 1], "world_size": 2,
         "coordinator_rank": 0}
    )
    problems = fleet_check(load_fleet_dir(root))
    assert any("orphan generation 1" in p and "parent" in p
               for p in problems)
    assert any("no re-election" in p for p in problems)


def _two_generation_store(root: str) -> RendezvousStore:
    """g0=[0,1] -> rank 1 dies at T0+2 -> g1=[0]; causally ordered."""
    store = RendezvousStore(root)
    store.write_world(
        {"generation": 0, "ranks": [0, 1], "world_size": 2,
         "coordinator_rank": 0}
    )
    store.write_world(
        {"generation": 1, "ranks": [0], "world_size": 1,
         "coordinator_rank": 0}
    )
    _event_line(root, "generation_start", T0, generation=0, world_size=2,
                ranks=[0, 1])
    _event_line(root, "worker_death", T0 + 2.0, generation=0, dead_rank=1,
                reason="sigkill")
    _write_json(
        os.path.join(root, "dead_g000000.json"),
        {"generation": 0, "dead": [1], "time": T0 + 2.05},
    )
    _event_line(root, "reelection", T0 + 2.1, parent_generation=0,
                generation=1, survivors=[0], dead=[1], coordinator_rank=0)
    _event_line(root, "generation_start", T0 + 2.2, generation=1,
                world_size=1, ranks=[0])
    _anchor(root, 0, 0)
    _anchor(root, 0, 1)
    _anchor(root, 1, 0, T0 + 2.3)
    return store


def test_fleet_check_passes_consistent_two_generation_run(tmp_path):
    root = str(tmp_path / "ok")
    _two_generation_store(root)
    with FleetStamper(root, 0, 0) as stamper:
        stamper.stamp_step(
            0,
            step_enter=_pair(0, T0 + 1.0),
            sync_enter=_pair(0, T0 + 1.01),
            sync_exit=_pair(0, T0 + 1.02),
            step_exit=_pair(0, T0 + 1.03),
        )
    assert fleet_check(load_fleet_dir(root)) == []


def test_fleet_check_flags_seal_crossing_step(tmp_path):
    root = str(tmp_path / "seal")
    _two_generation_store(root)
    # rank 0 claims a g0 step that EXITS 4 s after g1 started: a step
    # completed in a world that no longer existed.
    with FleetStamper(root, 0, 0) as stamper:
        stamper.stamp_step(
            2,
            step_enter=_pair(0, T0 + 1.0),
            sync_enter=_pair(0, T0 + 1.01),
            sync_exit=_pair(0, T0 + 6.0),
            step_exit=_pair(0, T0 + 6.2),
        )
    problems = fleet_check(load_fleet_dir(root))
    assert any("crosses the generation seal" in p for p in problems)


def test_fleet_check_flags_out_of_order_stamp(tmp_path):
    root = str(tmp_path / "disorder")
    _synthetic_store(root, steps=1)
    with FleetStamper(root, 0, 0) as stamper:
        stamper.stamp_step(
            9,
            step_enter=_pair(0, T0 + 9.0),
            sync_enter=_pair(0, T0 + 8.0),  # before step_enter
            sync_exit=_pair(0, T0 + 9.1),
            step_exit=_pair(0, T0 + 9.2),
        )
    problems = fleet_check(load_fleet_dir(root))
    assert any("out of order" in p for p in problems)


# --------------------------------------------- store durability fixes
def test_append_event_single_line_and_torn_tail_tolerated(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    store.append_event("alpha", n=1)
    store.append_event("beta", n=2)
    # every intact record is one line and carries the monotonic stamp
    events, torn = store.events_with_torn()
    assert [e["event"] for e in events] == ["alpha", "beta"]
    assert torn == 0
    assert all(isinstance(e.get("monotonic"), float) for e in events)
    # a writer SIGKILLed mid-append leaves a torn tail: reader skips it
    with open(store.events_path, "a", encoding="utf-8") as f:
        f.write('{"kind": "event", "event": "gam')
    events, torn = store.events_with_torn()
    assert [e["event"] for e in events] == ["alpha", "beta"]
    assert torn == 1
    assert store.events() == events  # plain reader unaffected
    # the fleet loader counts it per source file
    data = load_fleet_dir(store.root)
    assert sum(data.torn_lines.values()) == 1


def test_heartbeat_age_prefers_monotonic_on_same_host(tmp_path):
    store = RendezvousStore(str(tmp_path / "store"))
    store.heartbeat(0, 0, step=3)
    with open(store._hb_path(0, 0), encoding="utf-8") as f:
        rec = json.load(f)
    assert rec["host"] == socket.gethostname()
    # monotonic path: age is the mono delta, immune to wall steps
    age = store.heartbeat_age(0, 0, now_mono=rec["monotonic"] + 5.0)
    assert age == pytest.approx(5.0, abs=0.01)
    # explicit `now` forces the wall path (tests pin time that way)
    age = store.heartbeat_age(0, 0, now=rec["time"] + 7.0)
    assert age == pytest.approx(7.0, abs=0.01)
    # a beat from another host cannot use this host's monotonic clock
    rec["host"] = "somewhere-else"
    rec["time"] = rec["time"] - 11.0
    with open(store._hb_path(0, 0), "w", encoding="utf-8") as f:
        json.dump(rec, f)
    age = store.heartbeat_age(0, 0)
    assert age == pytest.approx(11.0, abs=2.0)


# ------------------------------------------------------------ CLI + e2e
def _cli(args, **kw):
    env = {**os.environ, "PYTHONPATH": _repo_root(), "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": ""}
    return subprocess.run(
        [sys.executable, "-m", "cs744_pytorch_distributed_tutorial_tpu.obs",
         *args],
        env=env, capture_output=True, text=True, timeout=kw.pop("timeout", 120),
    )


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_fleet_report_cli_check_gates_on_problems(tmp_path):
    ok_root = str(tmp_path / "ok")
    _synthetic_store(ok_root, steps=2)
    proc = _cli(["fleet-report", ok_root, "--check"])
    assert proc.returncode == 0, proc.stderr
    assert "fleet check: OK" in proc.stdout
    assert os.path.exists(os.path.join(ok_root, "fleet_trace.json"))
    assert os.path.exists(os.path.join(ok_root, "fleet_report.json"))

    bad_root = str(tmp_path / "bad")
    store = RendezvousStore(bad_root)
    store.write_world(
        {"generation": 1, "ranks": [0], "world_size": 1,
         "coordinator_rank": 0}
    )
    proc = _cli(["fleet-report", bad_root, "--check", "--no-artifacts"])
    assert proc.returncode == 1
    assert "orphan generation" in proc.stderr
    assert not os.path.exists(os.path.join(bad_root, "fleet_trace.json"))


def _store_root(tmp_path, name):
    """CI artifact hook: multihost-smoke sets GRAFT_ELASTIC_TEST_STORE
    so the run dir (including fleet artifacts) lands in an uploaded
    directory."""
    base = os.environ.get("GRAFT_ELASTIC_TEST_STORE")
    if base:
        return os.path.join(base, name)
    return str(tmp_path / name)


@pytest.mark.slow  # multihost-smoke CI runs these without the tier-1 filter
def test_fleet_report_on_coordinator_kill_with_seeded_straggler(tmp_path):
    """Issue-17 acceptance: 4 processes, rank 3 stalled 150 ms per step,
    coordinator (rank 0) SIGKILLed at step 3. The audit must pass, the
    merged trace must carry every process across both generations with
    the incident instants in causal order, and the attribution must name
    rank 3 the straggler on every post-warmup step."""
    store_root = _store_root(tmp_path, "fleet_kill")
    repo = _repo_root()
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
            "--nprocs", "4", "--store", store_root,
            "--steps", "7", "--kill", "3:0", "--slow", "3:150",
            "--collective-deadline-s", "6",
        ],
        env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, (
        f"supervisor failed rc={proc.returncode}\n"
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    )
    # the supervisor already merged the artifacts at exit
    assert os.path.exists(os.path.join(store_root, "fleet_trace.json"))

    cli = _cli(["fleet-report", store_root, "--check"], timeout=180)
    assert cli.returncode == 0, (
        f"fleet check failed\nstdout:\n{cli.stdout}\nstderr:\n{cli.stderr}"
    )
    assert "fleet check: OK" in cli.stdout

    with open(os.path.join(store_root, "fleet_trace.json")) as f:
        events = json.load(f)["traceEvents"]
    lanes = {
        e["args"]["name"]
        for e in events
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert lanes == {"fleet", "rank 0", "rank 1", "rank 2", "rank 3"}
    # generation track: g0 then g1 on the fleet lane
    gen_track = [e for e in events if e.get("cat") == "generation"]
    assert [e["args"]["generation"] for e in gen_track] == [0, 1]
    # every survivor's lane continues into generation 1; the victim's
    # stops at generation 0
    gens_by_pid: dict[int, set] = {}
    for e in events:
        if e.get("cat") == "step":
            gens_by_pid.setdefault(e["pid"], set()).add(
                e["args"]["generation"]
            )
    assert gens_by_pid[1] == {0}  # rank 0 (killed)
    for pid in (2, 3, 4):  # ranks 1-3 survive into g1
        assert gens_by_pid[pid] == {0, 1}, gens_by_pid

    def first_instant(prefix):
        ts = [
            e["ts"] for e in events
            if e.get("ph") == "i" and e["name"].startswith(prefix)
        ]
        assert ts, f"no instant named {prefix!r}"
        return min(ts)

    kill = first_instant("chaos process_kill")
    death = first_instant("death r0")
    note = first_instant("death note g0")
    reelect = first_instant("re-election g0->g1")
    reexec = first_instant("re-exec g1")
    assert kill <= death <= note <= reelect <= reexec

    with open(os.path.join(store_root, "fleet_report.json")) as f:
        report = json.load(f)
    assert report["problems"] == []
    assert report["generations"] == [0, 1]
    assert report["ranks"] == [0, 1, 2, 3]
    skew = [
        r for r in report["records"]
        if r.get("kind") == "fleet_skew" and not r.get("warmup")
    ]
    assert len(skew) >= 4  # 7 steps attributed minus one warmup per gen
    for row in skew:
        assert row["straggler"] == 3, row
        # the stall dominates the spread; the straggler itself waits
        # the least inside the collective
        waits = row["collective_wait_ms"]
        assert min(waits, key=waits.get) == "3"
        assert row["skew_ms"] > 50.0
