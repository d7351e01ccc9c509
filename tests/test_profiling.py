"""Profiler capture wired into Trainer.fit (utils/profiling.py, SURVEY §5.1)."""

import os

import pytest
from conftest import TINY_DP4_CFG

from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer


@pytest.mark.slow
def test_fit_captures_profile_trace(mesh4, tmp_path):
    """profile_dir + a window inside the run: fit records an XLA trace
    (TensorBoard profile-plugin layout) and training completes normally."""
    profile_dir = str(tmp_path / "trace")
    cfg = TrainConfig(
        **TINY_DP4_CFG,
        sync="allreduce",
        profile_dir=profile_dir,
        profile_start_step=1,
        profile_num_steps=2,
    )
    tr = Trainer(cfg, mesh=mesh4)
    _, history = tr.fit()
    assert history["eval"]
    # the capture produced the plugins/profile/<run>/ tree with event data
    hits = [
        os.path.join(root, f)
        for root, _, files in os.walk(profile_dir)
        for f in files
    ]
    assert hits, f"no profiler output under {profile_dir}"


def test_lm_fit_captures_profile_trace(tmp_path):
    """Same contract on the LM engine (LMConfig.profile_dir)."""
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    profile_dir = str(tmp_path / "lm_trace")
    cfg = LMConfig(vocab_size=32, num_layers=1, num_heads=2, d_model=16,
                   d_ff=32, max_seq_len=32, seq_len=16, global_batch_size=4,
                   attention_impl="ring", data_parallel=2, seq_parallel=2,
                   profile_dir=profile_dir, profile_start_step=1,
                   profile_num_steps=2)
    tr = LMTrainer(cfg, mesh=make_mesh({"data": 2, "seq": 2}))
    _, _, losses = tr.fit(synthetic_tokens(8, 16, 32, seed=0), steps=4)
    assert len(losses) == 4
    hits = [
        os.path.join(root, f)
        for root, _, files in os.walk(profile_dir)
        for f in files
    ]
    assert hits, f"no profiler output under {profile_dir}"


def test_fit_profile_window_past_end_is_noop(mesh4, tmp_path):
    """A window that never opens (start beyond the run) must not trace or
    error."""
    profile_dir = str(tmp_path / "trace2")
    cfg = TrainConfig(
        **TINY_DP4_CFG,
        sync="allreduce",
        profile_dir=profile_dir,
        profile_start_step=10_000,
    )
    tr = Trainer(cfg, mesh=mesh4)
    _, history = tr.fit()
    assert history["eval"]
    assert not os.path.isdir(profile_dir) or not os.listdir(profile_dir)


def test_device_op_breakdown_cpu():
    """The round-2 instrument: per-op device time from a real profiler
    trace (a host timer around a small op measures dispatch, not
    compute). CPU traces exercise the same parse path."""
    import jax
    import jax.numpy as jnp

    from cs744_pytorch_distributed_tutorial_tpu.utils.profiling import (
        device_op_breakdown,
    )

    @jax.jit
    def f(a):
        return (a @ a).sum() + jnp.tanh(a).sum()

    a = jnp.ones((256, 256))
    total, rows = device_op_breakdown(f, a, iters=2, top=10)
    assert total >= 0.0
    assert isinstance(rows, list)
    # on CPU the device lanes may be named differently per backend
    # version; the contract is "no crash, sane types" (on the chip the
    # serve cell's traced runs go through this parse, docs/kernels.md)
    for ms, name in rows:
        assert ms >= 0.0 and isinstance(name, str)


# ---------------------------------------------------------------------------
# graftscope: straggler monitor + flight recorder (obs/flight.py)
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_seeded_outlier():
    from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
        StragglerMonitor,
    )

    mon = StragglerMonitor(min_samples=16, mad_k=5.0)
    outliers = []
    for step in range(64):
        wall = 0.102 if step % 2 else 0.098  # jittery but tight
        if step == 50:
            wall = 1.5  # the seeded straggler
        out = mon.record(step, wall)
        if out is not None:
            outliers.append(out)
    assert [o["step"] for o in outliers] == [50]
    assert outliers[0]["wall_s"] == 1.5
    assert outliers[0]["excess_sigma"] > 0
    stats = mon.stats()
    assert stats["outlier_count"] == 1
    assert stats["max_s"] == 1.5
    assert mon.tail(4)[-1]["step"] == 63


def test_straggler_monitor_quiet_on_uniform_and_warmup():
    """No outliers on uniform timing, and never before min_samples — the
    first post-compile steps must not page anyone."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
        StragglerMonitor,
    )

    mon = StragglerMonitor(min_samples=16)
    assert mon.record(0, 30.0) is None  # huge compile step: under warmup
    for step in range(1, 64):
        assert mon.record(step, 0.1) is None


def test_flight_recorder_dumps_on_watchdog():
    """StepWatchdog(flight_recorder=...) fires -> structured flight_dump
    event records land on the sink, tail first."""
    import time

    from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
        FlightRecorder,
        StragglerMonitor,
    )
    from cs744_pytorch_distributed_tutorial_tpu.utils.failure import (
        StepWatchdog,
    )

    events = []

    def emit(event, **fields):
        events.append({"event": event, **fields})

    mon = StragglerMonitor(min_samples=2)
    for step in range(8):
        mon.record(step, 0.1)
    fr = FlightRecorder(straggler=mon, emit=emit)
    wd = StepWatchdog(timeout_s=0.05, dump_stacks=False, flight_recorder=fr)
    try:
        wd.arm()
        deadline = time.monotonic() + 5.0
        while wd.fired == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        wd.disarm()
    finally:
        wd.close()
    assert wd.fired >= 1
    assert fr.dumps >= 1
    dump = [e for e in events if e["event"] == "flight_dump"]
    assert dump and dump[0]["reason"] == "watchdog"
    assert dump[0]["straggler_steps_recorded"] == 8
    steps = [e for e in events if e["event"] == "flight_step"]
    assert steps and steps[-1]["step"] == 7


def test_flight_recorder_excepthook_chains():
    """install() wraps sys.excepthook: a dump happens AND the previous
    hook still runs; uninstall() restores it."""
    import sys

    from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
        FlightRecorder,
    )

    events = []
    seen = []
    prev_hook = sys.excepthook
    sys.excepthook = lambda *a: seen.append(a)
    try:
        fr = FlightRecorder(emit=lambda event, **f: events.append(event))
        fr.install(sigterm=False, excepthook=True)
        try:
            raise RuntimeError("boom")
        except RuntimeError:
            sys.excepthook(*sys.exc_info())
        assert "flight_dump" in events
        assert len(seen) == 1  # the chained original hook ran
        fr.uninstall()
        assert sys.excepthook is not fr and len(events) >= 1
    finally:
        sys.excepthook = prev_hook


def test_flight_recorder_requires_a_sink():
    from cs744_pytorch_distributed_tutorial_tpu.obs.flight import (
        FlightRecorder,
    )

    with pytest.raises(ValueError):
        FlightRecorder()
