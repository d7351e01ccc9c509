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
    # version; the contract is "no crash, sane types", the TPU value was
    # validated by hand in benchmarks/ablate.py round-2 notes
    for ms, name in rows:
        assert ms >= 0.0 and isinstance(name, str)


# ---------------------------------------------------------------------------
# graftscope: segmented-step phase attribution (obs/phases.py)
# ---------------------------------------------------------------------------


def _cifar_step_inputs(mesh, cfg):
    """(trainer, state, x, y, key) — the canonical parity-suite recipe."""
    import jax

    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )

    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(cfg.global_batch_size, 8, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    return tr, state, x, y, jax.random.key(cfg.seed)


@pytest.mark.parametrize(
    "sync,compress,overrides",
    [
        ("allreduce", "none", {}),  # bucketed flat allreduce (default)
        ("allreduce", "none", {"sync_bucket_mb": 0}),  # per-leaf
        ("ring", "none", {}),
        ("allreduce", "int8", {}),
        pytest.param(  # fused scatter/apply/gather
            "zero1", "none", {}, marks=pytest.mark.slow
        ),
        ("zero1", "none", {"sync_overlap": "bucket"}),
        pytest.param(
            "zero1", "int8", {"sync_overlap": "bucket+int8"},
            marks=pytest.mark.slow,
        ),
    ],
    ids=[
        "allreduce", "allreduce-perleaf", "ring", "int8",
        "zero1", "zero1-overlap", "zero1-int8",
    ],
)
def test_segmented_fused_parity_cifar(mesh4, sync, compress, overrides):
    """The segmented profiled step (forward/grads | sync | opt as separate
    jitted programs) must produce the SAME loss and params as the fused
    fast path — same tolerance discipline as test_sync_parity."""
    import jax
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        PARITY_ATOL,
        PARITY_LOSS_RTOL,
        PARITY_RTOL,
        build_cifar_segments,
    )

    cfg = TrainConfig(
        **TINY_DP4_CFG, sync=sync, grad_compress=compress,
        compute_dtype="float32", **overrides,
    )
    tr, state, x, y, key = _cifar_step_inputs(mesh4, cfg)
    segs = build_cifar_segments(tr)
    new_f, m_f = segs.fused(state, x, y, key)
    new_s, loss_s = segs.segmented_step(state, x, y, key)
    loss_f = float(m_f["loss"])
    assert abs(float(loss_s) - loss_f) <= PARITY_LOSS_RTOL * max(
        1.0, abs(loss_f)
    )
    for a, b in zip(
        jax.tree.leaves(new_f.params), jax.tree.leaves(new_s.params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=PARITY_RTOL, atol=PARITY_ATOL
        )


@pytest.mark.parametrize("compress", ["none", "int8"])
def test_segmented_fused_parity_lm(compress):
    """Same contract on the LM engine (pure-DP configs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        PARITY_ATOL,
        PARITY_LOSS_RTOL,
        PARITY_RTOL,
        build_lm_segments,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

    cfg = LMConfig(
        vocab_size=64, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_seq_len=16, seq_len=16, global_batch_size=8, data_parallel=4,
        seq_parallel=1, grad_compress=compress,
    )
    tr = LMTrainer(cfg)
    params, opt_state = tr.init()
    import numpy as _np

    toks = _np.random.RandomState(0).randint(0, 64, size=(8, 17))
    x, y = tr.shard_batch(toks)
    segs = build_lm_segments(tr)
    step = jnp.int32(0)
    new_p, _new_o, m_f = segs.fused(params, opt_state, x, y, step)
    (seg_p, _seg_o), loss_s = segs.segmented_step(params, opt_state, x, y, step)
    loss_f = float(m_f["loss"])
    assert abs(float(loss_s) - loss_f) <= PARITY_LOSS_RTOL * max(
        1.0, abs(loss_f)
    )
    for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(seg_p)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=PARITY_RTOL, atol=PARITY_ATOL
        )


def test_cifar_segments_reject_fsdp(mesh4):
    """fsdp's gradient reduction is the AD transpose of its parameter
    all_gather — there is no separable sync phase, so segmentation must
    fail loudly, not silently mis-attribute. (zero1 IS segmentable:
    see the zero1 cases in the parity sweep above.)"""
    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        build_cifar_segments,
    )

    cfg = TrainConfig(**TINY_DP4_CFG, sync="fsdp")
    tr = Trainer(cfg, mesh=mesh4)
    with pytest.raises(ValueError, match="fsdp"):
        build_cifar_segments(tr)


def test_cifar_segments_reject_unbucketed_zero1(mesh4):
    """zero1 segmentation carves the BUCKETED schedule; the per-leaf
    fallback (sync_bucket_mb=0) has no bucket lanes to time."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        build_cifar_segments,
    )

    cfg = TrainConfig(**TINY_DP4_CFG, sync="zero1", sync_bucket_mb=0)
    tr = Trainer(cfg, mesh=mesh4)
    with pytest.raises(ValueError, match="bucket"):
        build_cifar_segments(tr)


def test_profile_phases_end_to_end(mesh4):
    """profile_phases: parity gate + the four-phase report with
    sink-ready records and a renderable table."""
    from cs744_pytorch_distributed_tutorial_tpu.obs.phases import (
        PHASE_NAMES,
        phase_records_from_stream,
        profile_phases,
        render_phase_table,
    )

    cfg = TrainConfig(
        **TINY_DP4_CFG, sync="allreduce", compute_dtype="float32"
    )
    tr, state, x, y, key = _cifar_step_inputs(mesh4, cfg)
    report = profile_phases(tr, state, x, y, key, iters=1)
    assert report.parity_ok
    assert tuple(p.name for p in report.phases) == PHASE_NAMES
    assert report.sync_exposed_ms >= 0.0
    assert report.phase("grad_sync").comm_bytes > 0
    assert report.phase("grad_sync").roofline == "comms"
    records = report.records(run="test")
    assert len(phase_records_from_stream(records)) == len(PHASE_NAMES) + 1
    table = render_phase_table(records)
    assert "grad_sync" in table and "sync_exposed_ms" in table


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
