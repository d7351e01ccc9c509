"""True multi-process rendezvous: two OS processes join through
``parallel.mesh.initialize`` (the ``init_process`` mirror,
``master/part2a/part2a.py:80-85``) and run a cross-process psum over a
global array assembled with ``local_to_global_batch`` — the reference's
4-CloudLab-node flow, on one machine. Every other test simulates
multi-device single-process; this one exercises the actual coordination
service + cross-process collective path."""

import os
import socket
import subprocess
import sys

import pytest

# Spawns whole multi-process jax clusters; ~10s+ per case.
pytestmark = pytest.mark.slow

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np

sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    initialize, local_to_global_batch,
)

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)  # the init_process mirror
assert jax.process_count() == 2
devices = jax.devices()
assert len(devices) == 2, devices

mesh = make_mesh({{"data": 2}}, devices=devices)
# Each process contributes ITS shard of the global batch (the
# DistributedSampler analog across hosts).
local = np.full((2, 4), float(rank + 1), np.float32)
global_batch = local_to_global_batch(mesh, local)
assert global_batch.shape == (4, 4)

from jax.sharding import NamedSharding, PartitionSpec as P

@jax.jit
def global_sum(x):
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P())
    ).sum()

total = float(global_sum(global_batch))
# rows: two of 1.0 (rank 0) + two of 2.0 (rank 1), 4 columns each
assert total == 2 * 4 * 1.0 + 2 * 4 * 2.0, total
print(f"rank {{rank}} ok total={{total}}")
"""


_LOADER_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np

sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.data import BatchLoader
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import initialize

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)
mesh = make_mesh({{"data": 2}}, devices=jax.devices())

# Identical host data on both processes; the loader's multi-host branch
# has each process contribute only its contiguous slice.
images = np.arange(8 * 2 * 2 * 3, dtype=np.uint8).reshape(8, 2, 2, 3)
labels = np.arange(8, dtype=np.int32)
loader = BatchLoader(images, labels, 4, mesh=mesh, shuffle=True, seed=3)

from jax.sharding import NamedSharding, PartitionSpec as P

@jax.jit
def reduce_sum(x, y):
    rep = NamedSharding(mesh, P())
    return (
        jax.lax.with_sharding_constraint(x, rep).astype(np.float32).sum()
        + jax.lax.with_sharding_constraint(y, rep).sum()
    )

totals = [float(reduce_sum(x, y)) for x, y in loader.epoch(0)]

# Reference: the same deterministic plan computed host-side.
from cs744_pytorch_distributed_tutorial_tpu.data.sampler import (
    epoch_permutation,
)
order = epoch_permutation(8, 3, 0, True)
expect = [
    float(images[order[b*4:(b+1)*4]].astype(np.float32).sum()
          + labels[order[b*4:(b+1)*4]].sum())
    for b in range(2)
]
assert totals == expect, (totals, expect)
print(f"rank {{rank}} loader ok {{totals}}")
"""


def _run_pair(script_template, tmp_path, repo, marker, extra_args=()):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = script_template.format(repo=repo, coord=f"127.0.0.1:{port}")
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",  # exactly one CPU device per process
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(rank), *map(str, extra_args)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=str(tmp_path),
        )
        for rank in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multi-process run hung; partial output: {outs}")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} {marker}" in out
    return outs


_FIT_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import initialize
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)
mesh = make_mesh({{"data": 2}}, devices=jax.devices())
cfg = TrainConfig(model="tiny_cnn", sync="allreduce", num_devices=2,
                  global_batch_size=8, synthetic_data=True,
                  synthetic_train_size=32, synthetic_test_size=16, epochs=1)
tr = Trainer(cfg, mesh=mesh)
state, hist = tr.fit(dataset=synthetic_cifar10(32, 16, seed=0))
loss = hist["train_loss"][-1][2]
acc = hist["eval"][-1]["accuracy"]
print(f"rank {{rank}} fit ok loss={{loss:.6f}} acc={{acc:.4f}}")
"""


_RESUME_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    initialize, shard_global_batch,
)
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer
from cs744_pytorch_distributed_tutorial_tpu.utils.checkpoint import Checkpointer

rank = int(sys.argv[1])
ckdir = "__CKDIR__"
initialize({coord!r}, 2, rank)
mesh = make_mesh({{"data": 2}}, devices=jax.devices())
# zero1: the optimizer momentum shards over the data axis, so the
# checkpointed opt_state leaves SPAN both processes — exactly the
# sharding family whose restore->place_state path used to crash in
# host_to_global's np.asarray fallback.
cfg = TrainConfig(model="tiny_cnn", sync="zero1", num_devices=2,
                  global_batch_size=8, synthetic_data=True,
                  synthetic_train_size=32, synthetic_test_size=16)
tr = Trainer(cfg, mesh=mesh)
state = tr.init()
ds = synthetic_cifar10(8, 8, seed=0)
x, y = shard_global_batch(mesh, ds.train_images[:8], ds.train_labels[:8])
key = jax.random.key(cfg.seed)
for _ in range(3):
    state, m = tr.train_step(state, x, y, key)

ckpt = Checkpointer(ckdir)
ckpt.save(state, wait=True)

# Uninterrupted continuation = the reference trajectory.
ref = state
for _ in range(2):
    ref, mref = tr.train_step(ref, x, y, key)
ref_loss = float(mref["loss"])

# "Restart": a fresh Trainer restores the checkpoint and resumes.
tr2 = Trainer(cfg, mesh=mesh)
template = tr2.init()
ckpt2 = Checkpointer(ckdir)
restored = ckpt2.restore_latest(template)
assert restored is not None
assert int(jax.device_get(restored.step)) == 3
st2 = tr2.place_state(restored)  # the multi-host placement path
for _ in range(2):
    st2, m2 = tr2.train_step(st2, x, y, key)
loss2 = float(m2["loss"])
assert loss2 == ref_loss, (loss2, ref_loss)
# params are replicated under zero1: compare resumed vs uninterrupted.
pa = jax.device_get(jax.tree.leaves(ref.params)[0])
pb = jax.device_get(jax.tree.leaves(st2.params)[0])
np.testing.assert_array_equal(pa, pb)
ckpt.close(); ckpt2.close()
print(f"rank {{rank}} resume ok loss={{loss2:.6f}}")
"""


def test_two_process_checkpoint_save_restore_resume(tmp_path):
    """Multi-host checkpointing: both processes save sharded (zero1)
    state into one Orbax directory, a fresh trainer restores it, and the
    resumed trajectory is bit-identical to the uninterrupted one on both
    ranks — the save->kill->restore->resume flow of SURVEY §5.4 at real
    process scope."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ckdir = str(tmp_path / "ckpt")
    script_template = _RESUME_WORKER.replace("__CKDIR__", ckdir)
    outs = _run_pair(script_template, tmp_path, repo, "resume ok")
    vals = [o.strip().splitlines()[-1].split("ok ", 1)[1] for o in outs]
    assert vals[0] == vals[1], vals


def test_full_trainer_fit_across_two_processes(tmp_path):
    """The reference's whole multi-node flow — rendezvous, sharded data,
    allreduce training, psum eval aggregation — over a REAL process
    boundary; both ranks report identical loss and accuracy."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _run_pair(_FIT_WORKER, tmp_path, repo, "fit ok")
    vals = [o.strip().splitlines()[-1].split("ok ", 1)[1] for o in outs]
    assert vals[0] == vals[1], vals  # bit-identical metrics on both ranks


def test_batchloader_multi_host_branch(tmp_path):
    """BatchLoader's process-local contribution path, exercised across a
    REAL process boundary: both ranks see the full deterministic batch
    stream as global arrays."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _run_pair(_LOADER_WORKER, tmp_path, repo, "loader ok")


def test_two_process_rendezvous_and_cross_process_reduction(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with socket.socket() as s:  # free port for the coordination service
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    script = _WORKER.format(repo=repo, coord=coord)

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "",  # exactly one CPU device per process
    }
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", script, str(rank)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, cwd=str(tmp_path),
        )
        for rank in (0, 1)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outs.append(out)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(f"multi-process rendezvous hung; partial output: {outs}")
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"rank {rank} ok" in out


_PIPELINE_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np

sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import initialize
from cs744_pytorch_distributed_tutorial_tpu.parallel.pipeline import (
    PipelineLMConfig, PipelineLMTrainer,
)

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)
# One device per process -> the PIPE axis spans the process boundary:
# every stage hop (forward ppermute, 1F1B reverse ppermute) is a real
# cross-process transfer, the reference's multi-node p2p flow
# (master/part2a/part2a_extra.py) doing pipeline work.
mesh = make_mesh({{"data": 1, "pipe": 2}}, devices=jax.devices())
cfg = PipelineLMConfig(
    vocab_size=64, num_layers=2, num_heads=2, d_model=32, d_ff=64,
    max_seq_len=32, data_parallel=1, pipeline_parallel=2,
    num_microbatches=2, global_batch_size=4, seq_len=16,
    schedule="1f1b", seed=5,
)
tr = PipelineLMTrainer(cfg, mesh=mesh)
params, opt = tr.init()
toks = np.random.default_rng(0).integers(0, 64, (4, 17), dtype=np.int64)
x, y = tr.shard_batch(toks)
losses = []
for s in range(3):
    params, opt, m = tr.train_step(params, opt, x, y, s)
    losses.append(round(float(m["loss"]), 8))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
print(f"rank {{rank}} pipeline ok losses={{losses}}")
"""


def test_pipeline_stages_across_two_processes(tmp_path):
    """The pipeline engine's stage hops crossing a REAL process
    boundary: pipe=2 over two single-device processes, 1F1B schedule —
    forward and reverse ppermutes ride the inter-process transport, and
    both ranks observe identical losses."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _run_pair(_PIPELINE_WORKER, tmp_path, repo, "pipeline ok")
    loss_lines = [
        next(l for l in out.splitlines() if "losses=" in l) for out in outs
    ]
    assert loss_lines[0].split("losses=")[1] == loss_lines[1].split(
        "losses="
    )[1], loss_lines


_RING_SEQ_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np

sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import initialize
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)
# One device per process -> the SEQ axis spans the process boundary:
# every ring-attention hop (forward K/V rotation AND its AD-transposed
# reverse ring in backward) is a real cross-process transfer — the
# long-context analog of the reference's multi-node p2p flow.
mesh = make_mesh({{"data": 1, "seq": 2}}, devices=jax.devices())
cfg = LMConfig(
    vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
    max_seq_len=64, attention_impl="ring", data_parallel=1,
    seq_parallel=2, global_batch_size=4, seq_len=16, use_rope=True,
    seed=5,
)
tr = LMTrainer(cfg, mesh=mesh)
params, opt = tr.init()
toks = np.random.default_rng(0).integers(0, 64, (4, 17), dtype=np.int64)
x, y = tr.shard_batch(toks)
losses = []
for s in range(3):
    params, opt, m = tr.train_step(params, opt, x, y, s)
    losses.append(round(float(m["loss"]), 8))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0], losses
print(f"rank {{rank}} ringseq ok losses={{losses}}")
"""


def test_ring_attention_across_two_processes(tmp_path):
    """Sequence-parallel ring attention crossing a REAL process
    boundary: seq=2 over two single-device processes — the ring's
    ppermute hops (and their reverse-ring transposes in backward) ride
    the inter-process transport; both ranks observe identical losses,
    and those losses match a single-process dense-attention run of the
    same config (the ring is exactly a layout change)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _run_pair(_RING_SEQ_WORKER, tmp_path, repo, "ringseq ok")
    loss_lines = [
        next(l for l in out.splitlines() if "losses=" in l) for out in outs
    ]
    assert loss_lines[0].split("losses=")[1] == loss_lines[1].split(
        "losses="
    )[1], loss_lines

    # Single-process oracle: same config at seq_parallel=1 / dense.
    import jax
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import (
        LMConfig,
        LMTrainer,
    )

    cfg = LMConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", data_parallel=1,
        seq_parallel=1, global_batch_size=4, seq_len=16, use_rope=True,
        seed=5,
    )
    tr = LMTrainer(
        cfg,
        mesh=make_mesh({"data": 1, "seq": 1}, devices=jax.devices()[:1]),
    )
    params, opt = tr.init()
    toks = np.random.default_rng(0).integers(0, 64, (4, 17), dtype=np.int64)
    x, y = tr.shard_batch(toks)
    want = []
    for s in range(3):
        params, opt, m = tr.train_step(params, opt, x, y, s)
        want.append(float(m["loss"]))
    import ast

    got = ast.literal_eval(loss_lines[0].split("losses=")[1])
    np.testing.assert_allclose(got, want, rtol=2e-5)


_STEP_PARITY_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
    initialize, shard_global_batch,
)
from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

rank = int(sys.argv[1])
initialize({coord!r}, 2, rank)
mesh = make_mesh({{"data": 2}}, devices=jax.devices())
cfg = TrainConfig(model="tiny_cnn", sync="allreduce", sync_bn=True,
                  augment=False, num_devices=2, global_batch_size=8,
                  synthetic_data=True, synthetic_train_size=8,
                  synthetic_test_size=8, seed=0)
tr = Trainer(cfg, mesh=mesh)
state = tr.init()
ds = synthetic_cifar10(8, 8, seed=0)
x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
key = jax.random.key(cfg.seed)
losses = []
for _ in range(3):
    state, m = tr.train_step(state, x, y, key)
    losses.append(round(float(jax.device_get(m["loss"])), 8))
print(f"rank {{rank}} stepparity ok losses={{losses}}")
"""


def test_train_step_psum_parity_across_two_processes(tmp_path):
    """The elastic demo worker's exact step recipe (tiny-CNN allreduce,
    sync_bn, fixed batch, trainer-folded PRNG) over a REAL process
    boundary: the grad psum and BN-stat psum cross the inter-process
    transport, both ranks observe identical losses, and the trajectory
    matches a single-process 2-virtual-device oracle — the parity claim
    the graftelastic e2e builds on, isolated from the launcher."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _run_pair(_STEP_PARITY_WORKER, tmp_path, repo, "stepparity ok")
    loss_lines = [
        next(l for l in out.splitlines() if "losses=" in l) for out in outs
    ]
    assert loss_lines[0].split("losses=")[1] == loss_lines[1].split(
        "losses="
    )[1], loss_lines

    import ast

    import jax
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.config import TrainConfig
    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_cifar10
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import (
        shard_global_batch,
    )
    from cs744_pytorch_distributed_tutorial_tpu.train import Trainer

    cfg = TrainConfig(model="tiny_cnn", sync="allreduce", sync_bn=True,
                      augment=False, num_devices=2, global_batch_size=8,
                      synthetic_data=True, synthetic_train_size=8,
                      synthetic_test_size=8, seed=0)
    mesh = make_mesh({"data": 2}, devices=jax.devices()[:2])
    tr = Trainer(cfg, mesh=mesh)
    state = tr.init()
    ds = synthetic_cifar10(8, 8, seed=0)
    x, y = shard_global_batch(mesh, ds.train_images, ds.train_labels)
    key = jax.random.key(cfg.seed)
    oracle = []
    for _ in range(3):
        state, m = tr.train_step(state, x, y, key)
        oracle.append(float(jax.device_get(m["loss"])))
    got = ast.literal_eval(loss_lines[0].split("losses=")[1])
    np.testing.assert_allclose(got, oracle, rtol=2e-5)


_ZERO_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
import numpy as np
sys.path.insert(0, {repo!r})
from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
from cs744_pytorch_distributed_tutorial_tpu.parallel.mesh import initialize
from cs744_pytorch_distributed_tutorial_tpu.train import LMConfig, LMTrainer

rank = int(sys.argv[1])
mode = sys.argv[2]  # "zero1" | "fsdp"
assert mode in ("zero1", "fsdp"), mode  # typo'd mode would pass trivially
initialize({coord!r}, 2, rank)
mesh = make_mesh({{"data": 2, "seq": 1}}, devices=jax.devices())
cfg = LMConfig(
    vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
    max_seq_len=64, attention_impl="dense", data_parallel=2,
    seq_parallel=1, global_batch_size=4, seq_len=16, use_rope=True,
    seed=5, zero1=(mode == "zero1"), fsdp=(mode == "fsdp"),
)
tr = LMTrainer(cfg, mesh=mesh)
params, opt = tr.init()
tokens = synthetic_tokens(16, cfg.seq_len, cfg.vocab_size, seed=11)
losses = []
for s in range(3):
    x, y = tr.shard_batch(tokens[s * 4 : s * 4 + 4])
    params, opt, m = tr.train_step(params, opt, x, y)
    losses.append(round(float(m["loss"]), 6))
print(f"rank {{rank}} zerolm ok losses={{losses}}")
"""


@pytest.mark.parametrize("mode", ["zero1", "fsdp"])
def test_zero_sharded_optimizer_across_two_processes(mode, tmp_path):
    """ZeRO's collective pair crossing a REAL process boundary: with
    dp=2 spanning two single-device processes, every per-leaf
    psum_scatter (mean-grad chunking) and all_gather (delta/param
    unshard) rides the inter-process transport — the fourth kind of
    2-real-process evidence (after DP metrics, pipeline hops, ring
    attention). Both ranks observe identical losses, and the
    trajectory matches the REPLICATED-optimizer single-process oracle
    on a 2-virtual-device mesh (the ZeRO identity, now over the real
    transport)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    outs = _run_pair(_ZERO_WORKER, tmp_path, repo, "zerolm ok",
                     extra_args=[mode])
    loss_lines = [
        next(l for l in out.splitlines() if "losses=" in l) for out in outs
    ]
    assert loss_lines[0].split("losses=")[1] == loss_lines[1].split(
        "losses="
    )[1], loss_lines

    import ast

    import jax
    import numpy as np

    from cs744_pytorch_distributed_tutorial_tpu.data import synthetic_tokens
    from cs744_pytorch_distributed_tutorial_tpu.parallel import make_mesh
    from cs744_pytorch_distributed_tutorial_tpu.train import (
        LMConfig,
        LMTrainer,
    )

    cfg = LMConfig(
        vocab_size=64, num_layers=2, num_heads=4, d_model=32, d_ff=64,
        max_seq_len=64, attention_impl="dense", data_parallel=2,
        seq_parallel=1, global_batch_size=4, seq_len=16, use_rope=True,
        seed=5,
    )
    mesh = make_mesh({"data": 2, "seq": 1}, devices=jax.devices()[:2])
    tr = LMTrainer(cfg, mesh=mesh)
    params, opt = tr.init()
    tokens = synthetic_tokens(16, cfg.seq_len, cfg.vocab_size, seed=11)
    oracle = []
    for s in range(3):
        x, y = tr.shard_batch(tokens[s * 4 : s * 4 + 4])
        params, opt, m = tr.train_step(params, opt, x, y)
        oracle.append(float(m["loss"]))
    got = ast.literal_eval(loss_lines[0].split("losses=")[1])
    np.testing.assert_allclose(got, oracle, rtol=2e-5)
