"""The reference's four mechanisms (parts 2a, 2a_extra, 2b, 3) plus
``ring`` and ``zero1`` over four real chips, through the CLI: one
semantics, six wire patterns. ``p2p_star``'s single-pair ``ppermute``
hops and ``ring``'s neighbour exchange meet the 2x2 ICI topology here;
on the CPU harness (``tests/test_sync_parity.py``) they only ever met
forced-host devices.

ResNet-18, float32, no augmentation, same seed: the strategies differ
only in the order four per-chip gradients are summed, so their loss
curves may differ by float32 reassociation and what a few SGD steps
make of it — the tolerance ``tests/test_sync_parity.py`` uses on CPU,
not more. Matmuls run at ``highest`` precision: at the
TPU default a float32 conv is a single bf16 pass, and a last-bit
difference in a weight then moves the loss by a bf16 ulp, which would
measure the rounding mode rather than the collectives.
"""

import json

import jax
import numpy as np
import pytest

from cs744_pytorch_distributed_tutorial_tpu import cli

STEPS = 4
RUNS = {
    "2a": ["--part", "2a"],
    "2a_extra": ["--part", "2a_extra"],
    "2b": ["--part", "2b"],
    "3": ["--part", "3"],
    "ring": ["--sync", "ring", "--num-devices", "4",
             "--global-batch-size", "256"],
    "zero1": ["--sync", "zero1", "--num-devices", "4",
              "--global-batch-size", "256"],
}

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="the reference's world is four ranks"
)


def run_losses(name, metrics_dir):
    """Per-step losses of one strategy's CLI run."""
    argv = RUNS[name] + [
        "--model", "resnet18", "--compute-dtype", "float32", "--no-augment",
        "--synthetic-data", "--synthetic-train-size", str(256 * STEPS),
        "--synthetic-test-size", "256", "--log-every", "1",
        "--metrics-dir", str(metrics_dir),
    ]
    with jax.default_matmul_precision("highest"):
        assert cli.main(argv) == 0
    with open(metrics_dir / "metrics.jsonl", encoding="utf-8") as f:
        records = [json.loads(line) for line in f]
    return [r["loss"] for r in records if r.get("kind") == "step"]


@pytest.fixture(scope="module")
def losses(tmp_path_factory):
    out = {
        name: run_losses(name, tmp_path_factory.mktemp(name)) for name in RUNS
    }
    print("strategy losses:", json.dumps(out))
    return out


def test_six_strategies_one_loss_curve(losses):
    table = np.asarray([losses[name] for name in RUNS])
    assert table.shape == (len(RUNS), STEPS) and np.isfinite(table).all()
    # Against allreduce at the CPU suite's tolerance (measured on a 2x2
    # v5e: five curves identical, zero1 off by 6e-8 at one step).
    ref = np.broadcast_to(table[list(RUNS).index("2b")], table.shape)
    np.testing.assert_allclose(table, ref, rtol=1e-6)
