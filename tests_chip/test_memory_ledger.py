"""graftmem's static ledger against what the device really allocates."""

import jax

from cs744_pytorch_distributed_tutorial_tpu.analysis.trace import (
    get_entrypoints,
    load_builtin_entrypoints,
)
from cs744_pytorch_distributed_tutorial_tpu.analysis.trace.memory import (
    measure_entry,
)


def test_ledger_cross_checks_live_memory_stats():
    """The static ledger must be a floor on what the device actually
    allocates: after one real step, peak bytes-in-use covers the
    compiled args+outputs+temps (docs/observability.md contract)."""
    load_builtin_entrypoints()
    (entry,) = get_entrypoints(["cifar"])
    step = entry.build()
    ledger = measure_entry(entry, step)
    out = step.fn(*step.args)
    jax.block_until_ready(out)
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    assert peak >= ledger["total_bytes"]
