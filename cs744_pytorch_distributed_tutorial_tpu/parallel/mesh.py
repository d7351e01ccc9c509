"""Device mesh construction and multi-host rendezvous.

Replaces the reference's process-group layer: ``init_process()`` sets
``MASTER_ADDR``/``MASTER_PORT`` and calls
``dist.init_process_group('gloo', rank, world_size)``
(``master/part2a/part2a.py:80-85``). On TPU the rendezvous is
``jax.distributed.initialize(coordinator, num_processes, process_id)`` —
a direct signature mirror — and the "process group" is a
``jax.sharding.Mesh`` laid out over ICI.

Unlike the reference, which hardcodes the world ``[0, 1, 2, 3]``
(``master/part2a/part2a.py:32``) and the divisor 4 in its averaging math
even though ``--num-nodes`` is a CLI flag, everything here generalizes to
``axis_size``.
"""

from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

# Canonical axis names. The reference only has data parallelism
# (SURVEY §2.3); MODEL_AXIS exists so tensor-parallel shardings slot in
# without reshaping the API.
DATA_AXIS = "data"
MODEL_AXIS = "model"


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    auto: bool = False,
) -> None:
    """Multi-host rendezvous; the ``init_process`` equivalent.

    Mirrors ``init_process(master_ip, rank, size, fn)`` at
    ``master/part2a/part2a.py:80-85`` — but where Gloo needs
    MASTER_ADDR/MASTER_PORT env vars and a TCPStore, JAX's coordination
    service takes the coordinator address directly. On Cloud TPU pods
    JAX can autodetect all three: pass ``auto=True`` (the CLI's
    ``--distributed`` flag) to run the no-arg autodetect rendezvous.

    With ``auto=False`` and no explicit args this is a no-op, so
    single-process runs can call it unconditionally.
    """
    explicit = not (
        coordinator_address is None and num_processes is None and process_id is None
    )
    if not (auto or explicit):
        return
    # Cross-process collectives on the CPU backend need an explicit
    # collectives implementation (XLA:CPU otherwise rejects multiprocess
    # computations outright). Opt into gloo before the backend
    # initializes — but only when the platform is pinned to cpu and the
    # user hasn't already chosen an implementation (e.g. mpi).
    platforms = jax.config.jax_platforms
    impl = jax.config.jax_cpu_collectives_implementation
    if (
        platforms
        and "cpu" in platforms.split(",")
        and impl in (None, "", "none")
    ):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def make_mesh(
    axes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axes`` maps axis name -> size, e.g. ``{"data": 4}`` for the
    reference's 4-rank data-parallel world or ``{"data": 2, "model": 4}``
    for a DP x TP grid. Default: a 1-D data mesh over all visible devices.

    On real hardware ``jax.make_mesh`` orders devices so the innermost
    axis rides the fastest ICI links; under
    ``--xla_force_host_platform_device_count`` the same code runs on
    virtual CPU devices (the reference's "4 CloudLab nodes" with no
    cluster — SURVEY §4).

    Every axis is ``AxisType.Auto`` whatever the device count:
    ``jax.make_mesh`` would otherwise hand back ``Explicit`` axes when
    the request covers all devices while the ``Mesh(...)`` built for a
    subset is ``Auto`` — two sharding-propagation regimes for one call.
    """
    if devices is None:
        devices = jax.devices()
    if axes is None:
        axes = {DATA_AXIS: len(devices)}
    names = tuple(axes.keys())
    shape = tuple(int(s) for s in axes.values())
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(
            f"mesh {dict(axes)} needs {need} devices, only {len(devices)} visible"
        )
    auto = (AxisType.Auto,) * len(names)
    if need == len(devices) and len(set(d.platform for d in devices)) == 1:
        return jax.make_mesh(shape, names, auto, devices=devices)
    dev_array = np.asarray(devices[:need]).reshape(shape)
    return Mesh(dev_array, names, axis_types=auto)


def interpret_kernels(mesh: Mesh) -> bool:
    """True when Pallas kernels must run in interpret mode for this mesh:
    its devices are not TPUs. Decided from the mesh the computation
    actually runs on, not the global default backend — a TPU host can
    drive a CPU test mesh."""
    return all(d.platform != "tpu" for d in mesh.devices.flat)


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for fully replicated values (params, opt state)."""
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding for a global batch split along its leading dim.

    The ``DistributedSampler`` analog at the array level: the reference
    shards the *dataset* per rank (``master/part2a/part2a.py:107``); here
    the global batch is one `jax.Array` whose leading dim is laid out
    along the mesh's data axis.
    """
    return NamedSharding(mesh, P(axis))


def device_stats_sharding(mesh: Mesh, axis: str = DATA_AXIS) -> NamedSharding:
    """Sharding for per-replica state (leading device axis), e.g. BatchNorm
    running statistics.

    The reference's DP keeps BN statistics local per rank — DDP's default,
    and the manual parts never sync BN buffers (SURVEY §7 hard part b).
    SPMD equivalent: store them with a leading ``[num_devices, ...]`` axis
    sharded along ``data`` so each replica owns its own stats. Today this
    is the same sharding as a data-sharded batch; it stays a named alias
    so per-replica state can move to its own layout without touching
    callers.
    """
    return batch_sharding(mesh, axis)


def host_to_global(tree, sharding: NamedSharding):
    """Place host values (each the FULL global array, identical on every
    process) onto ``sharding`` — which may span processes. Single-process
    (or fully addressable) this is ``device_put``; across processes each
    host contributes the slices its addressable devices own via
    ``jax.make_array_from_callback`` (``device_put`` rejects
    non-addressable shardings outright — the multi-host placement bug
    this helper exists to avoid)."""

    def put(x):
        if (
            isinstance(x, jax.Array)
            and not sharding.is_fully_addressable
            and x.sharding.is_equivalent_to(sharding, x.ndim)
        ):
            # Orbax-restored (or otherwise already-placed) global arrays
            # come back with the target sharding; re-placing a
            # process-spanning one would crash in np.asarray below.
            # An addressable one still goes through device_put: on a
            # one-device mesh a fresh, uncommitted array is *equivalent*
            # to the target yet keys the jit cache differently from the
            # step's own outputs, and the second step would recompile.
            return x
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
            x.dtype, jax.dtypes.prng_key
        ):
            # Typed PRNG keys can't round-trip through NumPy: place the
            # underlying uint32 data, re-wrap with the same impl.
            impl = jax.random.key_impl(x)
            placed = put(jax.random.key_data(x))
            return jax.random.wrap_key_data(placed, impl=impl)
        if sharding.is_fully_addressable:
            return jax.device_put(x, sharding)
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # A global array on a *different* process-spanning sharding:
            # np.asarray would raise 'spans non-addressable devices'.
            # Serve the target's local slices from the shards this
            # process owns (restore flows keep per-process coverage
            # aligned, e.g. replicated -> sharded on the same mesh).
            shards = [
                (
                    tuple(s_.indices(d) for s_, d in zip(sh.index, x.shape)),
                    np.asarray(sh.data),
                )
                for sh in x.addressable_shards
            ]

            def from_local(idx):
                want = tuple(
                    s_.indices(d) for s_, d in zip(idx, x.shape)
                )
                for have, data in shards:
                    if all(
                        h[0] <= w[0] and w[1] <= h[1]
                        for h, w in zip(have, want)
                    ):
                        rel = tuple(
                            slice(w[0] - h[0], w[1] - h[0])
                            for h, w in zip(have, want)
                        )
                        return data[rel]
                raise ValueError(
                    f"process owns no data for index {idx} of global array "
                    f"with shape {x.shape}; cross-process resharding via "
                    "host_to_global requires local coverage of the target's "
                    "slices"
                )

            return jax.make_array_from_callback(x.shape, sharding, from_local)
        arr = np.asarray(x)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    return jax.tree.map(put, tree)


def shard_global_batch(mesh: Mesh, *arrays: jax.Array | np.ndarray, axis: str = DATA_AXIS):
    """Place host arrays as data-sharded global jax.Arrays."""
    sharding = batch_sharding(mesh, axis)
    out = tuple(host_to_global(a, sharding) for a in arrays)
    return out[0] if len(out) == 1 else out


def shard_stacked_batches(
    mesh: Mesh, *arrays: jax.Array | np.ndarray, axis: str = DATA_AXIS
):
    """Place ``[num_steps, global_batch, ...]`` host arrays with the batch
    (second) dim sharded along the data axis — the layout
    ``Trainer.train_steps`` scans over (leading dim = scan steps)."""
    sharding = NamedSharding(mesh, P(None, axis))
    out = tuple(jax.device_put(a, sharding) for a in arrays)
    return out[0] if len(out) == 1 else out


def local_to_global_batch(mesh: Mesh, *arrays: np.ndarray, axis: str = DATA_AXIS):
    """Assemble a global sharded array from per-process local shards.

    Multi-host path: each host contributes its local slice (the
    ``DistributedSampler`` equivalent across hosts), glued into one
    global array via ``jax.make_array_from_process_local_data``.
    """
    sharding = batch_sharding(mesh, axis)
    out = tuple(
        jax.make_array_from_process_local_data(sharding, np.asarray(a)) for a in arrays
    )
    return out[0] if len(out) == 1 else out
