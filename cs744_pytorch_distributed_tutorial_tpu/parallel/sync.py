"""Gradient-sync strategies — the reference's four parts as plug-ins.

The reference implements the same data-parallel semantics four times as
copy-pasted scripts whose ONLY difference is the gradient-sync section of
``train_model`` (SURVEY §2.1, §3.5):

=================  =============================================  =====================
strategy           reference                                      mechanism here
=================  =============================================  =====================
``none``           part1 (single process, no comm)                identity
``gather_scatter`` part2a  (``master/part2a/part2a.py:42-52``)    all_gather -> mean
``p2p_star``       part2a_extra (``part2a_extra.py:41-58``)       sequential ppermute star
``allreduce``      part2b  (``master/part2b/part2b.py:43-45``)    in-graph pmean
``ring``           (TPU-native explicit variant)                  ppermute ring allreduce
``auto``           part3 DDP (``master/part3/part3.py:116``)      engine-inserted pmean
=================  =============================================  =====================

A strategy is ``fn(grads_pytree, axis_name, axis_size) -> grads_pytree``,
applied per-leaf inside the jitted train step under ``shard_map`` — the
SPMD analog of the reference's ``for p in model.parameters():`` loops.
All strategies compute the same mean; they differ (deliberately) in the
communication structure traced into the graph. ``auto`` is special-cased
by the engine: like DDP, the user-visible step has *no* explicit comm and
the framework inserts the averaging itself.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from cs744_pytorch_distributed_tutorial_tpu.parallel import buckets as B
from cs744_pytorch_distributed_tutorial_tpu.parallel import collectives as C
from cs744_pytorch_distributed_tutorial_tpu.parallel.buckets import (
    DEFAULT_BUCKET_BYTES,
)

SyncFn = Callable[[jax.Array, str, int], jax.Array]

#: Quantization group size for the int8 strategies: each chunk of this
#: many elements shares one f32 scale, so the scale overhead is
#: 4/QUANT_CHUNK bytes per element (~1.6% at 256).
QUANT_CHUNK = 256


def _none(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """part1: single-process, no communication (``master/part1/part1.py``)."""
    return g


def _allreduce(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """part2b: pre-divide + all_reduce(SUM) == pmean
    (``master/part2b/part2b.py:43-45``, divisor generalized from the
    hardcoded 4 to ``axis_size``)."""
    return C.all_reduce_mean(g, axis_name)


def _gather_scatter(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """part2a: gather at rank 0, mean, scatter back
    (``master/part2a/part2a.py:42-52``)."""
    return C.gather_scatter_mean(g, axis_name)


def _p2p_star(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """part2a_extra: the fully-serialized isend/irecv parameter-server star
    (``master/part2a/part2a_extra.py:41-58``)."""
    return C.star_mean(g, axis_name, axis_size)


def _ring(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Explicit bandwidth-optimal ring allreduce over ppermute hops."""
    return C.ring_all_reduce_mean(g, axis_name, axis_size)


# --------------------------------------------------------------- int8 payloads
def _int8_allreduce_flat(
    x: jax.Array, axis_name: str, axis_size: int, quant_chunk: int = QUANT_CHUNK
) -> tuple[jax.Array, jax.Array]:
    """Quantized allreduce-mean of a flat f32 buffer; returns
    ``(mean, residual)`` where ``residual`` is everything THIS device
    knows the wire failed to deliver — the error-feedback payload.

    Structure (the reduce-scatter + all-gather decomposition with an int8
    wire format, per-SENDER scales keeping the reduction exact):

    1. pad to ``n * m * Q`` and quantize per chunk;
    2. ``all_to_all``: device d collects every sender's shard d —
       int8 codes + their f32 scales ((1 + 4/Q) bytes/element on the
       wire, vs 4 for f32);
    3. dequantize-and-sum in f32 (exact — each sender's own scale is
       applied, so no int8 overflow and no cross-sender rounding);
    4. requantize the averaged shard and ``all_gather`` codes + scales.

    The residual has two parts, both fully recoverable (two-stage EF):

    - sender error ``x - dequant(quant(x))`` — what this device's own
      contribution lost in step 2;
    - server error: device d is the reducer for shard d, so it alone
      knows ``shard_mean - dequant(requant(shard_mean))`` from step 4.
      It books ``n *`` that error into its shard of the residual — the
      next sync divides by n, so exactly the missing mean mass returns.

    Total payload per device: 2(n-1)/n * S * (1 + 4/Q) bytes — the same
    ring factor as a float allreduce at ~1/3.94 of the bytes.
    """
    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
        dequantize_chunked,
        quantize_chunked,
    )

    n = axis_size
    size = x.size
    m = -(-size // (n * quant_chunk))  # chunks per shard
    pad = n * m * quant_chunk - size
    xp = jnp.pad(x.astype(jnp.float32), (0, pad))
    q, scale = quantize_chunked(xp, quant_chunk)  # [n*m, Q], [n*m]
    own_full = dequantize_chunked(q, scale)
    if n == 1:
        return own_full[:size], (xp - own_full)[:size]
    q = q.reshape(n, m, quant_chunk)
    scale = scale.reshape(n, m)
    # After all_to_all: row i of the result is sender i's shard `my_idx`.
    q_all = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_all = lax.all_to_all(scale, axis_name, split_axis=0, concat_axis=0, tiled=False)
    shard_mean = (
        jnp.sum(q_all.astype(jnp.float32) * s_all[..., None], axis=0) / n
    ).reshape(-1)  # [m*Q]
    q2, s2 = quantize_chunked(shard_mean, quant_chunk)  # [m, Q], [m]
    q2g = lax.all_gather(q2, axis_name)  # [n, m, Q]
    s2g = lax.all_gather(s2, axis_name)  # [n, m]
    mean = dequantize_chunked(
        q2g.reshape(n * m, quant_chunk), s2g.reshape(-1)
    )[:size]
    # Two-stage residual: sender error everywhere + n * server error on
    # the shard this device reduced.
    resid = (xp - own_full).reshape(n, m * quant_chunk)
    server_err = shard_mean - dequantize_chunked(q2, s2)
    idx = lax.axis_index(axis_name)
    mine = lax.dynamic_index_in_dim(resid, idx, axis=0, keepdims=False)
    resid = lax.dynamic_update_index_in_dim(
        resid, mine + n * server_err, idx, axis=0
    )
    return mean, resid.reshape(-1)[:size]


def _int8_ring_flat(
    x: jax.Array, axis_name: str, axis_size: int, quant_chunk: int = QUANT_CHUNK
) -> tuple[jax.Array, jax.Array]:
    """EQuARX-style quantized ring allreduce-mean of a flat f32 buffer;
    returns ``(mean, residual)`` like ``_int8_allreduce_flat``.

    Reduce-scatter phase: the f32 running sum of each ring row is
    REQUANTIZED before every ``ppermute`` hop (int8 codes + per-chunk
    scales on the wire), and the receiver dequantizes and accumulates in
    f32. The accumulator is seeded from ``dequant(quant(x))`` so the
    initial quantization error lands in the residual and error feedback
    replays it; likewise the final quantization of the finished row —
    its owner books ``n *`` that error into its row of the residual
    (two-stage EF, see ``_int8_allreduce_flat``). Only the per-hop
    requantization of partial sums stays unfed-back — the (small) error
    the EQuARX design accepts for its bandwidth.
    All-gather phase: the finished row is quantized ONCE and its codes
    rotate verbatim — no re-rounding on the way out.
    """
    from cs744_pytorch_distributed_tutorial_tpu.ops.quant import (
        dequantize_chunked,
        quantize_chunked,
    )

    n = axis_size
    size = x.size
    cols = -(-size // n)
    cols = -(-cols // quant_chunk) * quant_chunk  # per-row chunk, Q-aligned
    pad = n * cols - size
    xp = jnp.pad(x.astype(jnp.float32), (0, pad))
    q0, s0 = quantize_chunked(xp, quant_chunk)
    own_full = dequantize_chunked(q0, s0)
    if n == 1:
        return own_full[:size], (xp - own_full)[:size]
    acc = own_full.reshape(n, cols)
    idx = lax.axis_index(axis_name)
    up = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(s, acc):
        send_row = (idx - s) % n
        payload = lax.dynamic_index_in_dim(acc, send_row, axis=0, keepdims=False)
        q, sc = quantize_chunked(payload, quant_chunk)
        q_r = lax.ppermute(q, axis_name, perm=up)
        sc_r = lax.ppermute(sc, axis_name, perm=up)
        recvd = dequantize_chunked(q_r, sc_r)
        recv_row = (idx - s - 1) % n
        current = lax.dynamic_index_in_dim(acc, recv_row, axis=0, keepdims=False)
        return lax.dynamic_update_index_in_dim(
            acc, current + recvd, recv_row, axis=0
        )

    acc = lax.fori_loop(0, n - 1, rs_step, acc)

    # Device i finished row (i + 1) mod n: average it and quantize once.
    done_row = (idx + 1) % n
    mine = lax.dynamic_index_in_dim(acc, done_row, axis=0, keepdims=False) / n
    qf, sf = quantize_chunked(mine, quant_chunk)  # [cols/Q, Q], [cols/Q]
    out_q = jnp.zeros((n,) + qf.shape, jnp.int8)
    out_s = jnp.zeros((n,) + sf.shape, jnp.float32)
    out_q = lax.dynamic_update_index_in_dim(out_q, qf, done_row, axis=0)
    out_s = lax.dynamic_update_index_in_dim(out_s, sf, done_row, axis=0)

    def ag_step(s, carry):
        out_q, out_s, qc, sc = carry
        q_r = lax.ppermute(qc, axis_name, perm=up)
        s_r = lax.ppermute(sc, axis_name, perm=up)
        recv_row = (idx - s) % n
        out_q = lax.dynamic_update_index_in_dim(out_q, q_r, recv_row, axis=0)
        out_s = lax.dynamic_update_index_in_dim(out_s, s_r, recv_row, axis=0)
        return (out_q, out_s, q_r, s_r)

    out_q, out_s, _, _ = lax.fori_loop(0, n - 1, ag_step, (out_q, out_s, qf, sf))
    mean = dequantize_chunked(
        out_q.reshape(-1, quant_chunk), out_s.reshape(-1)
    )[:size]
    # Two-stage residual: seed error everywhere + n * final-quantization
    # error on the row this device finished.
    resid = (xp - own_full).reshape(n, cols)
    final_err = mine - dequantize_chunked(qf, sf)
    row = lax.dynamic_index_in_dim(resid, done_row, axis=0, keepdims=False)
    resid = lax.dynamic_update_index_in_dim(
        resid, row + n * final_err, done_row, axis=0
    )
    return mean, resid.reshape(-1)[:size]


def _int8_allreduce(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Leaf-wise int8 allreduce (residual DISCARDED — for standalone
    ``sync_grads`` use; the engine routes int8 syncs through
    ``sync_grads_compressed`` to keep the error-feedback state)."""
    mean, _ = _int8_allreduce_flat(g.reshape(-1), axis_name, axis_size)
    return mean.reshape(g.shape).astype(g.dtype)


def _int8_ring(g: jax.Array, axis_name: str, axis_size: int) -> jax.Array:
    """Leaf-wise EQuARX-style int8 ring allreduce (residual discarded)."""
    mean, _ = _int8_ring_flat(g.reshape(-1), axis_name, axis_size)
    return mean.reshape(g.shape).astype(g.dtype)


# ``auto`` maps to allreduce numerics; the engine treats it as "framework
# inserts the sync" (DDP automation) rather than a user-plugged loop.
# ``zero1`` is identity HERE because its reduce-scatter is fused into the
# sharded-optimizer update (parallel/zero.py) — grads leave the loss
# local and the averaging happens chunk-wise inside ``Zero1SGD.apply``.
# ``fsdp`` likewise: its reduce-scatter is the AD transpose of the
# parameter all_gather (parallel/zero.py FsdpSGD), so no grad-sync pass
# exists to plug in.
SYNC_STRATEGIES: dict[str, SyncFn] = {
    "none": _none,
    "allreduce": _allreduce,
    "gather_scatter": _gather_scatter,
    "p2p_star": _p2p_star,
    "ring": _ring,
    "auto": _allreduce,
    "zero1": _none,
    "fsdp": _none,
    "int8_allreduce": _int8_allreduce,
    "int8_ring": _int8_ring,
}

#: Strategies whose outputs the VMA replication checker cannot statically
#: prove replicated (axis_index-routed selects; ``all_gather`` outputs),
#: so the enclosing ``shard_map`` needs ``check_vma=False``.
UNCHECKED_REPLICATION = {
    "p2p_star",
    "ring",
    "gather_scatter",
    "zero1",
    "fsdp",
    "int8_allreduce",
    "int8_ring",
}

#: Strategies whose collective is elementwise-mean over flat data, so the
#: DDP-style bucketed path below may coalesce leaves into flat buffers.
_BUCKETED = {"allreduce", "ring"}


def get_sync(name: str) -> SyncFn:
    try:
        return SYNC_STRATEGIES[name]
    except KeyError:
        raise ValueError(
            f"unknown sync strategy {name!r}; choose from {sorted(SYNC_STRATEGIES)}"
        ) from None


def sync_grads(
    grads,
    name: str,
    axis_name: str,
    axis_size: int,
    bucket_bytes: int | None = DEFAULT_BUCKET_BYTES,
):
    """Apply strategy ``name`` over a gradient pytree.

    For ``allreduce`` and ``ring`` the DEFAULT path is bucketed: the tree
    is coalesced into a few flat buffers (``parallel/buckets.py``) and one
    collective per bucket replaces one per leaf — DDP's bucketing reducer,
    here as layout math. Bitwise-identical to the per-leaf path: ``pmean``
    is elementwise, and the ring layout preserves each element's ring-row
    (hence its summation order). ``bucket_bytes=None``/``0`` restores the
    per-leaf tracing; other strategies always trace per leaf (their
    communication SHAPE — star hops, gather trees — is the point).
    """
    fn = get_sync(name)
    # named_scope: pure HLO metadata (zero jaxpr eqns, graftcheck-TA003
    # invisible) that labels the collective rows in Perfetto captures
    # (docs/observability.md has the label table).
    if bucket_bytes and name in _BUCKETED and axis_size > 1:
        with jax.named_scope(f"graftscope/sync/{name}/bucketed"):
            rows = axis_size if name == "ring" else 0
            layout = B.bucket_layout(grads, bucket_bytes, rows=rows)
            bufs = B.flatten_for_sync(grads, layout)
            if name == "ring":
                synced = [
                    C.ring_all_reduce_rows(buf, axis_name, axis_size) / axis_size
                    for buf in bufs
                ]
            else:
                synced = [C.all_reduce_mean(buf, axis_name) for buf in bufs]
            return B.unflatten(synced, layout)
    with jax.named_scope(f"graftscope/sync/{name}"):
        return C.tree_map_sync(lambda g: fn(g, axis_name, axis_size), grads)


def sync_grads_compressed(
    grads,
    ef,
    name: str,
    axis_name: str,
    axis_size: int,
    *,
    bucket_bytes: int | None = DEFAULT_BUCKET_BYTES,
    quant_chunk: int = QUANT_CHUNK,
):
    """Int8-quantized gradient sync with error feedback.

    Per bucket: compress-and-sync ``b = g + ef`` (the gradient plus the
    residual this device failed to transmit last step), and carry forward
    the two-stage residual the wire kernel reports — sender quantization
    error plus this device's share of the reduce-side requantization
    error. That is EF-SGD's memory, which makes the compressed
    trajectory track the uncompressed one instead of accumulating
    quantization bias. ``ef`` is a pytree of f32 leaves shaped like
    ``grads`` (per-DEVICE state: each replica's residual is its own).
    Returns ``(mean_grads, new_ef)``.

    ``name`` picks the wire algorithm: ``int8_ring``/``ring`` the
    per-hop-requantizing ring, anything else the all_to_all + all_gather
    form. Bucketing always applies (``bucket_bytes=None`` means one
    bucket per leaf) so quantization chunks span leaf boundaries and tiny
    leaves don't each pay a collective.
    """
    flat_fn = (
        _int8_ring_flat if name in ("ring", "int8_ring") else _int8_allreduce_flat
    )
    wire = "int8_ring" if name in ("ring", "int8_ring") else "int8_allreduce"
    with jax.named_scope(f"graftscope/sync/{wire}"):
        layout = B.bucket_layout(grads, bucket_bytes or B.DEFAULT_BUCKET_BYTES, rows=0)
        g_bufs = B.flatten_for_sync(grads, layout)
        e_bufs = B.flatten_for_sync(ef, layout)
        means, residuals = [], []
        for g, e in zip(g_bufs, e_bufs):
            dtype = g.dtype
            b = g.astype(jnp.float32) + e.astype(jnp.float32)
            mean, resid = flat_fn(b, axis_name, axis_size, quant_chunk)
            means.append(mean.astype(dtype))
            residuals.append(resid)
        return B.unflatten(means, layout), B.unflatten(residuals, layout)


def sync_wire_bytes(
    params,
    name: str,
    axis_size: int,
    grad_compress: str = "none",
    *,
    quant_chunk: int = QUANT_CHUNK,
    bucket_bytes: int | None = None,
    overlap: bool = False,
) -> int:
    """Per-step gradient-sync payload bytes of the ACTIVE configuration.

    This is the strategy's own accounting (``buckets.sync_bytes_per_step``)
    resolved through the same knobs the engines resolve: ``name`` is the
    ``cfg.sync`` strategy, and ``grad_compress="int8"`` reroutes the wire
    math to the quantized payload regardless of the base strategy —
    exactly what ``sync_grads_compressed`` does to the collectives. Pass
    the engine's ``bucket_bytes`` so the int8 paths count their padded
    payload exactly (graftcheck TA003 holds this number to within 1% of
    the bytes derived from the traced jaxpr). ``overlap=True`` selects
    the overlapped schedule's reverse-order bucket layout
    (``parallel/overlap.py``) — same float bytes, but the int8 padding
    follows the reversed bucket partition. The telemetry layer records
    this number as ``grad_sync_bytes`` per step.
    """
    if name == "zero1" and grad_compress == "int8":
        # zero1's int8+EF wire flattens the rows=axis_size chunk
        # buckets through the quantized allreduce and still pays the
        # float delta all_gather — its own accounting branch.
        strategy = "zero1_int8"
    elif grad_compress == "int8" or name in ("int8_allreduce", "int8_ring"):
        strategy = "int8_ring" if name in ("ring", "int8_ring") else "int8_allreduce"
    else:
        strategy = name
    return B.sync_bytes_per_step(
        params,
        strategy,
        axis_size,
        quant_chunk=quant_chunk,
        bucket_bytes=bucket_bytes,
        reverse=overlap,
    )


# ----------------------------------------------------- schedule contracts
def sync_units(
    params,
    name: str,
    axis_size: int,
    *,
    bucket_bytes: int | None = DEFAULT_BUCKET_BYTES,
    grad_compress: str = "none",
    overlap: bool = False,
) -> int:
    """How many sync UNITS one pass over ``params`` issues collectives
    for: buckets where the strategy coalesces (``allreduce``/``ring``
    with bucketing on, every int8 path, bucketed zero1/fsdp), leaves
    everywhere else. This mirrors the routing in :func:`sync_grads`,
    :func:`sync_grads_compressed` and ``zero.Zero1SGD.apply`` exactly —
    it is the unit count :func:`expected_collective_schedule` scales by.
    ``overlap=True`` counts the overlapped schedule's reverse-order
    buckets (``parallel/overlap.py``: always bucketed, same collective
    classes per unit, but the reversed greedy walk can partition the
    tree into a different number of buckets).
    """
    leaves = len(jax.tree.leaves(params))
    if axis_size <= 1 or name == "none":
        return leaves
    # zero1/fsdp resolve FIRST: their units follow the rows=axis_size
    # chunk layout even when the int8 wire rides on top (zero1's
    # quantized allreduce flattens the same [axis_size, cols] buckets).
    if name in ("zero1", "fsdp"):
        if bucket_bytes:
            layout = B.bucket_layout(
                params, bucket_bytes, rows=axis_size, reverse=overlap
            )
            return len(layout.bucket_cols)
        return leaves
    if grad_compress == "int8" or name in ("int8_allreduce", "int8_ring"):
        layout = B.bucket_layout(
            params, bucket_bytes or B.DEFAULT_BUCKET_BYTES, rows=0, reverse=overlap
        )
        return len(layout.bucket_cols)
    if (bucket_bytes or overlap) and name in _BUCKETED:
        rows = axis_size if name == "ring" else 0
        layout = B.bucket_layout(
            params,
            bucket_bytes or B.DEFAULT_BUCKET_BYTES,
            rows=rows,
            reverse=overlap,
        )
        return len(layout.bucket_cols)
    return leaves


def expected_collective_schedule(
    name: str,
    axis_size: int,
    units: int,
    *,
    grad_compress: str = "none",
    syncs_per_step: int = 1,
) -> dict[str, int] | None:
    """The gradient-collective contract of one train step: canonical
    collective class -> count, for ``units`` sync units synced
    ``syncs_per_step`` times. graftcheck's TA003 asserts the traced jaxpr
    contains EXACTLY this multiset of non-trivial (payload beyond a
    scalar, group beyond one device) collectives — a drifted count means
    a strategy regressed into extra hops or silently stopped syncing.

    Counts per unit, ``n = axis_size``:

    - ``allreduce``/``auto``: 1 psum;
    - ``ring``/``p2p_star``: 2(n-1) ppermutes (reduce-scatter +
      all-gather hop sequences; the star serializes the same hop count
      through rank 0);
    - ``gather_scatter``: 1 all_gather (the mean + broadcast stay local);
    - ``int8_allreduce``: 2 all_to_alls + 2 all_gathers (codes and
      scales travel separately in each phase);
    - ``int8_ring``: 4(n-1) ppermutes (codes + scales per hop, both
      phases);
    - ``zero1``/``fsdp``: delegated to ``parallel.zero``'s own contract
      (with ``grad_compress="int8"``, zero1's int8+EF wire contract —
      2 all_to_alls + 3 all_gathers per unit, no reduce_scatter);
    - ``none`` (or 1-sized axis): no collectives.

    Returns None for unknown names (no contract to assert).
    """
    from cs744_pytorch_distributed_tutorial_tpu.parallel.zero import (
        fsdp_collective_schedule,
        zero1_collective_schedule,
        zero1_int8_collective_schedule,
    )

    n = int(axis_size)
    u = int(units) * int(syncs_per_step)
    if name == "none" or n <= 1:
        return {}
    if name == "zero1" and grad_compress == "int8":
        return zero1_int8_collective_schedule(u, n)
    if grad_compress == "int8" or name in ("int8_allreduce", "int8_ring"):
        if name in ("ring", "int8_ring"):
            return {"ppermute": 4 * (n - 1) * u}
        return {"all_to_all": 2 * u, "all_gather": 2 * u}
    if name in ("allreduce", "auto"):
        return {"psum": u}
    if name in ("ring", "p2p_star"):
        return {"ppermute": 2 * (n - 1) * u}
    if name == "gather_scatter":
        return {"all_gather": u}
    if name == "zero1":
        return zero1_collective_schedule(u, n)
    if name == "fsdp":
        return fsdp_collective_schedule(u, n)
    return None
