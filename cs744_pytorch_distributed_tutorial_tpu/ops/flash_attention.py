"""Flash attention as a Pallas TPU kernel — the on-chip hot path.

The reference has no attention (SURVEY §5.7); this kernel is the
single-chip compute half of the framework's long-context story:
``parallel/ring_attention.py`` moves K/V blocks BETWEEN chips over ICI,
and this kernel is the within-chip blockwise attention that never
materializes the [T, T] score matrix — scores live tile-at-a-time in
VMEM, with the flash-style running (max, normalizer, accumulator) update.

Layout: [B, T, H, D] (the model zoo's convention), computed per
(batch*head) over a grid of query blocks. K/V for one (batch, head) ride
in VMEM whole (T*D*2 bytes each in bf16 — 1 MB at T=4096, D=128, well
inside the ~16 MB budget); the kernel loops over key tiles. Softmax
statistics accumulate in float32 regardless of input dtype (bfloat16
inputs hit the MXU; the normalizer stays full precision).

Causal tiling: ``flash_tile_plan`` picks the sizes from the call's shape
and says what every grid step computes. A step holds a block of up to
1,024 rows, cut into sub-blocks; each sub-block runs the whole tiles
that lie clear of the diagonal in a runtime loop with no mask (none at
T <= 1,024), then ONE more piece, a *span* cut to the extent the
diagonal leaves visible and masked only in the square the diagonal
crosses. What lies past the span is never computed. A span's extent is
static and the sub-blocks are unrolled in Python, so at T <= 1,024 a
kernel is straight-line code: four forward pieces of 256 rows by 256 to
1,024 keys. That shape is what the chip asked for (docs/kernels.md has
the three sweeps): a piece's cost is far from proportional to its area,
and a piece under a ``fori_loop`` with a traced trip count or under a
``lax.switch`` costs about twice the same piece in straight-line code,
so uniform small tiles in runtime loops LOSE to the fixed (512, 1024)
tiles this module had before, which put every T <= 1,024 call in one
key tile, skipped nothing and masked everything. The plan also counts
the score elements run / masked / skipped, a function of the shape
alone.

Differentiation: ``jax.custom_vjp`` with Pallas kernels on BOTH sides
(FlashAttention-2 style). The forward additionally emits the per-row
logsumexp; the backward recomputes score tiles from (q, k, lse) and
accumulates dq (grid over query blocks) and dk/dv (grid over key
blocks) — nothing of [T, T] shape is materialized in either direction.
The softmax-grad identity ``ds = p * (dp - rowsum(do*o))`` uses the
delta vector computed once outside the kernel.

``interpret=True`` runs the same kernels on any backend for tests.
"""

from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
_LANES, _SUBLANES = 128, 8

# Preferred (block_q, block_k, cut) by (causal, the axis a kernel sweeps
# inside a grid step); docs/kernels.md has the sweep that chose them.
_PREFERRED = {
    (True, "keys"): (1024, 1024, 256),
    (True, "queries"): (1024, 1024, 512),
    (False, "keys"): (512, 1024, 512),
    (False, "queries"): (512, 1024, 1024),
}


class TilePlan(NamedTuple):
    """How one flash kernel covers its ``t_q x t_k`` score matrix.

    A grid step holds one *block* of the kernel's own axis: ``block_q``
    rows for the forward and dq, which sweep the keys, ``block_k``
    columns for dk/dv, which sweeps the queries. The block is cut into
    sub-blocks of ``cut``, and sub-block ``r`` of block ``i`` computes
    ``pieces[r] = (tiles, span, span_clear)``: ``i * ratio + tiles``
    whole tiles of the swept axis that lie clear of the diagonal
    (``block_k`` keys wide, or ``block_q`` rows high), run by a runtime
    loop with no mask, and one *span* of static extent that reaches the
    diagonal, of which ``span_clear`` (leading columns, or trailing
    rows) need no mask. What lies past the span is never computed.
    ``run`` / ``masked`` / ``skipped`` count the score elements
    computed, passed through the mask and never computed: functions of
    the shape alone."""

    block_q: int
    block_k: int
    blocks: int
    cut: int
    ratio: int
    pieces: tuple[tuple[int, int, int], ...]
    run: int
    masked: int
    skipped: int

    @property
    def run_share(self) -> float:
        return self.run / (self.run + self.skipped)


def _pick_block(t: int, preferred: int) -> int:
    if t % preferred == 0:
        return preferred
    b = min(t, preferred)
    while t % b:
        b -= 1
    if b < min(t, 8):
        # A degenerate divisor (worst case 1 when T is prime) would grid
        # one sublane-padded row per step — orders of magnitude slower
        # than dense. Refuse instead of silently crawling.
        raise ValueError(
            f"sequence length {t} has no block divisor >= 8 near {preferred}; "
            "pad the sequence to a multiple of the block size"
        )
    return b


def flash_tile_plan(
    t_q: int,
    t_k: int,
    causal: bool,
    sweep: str = "keys",
    block_q: int | None = None,
    block_k: int | None = None,
) -> TilePlan:
    """The one place tile sizes come from: all three kernels, and through
    their wrappers the ring's per-hop calls. ``sweep`` names the axis a
    kernel walks inside a grid step: ``"keys"`` (forward, dq) or
    ``"queries"`` (dk/dv). ``block_q`` / ``block_k`` override the choice
    (tests only; no caller passes them); either way a size that does
    not divide its length shrinks to the nearest divisor below it.

    Every extent a kernel slices by must be static, so the swept axis's
    tile divides the block (it shrinks to their common divisor), and a
    causal call whose lengths differ, which no caller makes at length,
    runs as a single block. Head widths 64 and 128 measured the same
    choice, so ``d`` is not an input."""
    pref_q, pref_k, pref_cut = _PREFERRED[causal, sweep]
    bq = _pick_block(t_q, block_q or pref_q)
    bk = _pick_block(t_k, block_k or pref_k)
    keys = sweep == "keys"
    # (own, swept): the axis the grid walks and the one a step sweeps
    t_own, t_swept = (t_q, t_k) if keys else (t_k, t_q)
    own, swept = (bq, bk) if keys else (bk, bq)
    cut = _pick_block(own, pref_cut)
    if causal:
        if t_q != t_k:
            own = t_own
        swept = math.gcd(own, swept)
    ratio = own // swept if causal else 0
    pieces = []
    for r in range(own // cut):
        lo, hi = r * cut, (r + 1) * cut  # the sub-block within its block
        if not causal:
            pieces.append((t_swept // swept if keys else 0, 0, 0))
        elif keys:
            # rows lo..hi-1 (plus the block's offset): all of them see
            # keys <= lo, some of them keys < hi
            every, some = min(lo + 1, t_k), min(hi, t_k)
            tiles = every // swept
            span = some - tiles * swept
            clear = span if every == some else (
                (every - tiles * swept) // _LANES * _LANES
            )
            pieces.append((tiles, span, clear))
        else:
            # keys lo..hi-1: rows >= lo see some, rows >= hi - 1 see all
            first, every = min(lo, t_q), min(hi - 1, t_q)
            tiles = -(-every // swept)  # first clear tile of queries
            span = min(tiles * swept, t_q) - first
            span_masked = min(-(-(every - first) // _SUBLANES) * _SUBLANES, span)
            pieces.append((tiles, span, span - span_masked))
    run = masked = 0
    for i in range(t_own // own):
        for tiles, span, clear in pieces:
            whole = i * ratio + tiles  # loop tiles: before it, or from it on
            if not keys:
                whole = t_swept // swept - whole
            run += cut * (whole * swept + span)
            masked += cut * (span - clear)
    bq, bk = (own, swept) if keys else (swept, own)
    return TilePlan(
        bq, bk, t_own // own, cut, ratio, tuple(pieces),
        run, masked, t_q * t_k - run,
    )


def _keep(shape, row0: int, col0: int):
    """Causal mask of a score piece whose element ``(0, 0)`` sits
    ``row0`` rows and ``col0`` columns from a point on the diagonal."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1) + col0
    return rows >= cols


def _mask_columns_from(s, clear: int, row0: int):
    """Mask columns ``[clear:]`` of span scores ``s`` whose first row is
    ``row0`` keys past the span's first key (keys sweep)."""
    if clear == s.shape[1]:
        return s
    tail = s[:, clear:]
    tail = jnp.where(_keep(tail.shape, row0, clear), tail, _NEG)
    return tail if clear == 0 else jnp.concatenate([s[:, :clear], tail], axis=1)


def _mask_rows_to(s, masked: int):
    """Mask rows ``[:masked]`` of span scores ``s`` whose first element
    is on the diagonal (queries sweep)."""
    if masked == 0:
        return s
    head = s[:masked]
    head = jnp.where(_keep(head.shape, 0, 0), head, _NEG)
    if masked == s.shape[0]:
        return head
    return jnp.concatenate([head, s[masked:]], axis=0)


def _scores(q, k, scale):
    # Feed the MXU its native input dtype (bf16 stays bf16 — casting to
    # f32 first would quarter the matmul rate); accumulate in f32 via
    # preferred_element_type, scale afterwards (distributes).
    return jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [rows of q, rows of k] f32


def _rows(ref, start, size: int, multiple: int):
    if not isinstance(start, int):
        start = pl.multiple_of(start, multiple)
    return ref[0, pl.ds(start, size), :]


def _block_index(plan: TilePlan):
    """This grid step's block along the kernel's own axis: the traced
    program id, or a plain 0 when the plan has one block, so that every
    trip count and slice start below it is static too."""
    return pl.program_id(1) if plan.blocks > 1 else 0


def _sweep_keys(plan: TilePlan, qi, r: int, q, k_ref, v_ref, scale, carry, step):
    """Sub-block ``r``'s pieces in key order, folded into ``carry`` by
    ``step(carry, scores, k, v)``: the tiles clear of the diagonal in a
    runtime loop, then the span that reaches it."""
    block_k = plan.block_k
    tiles0, span, clear = plan.pieces[r]

    def clear_tile(kb, carry):
        k = _rows(k_ref, kb * block_k, block_k, block_k)
        v = _rows(v_ref, kb * block_k, block_k, block_k)
        return step(carry, _scores(q, k, scale), k, v)

    tiles = qi * plan.ratio + tiles0
    if not isinstance(tiles, int) or tiles:  # a loop that cannot run is not traced
        carry = jax.lax.fori_loop(0, tiles, clear_tile, carry)
    if span:
        k = _rows(k_ref, tiles * block_k, span, block_k)
        v = _rows(v_ref, tiles * block_k, span, block_k)
        s = _mask_columns_from(
            _scores(q, k, scale), clear, r * plan.cut - tiles0 * block_k
        )
        carry = step(carry, s, k, v)
    return carry


def _kernel(
    plan: TilePlan, scale: float, q_ref, k_ref, v_ref, o_ref, lse_ref=None
):
    cut, d = plan.cut, q_ref.shape[2]
    qi = _block_index(plan)

    def update(carry, s, _, v):
        m, l, acc = carry
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        correction = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = correction * l + p.sum(axis=-1, keepdims=True)
        acc_new = acc * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    for r in range(len(plan.pieces)):
        rows = pl.ds(r * cut, cut)
        # Key 0 is in the first piece run, tile or span, and every row
        # sees it: each row's running max is finite from then on.
        m, l, acc = _sweep_keys(
            plan, qi, r, q_ref[0, rows, :], k_ref, v_ref, scale,
            (
                jnp.full((cut, 1), _NEG, jnp.float32),
                jnp.zeros((cut, 1), jnp.float32),
                jnp.zeros((cut, d), jnp.float32),
            ),
            update,
        )
        o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
        if lse_ref is not None:
            lse_ref[0, rows, :] = m + jnp.log(l)  # [cut, 1]


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Blockwise attention on [B, T, H, D] without the [T, T] matrix.

    Tile sizes come from ``flash_tile_plan`` (the call's shape); pass
    ``block_q`` / ``block_k`` only to force them in a test."""
    return _forward(q, k, v, causal, block_q, block_k, interpret)


def _to_bh(x, b, t, h, d):  # [B, T, H, D] -> [B*H, T, D]
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from_bh(x, b, t, h, d):
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


# One trace for every layer: a kernel's body is traced anew at each
# ``pallas_call``, and a model makes the same call once a layer. Under an
# inlined ``jit`` the call is traced once for each shape and its equations
# are copied into the caller's trace, scope names and all.
_STATIC = ("causal", "block_q", "block_k", "interpret")
_traced_once = partial(jax.jit, static_argnames=_STATIC, inline=True)


@partial(jax.jit, static_argnames=(*_STATIC, "with_lse"), inline=True)
def _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=False):
    b, t, h, d = q.shape
    plan = flash_tile_plan(t, t, causal, "keys", block_q, block_k)
    block_q = plan.block_q
    scale = d**-0.5

    qb, kb, vb = (_to_bh(x, b, t, h, d) for x in (q, k, v))
    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, qi: (bh, qi, 0))
    kv_spec = pl.BlockSpec((1, t, d), lambda bh, qi: (bh, 0, 0))
    # Row statistics ride as [BH, T, 1]: a trailing singleton keeps the
    # last-two-dims (8, 128)-divisibility rule satisfiable at any block.
    row_spec = pl.BlockSpec((1, block_q, 1), lambda bh, qi: (bh, qi, 0))

    out_shapes = [jax.ShapeDtypeStruct(qb.shape, v.dtype)]
    out_specs = [q_spec]
    if with_lse:
        out_shapes.append(jax.ShapeDtypeStruct((b * h, t, 1), jnp.float32))
        out_specs.append(row_spec)

    res = pl.pallas_call(
        partial(_kernel, plan, scale),
        out_shape=out_shapes,
        grid=(b * h, t // block_q),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        interpret=interpret,
    )(qb, kb, vb)
    out = _from_bh(res[0], b, t, h, d)
    return (out, res[1]) if with_lse else out


def _dq_kernel(
    plan: TilePlan, scale, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref,
):
    cut, d = plan.cut, q_ref.shape[2]
    qi = _block_index(plan)

    for r in range(len(plan.pieces)):
        rows = pl.ds(r * cut, cut)
        do = do_ref[0, rows, :]
        lse, delta = lse_ref[0, rows, :], delta_ref[0, rows, :]  # [cut, 1] f32

        def add(acc, s, k, v, do=do, lse=lse, delta=delta):
            p = jnp.exp(s - lse)  # masked entries underflow to 0
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta)
            return acc + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        acc = _sweep_keys(
            plan, qi, r, q_ref[0, rows, :], k_ref, v_ref, scale,
            jnp.zeros((cut, d), jnp.float32), add,
        )
        dq_ref[0, rows, :] = (acc * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    plan: TilePlan, scale, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref,
):
    block_q, block_k, cut = plan.block_q, plan.block_k, plan.cut
    d = k_ref.shape[2]
    num_qb = q_ref.shape[1] // block_q
    ki = _block_index(plan)

    for r, (tiles, span, clear) in enumerate(plan.pieces):
        cols = pl.ds(r * cut, cut)
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]

        def add(carry, start, size, multiple, masked, k=k, v=v):
            dk_acc, dv_acc = carry
            q, do, lse, delta = (
                _rows(ref, start, size, multiple)
                for ref in (q_ref, do_ref, lse_ref, delta_ref)
            )
            s = _mask_rows_to(_scores(q, k, scale), masked)
            p = jnp.exp(s - lse)
            dv_new = dv_acc + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dp = jax.lax.dot_general(
                do, v, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ds = p * (dp - delta)
            dk_new = dk_acc + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return dk_new, dv_new

        zeros = jnp.zeros((cut, d), jnp.float32)
        carry = (zeros, zeros)
        if span:
            # the span starts on the diagonal, at this sub-block's first key
            carry = add(
                carry, ki * block_k + r * cut, span, cut, span - clear
            )
        first = ki * plan.ratio + tiles
        if not isinstance(first, int) or first < num_qb:
            carry = jax.lax.fori_loop(
                first, num_qb,
                lambda qb, carry, add=add: add(
                    carry, qb * block_q, block_q, block_q, 0
                ),
                carry,
            )
        dk_acc, dv_acc = carry
        dk_ref[0, cols, :] = (dk_acc * scale).astype(dk_ref.dtype)
        dv_ref[0, cols, :] = dv_acc.astype(dv_ref.dtype)


def flash_forward_lse(
    q, k, v, causal=False, block_q=None, block_k=None, interpret=False
):
    """Non-differentiable forward primitive returning ``(out, lse)`` with
    ``lse`` as ``[B*H, T, 1]`` float32 — the building block composite
    attentions (``parallel/ring_attention.py::ring_flash_attention``)
    merge across partial key sets. Differentiate the composite with its
    own custom_vjp, not through this."""
    return _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=True)


def flash_delta(out, g):
    """The softmax-grad row term delta = rowsum(do * o) as [B*H, T, 1]
    float32 — O(T*D), no [T, T] shape, plain XLA."""
    b, t, h, d = out.shape
    ob, gb = _to_bh(out, b, t, h, d), _to_bh(g, b, t, h, d)
    return jnp.sum(
        gb.astype(jnp.float32) * ob.astype(jnp.float32), axis=-1, keepdims=True
    )


@_traced_once
def flash_dq(
    q, k, v, do, lse, delta, causal, block_q=None, block_k=None, interpret=False
):
    """dq for attention of ``q`` [B,Tq,H,D] against keys ``k``/``v``
    [B,Tk,H,D], given the FINAL per-row ``lse``/``delta`` [B*H,Tq,1].
    With an lse computed over a superset of these keys (a merged
    multi-block softmax), this yields exactly this key-set's additive
    contribution to dq."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    plan = flash_tile_plan(tq, tk, causal, "keys", block_q, block_k)
    block_q = plan.block_q
    scale = d**-0.5
    qb, kb, vb, gb = (
        _to_bh(x, b, x.shape[1], h, d) for x in (q, k, v, do)
    )
    q_tile = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    kv_full = pl.BlockSpec((1, tk, d), lambda i, j: (i, 0, 0))
    row_tile = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    dq = pl.pallas_call(
        partial(_dq_kernel, plan, scale),
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        grid=(b * h, tq // block_q),
        in_specs=[q_tile, kv_full, kv_full, q_tile, row_tile, row_tile],
        out_specs=q_tile,
        interpret=interpret,
    )(qb, kb, vb, gb, lse, delta)
    return _from_bh(dq, b, tq, h, d)


@_traced_once
def flash_dkv(
    q, k, v, do, lse, delta, causal, block_q=None, block_k=None, interpret=False
):
    """(dk, dv) for keys ``k``/``v`` [B,Tk,H,D] under queries ``q``
    [B,Tq,H,D] with FINAL ``lse``/``delta`` [B*H,Tq,1] (see flash_dq)."""
    b, tq, h, d = q.shape
    tk = k.shape[1]
    plan = flash_tile_plan(tq, tk, causal, "queries", block_q, block_k)
    block_k = plan.block_k
    scale = d**-0.5
    qb, kb, vb, gb = (
        _to_bh(x, b, x.shape[1], h, d) for x in (q, k, v, do)
    )
    q_full = pl.BlockSpec((1, tq, d), lambda i, j: (i, 0, 0))
    k_tile = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    row_full = pl.BlockSpec((1, tq, 1), lambda i, j: (i, 0, 0))
    dk, dv = pl.pallas_call(
        partial(_dkv_kernel, plan, scale),
        out_shape=[
            jax.ShapeDtypeStruct(kb.shape, k.dtype),
            jax.ShapeDtypeStruct(vb.shape, v.dtype),
        ],
        grid=(b * h, tk // block_k),
        in_specs=[q_full, k_tile, k_tile, q_full, row_full, row_full],
        out_specs=[k_tile, k_tile],
        interpret=interpret,
    )(qb, kb, vb, gb, lse, delta)
    return _from_bh(dk, b, tk, h, d), _from_bh(dv, b, tk, h, d)


def _fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _forward(q, k, v, causal, block_q, block_k, interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, interpret, residuals, g):
    q, k, v, out, lse = residuals
    delta = flash_delta(out, g)
    dq = flash_dq(q, k, v, g, lse, delta, causal, block_q, block_k, interpret)
    dk, dv = flash_dkv(q, k, v, g, lse, delta, causal, block_q, block_k, interpret)
    return dq, dk, dv


flash_attention.defvjp(_fwd, _bwd)
