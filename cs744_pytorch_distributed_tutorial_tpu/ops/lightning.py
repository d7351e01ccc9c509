"""Lightning attention's two serving forms as Pallas TPU kernels: the
one-token recurrent update of a decode step and the chunked form of a
prefill chunk (models/lightning.py has the layer and its equations).

The layer keeps a float32 state ``S [d, d]`` a head and a slot, decayed
by one factor a head: ``S_t = lam S_{t-1} + k_t^T v_t``, ``o_t = q_t
S_t`` (``q`` already carries its ``1/sqrt(d)``). ``lam = exp(-rate)``.

**The decode update** (``lightning_decode``). One grid step a slot and a
block of heads: the block's states are read once, decayed, given the
token's outer product and written once, in place (the state operand is
aliased to the output); the output is read off the new state, ``o = q
S_new``, by a sum over its rows. All float32 on the VPU: a product of a
row and a column is one multiply a lane, and nothing is rounded to
bfloat16. A slot that is not active writes back the state it read.

**The chunk** (``lightning_chunk``). One grid step a head, over a chunk
of ``C`` positions of one slot at ``offset``, its first ``length`` rows
real::

    O     = ((Q K^T) * M) V + (Lq * Q) S_prev
    S_new = exp(-rate length) S_prev + (Lk * K)^T V

with ``M[i, j] = exp(-rate (i - j))`` for ``i >= j`` (else 0), ``Lq[i] =
exp(-rate (i + 1))`` and ``Lk[j] = exp(-rate (length - 1 - j))`` for ``j
< length`` (else 0: padding rows add nothing to the state). Every factor
is an ``exp`` of a non-positive number, never a ratio of two powers,
which would overflow for the fast heads at ``C = 512``. ``Q K^T`` and
its product with ``V`` run on the MXU in the inputs' type with float32
accumulation; the state's two products run in float32 at ``HIGHEST``.
The slot's state row is read and written in place (the slot index is a
scalar-prefetched operand of the index map); a chunk at ``offset`` 0
starts from zero, so a slot given to a new request needs no reset
program.

``interpret=True`` runs the same kernels on any backend for tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST
# Heads a grid step of the decode update: 8 states of 64 KB in, 8 out,
# double-buffered, 2 MB of VMEM.
_DECODE_HEADS = 8
# The chunk's [C, C] float32 scores, mask and products exceed Mosaic's
# default scoped VMEM at C = 512.
_CHUNK_VMEM_LIMIT = 48 * 1024 * 1024


def _interpret(interpret):
    if interpret is None:
        from cs744_pytorch_distributed_tutorial_tpu.ops._backend import (
            default_interpret,
        )

        return default_interpret()
    return interpret


def _decode_kernel(live_ref, q_ref, k_ref, v_ref, rate_ref, s_ref, o_ref, s_out):
    b = pl.program_id(0)

    @pl.when(live_ref[b] != 0)
    def _update():
        for h in range(s_ref.shape[1]):
            lam = jnp.exp(-rate_ref[h][:, :1])  # [1, 1]
            # the token's k and q as columns, v as a row (row 0 of the
            # [8, d] operands holds the token, the rest is zero)
            k_col = k_ref[0, h].T[:, :1]
            q_col = q_ref[0, h].T[:, :1]
            new = lam * s_ref[0, h] + k_col * v_ref[0, h][:1]
            s_out[0, h] = new
            o_ref[0, h] = jnp.broadcast_to(
                jnp.sum(q_col * new, axis=0, keepdims=True), o_ref.shape[2:]
            )

    @pl.when(live_ref[b] == 0)
    def _keep():
        s_out[...] = s_ref[...]
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def lightning_decode(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    state: jax.Array,
    rate: jax.Array,
    live: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One decode step of every slot: ``q``, ``k``, ``v [B, H, d]`` (``q``
    scaled), ``state [B, H, d, d]`` float32, ``rate [H]`` (``lam =
    exp(-rate)``), ``live [B]`` (0: the slot's state is left as it is and
    its output is 0) -> (``o [B, H, d]`` float32, the new state). The
    state is updated in place (module docstring)."""
    b, h, d = q.shape
    if state.shape != (b, h, d, d) or state.dtype != jnp.float32:
        raise ValueError(
            f"the state is [B, H, d, d] float32 = {(b, h, d, d)}, got "
            f"{state.shape} {state.dtype}"
        )
    hb = _DECODE_HEADS if h % _DECODE_HEADS == 0 else h

    def rows8(x):  # [B, H, d] -> [B, H, 8, d], the token in row 0
        return jnp.pad(x.astype(jnp.float32)[:, :, None], ((0, 0),) * 2 + ((0, 7), (0, 0)))

    heads = pl.BlockSpec((1, hb, 8, d), lambda bi, j, *_: (bi, j, 0, 0))
    states = pl.BlockSpec((1, hb, d, d), lambda bi, j, *_: (bi, j, 0, 0))
    o, new_state = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // hb),
            in_specs=[
                heads, heads, heads,
                pl.BlockSpec((hb, 1, d), lambda bi, j, *_: (j, 0, 0)),
                states,
            ],
            out_specs=[heads, states],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 8, d), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        # operand 5 (after the prefetched ``live``) is the state
        input_output_aliases={5: 1},
        interpret=_interpret(interpret),
    )(
        live.astype(jnp.int32), rows8(q), rows8(k), rows8(v),
        jnp.broadcast_to(rate.astype(jnp.float32)[:, None, None], (h, 1, d)),
        state,
    )
    return o[:, :, 0], new_state


def _chunk_kernel(
    scalars_ref, q_ref, k_ref, v_ref, rate_ref, s_ref, o_ref, s_out
):
    length, fresh = scalars_ref[1], scalars_ref[2]
    c = q_ref.shape[0]
    r = rate_ref[0][:, :1]  # [1, 1]
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lag = (i - j).astype(jnp.float32)
    mask = jnp.where(i >= j, jnp.exp(-r * jnp.maximum(lag, 0.0)), 0.0)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    intra = jax.lax.dot_general(
        (scores * mask).astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    prev = jnp.where(fresh != 0, 0.0, s_ref[0, 0])
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    lam_q = jnp.exp(-r * (row + 1).astype(jnp.float32))
    inter = lam_q * jax.lax.dot_general(
        q.astype(jnp.float32), prev, (((1,), (0,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32,
    )
    o_ref[...] = intra + inter
    lam_k = jnp.where(
        row < length,
        jnp.exp(-r * jnp.maximum(length - 1 - row, 0).astype(jnp.float32)),
        0.0,
    )
    kt = (lam_k * k.astype(jnp.float32)).T  # [d, C]
    s_out[0, 0] = jnp.exp(-r * length.astype(jnp.float32)) * prev + (
        jax.lax.dot_general(
            kt, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32,
        )
    )


def lightning_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    state: jax.Array,
    rate: jax.Array,
    slot: jax.Array,
    offset: jax.Array,
    length: jax.Array,
    *,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """A prefill chunk of one slot: ``q``, ``k``, ``v [C, H, d]`` (``q``
    scaled) at positions ``offset ..``, the first ``length`` real;
    ``state [slots, H, d, d]`` float32, of which row ``slot`` is this
    slot's (read as zero where ``offset`` is 0) -> (``o [C, H, d]``
    float32, the state with row ``slot`` advanced past the chunk's real
    rows), in place (module docstring)."""
    c, h, d = q.shape
    if state.ndim != 4 or state.shape[1:] != (h, d, d) or state.dtype != jnp.float32:
        raise ValueError(
            f"the state is [slots, H, d, d] float32 with H, d = {h}, {d}; "
            f"got {state.shape} {state.dtype}"
        )
    scalars = jnp.stack([
        jnp.asarray(slot, jnp.int32), jnp.asarray(length, jnp.int32),
        (jnp.asarray(offset) == 0).astype(jnp.int32),
    ])
    heads = pl.BlockSpec((c, d), lambda hi, *_: (0, hi))
    row = pl.BlockSpec((1, 1, d, d), lambda hi, s: (s[0], hi, 0, 0))
    o, new_state = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h,),
            in_specs=[
                heads, heads, heads,
                pl.BlockSpec((1, 1, d), lambda hi, *_: (hi, 0, 0)),
                row,
            ],
            out_specs=[pl.BlockSpec((c, d), lambda hi, *_: (0, hi)), row],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((c, h * d), jnp.float32),
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
        ],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        interpret=_interpret(interpret),
    )(
        scalars, q.reshape(c, h * d), k.reshape(c, h * d), v.reshape(c, h * d),
        jnp.broadcast_to(rate.astype(jnp.float32)[:, None, None], (h, 1, d)),
        state,
    )
    return o.reshape(c, h, d), new_state


# ---- the same forms in plain XLA: the "gather" reference path --------------


def decode_reference(q, k, v, state, rate, live):
    """``lightning_decode`` in XLA (the engine's "gather" path)."""
    lam = jnp.exp(-rate.astype(jnp.float32))[None, :, None, None]
    kf, vf, qf = (x.astype(jnp.float32) for x in (k, v, q))
    new = lam * state + kf[..., :, None] * vf[..., None, :]
    new = jnp.where(live[:, None, None, None] != 0, new, state)
    o = jnp.einsum("bhi,bhij->bhj", qf, new, precision=HIGHEST)
    return jnp.where(live[:, None, None] != 0, o, 0.0), new


def chunk_reference(q, k, v, prev, rate, length):
    """The chunked form over one slot's row ``prev [H, d, d]`` in XLA
    (the "gather" path, and ``forward``'s blocks): (``o [C, H, d]``, the
    state after the first ``length`` rows)."""
    c = q.shape[0]
    r = rate.astype(jnp.float32)[:, None, None]  # [H, 1, 1]
    i = jnp.arange(c)[:, None]
    j = jnp.arange(c)[None, :]
    mask = jnp.where(i >= j, jnp.exp(-r * jnp.maximum(i - j, 0)), 0.0)
    scores = jnp.einsum(
        "ihd,jhd->hij", q, k, preferred_element_type=jnp.float32
    )
    intra = jnp.einsum(
        "hij,jhd->ihd", (scores * mask).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    row = jnp.arange(c)
    lam_q = jnp.exp(-r[:, :, 0] * (row + 1))  # [H, C]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    inter = jnp.einsum(
        "ihd,hde->ihe", qf, prev, precision=HIGHEST
    ) * lam_q.T[:, :, None]
    lam_k = jnp.where(
        row < length, jnp.exp(-r[:, :, 0] * jnp.maximum(length - 1 - row, 0)), 0.0
    )  # [H, C]
    new = jnp.exp(-r * length) * prev + jnp.einsum(
        "jhd,jhe->hde", kf * lam_k.T[:, :, None], vf, precision=HIGHEST
    )
    return intra + inter, new


def full_forward(q, k, v, rate, block: int = 256):
    """Every position of one sequence, ``q``, ``k``, ``v [T, H, d]``, by
    the chunked form a block at a time with the state carried between
    blocks: the training shapes' form, and what the one-token and chunk
    forms are tested against."""
    t, h, d = q.shape
    pad = -(-t // block) * block - t
    qp, kp, vp = (jnp.pad(x, ((0, pad), (0, 0), (0, 0))) for x in (q, k, v))

    def step(prev, xs):
        qb, kb, vb = xs
        o, new = chunk_reference(qb, kb, vb, prev, rate, block)
        return new, o

    blocks = lambda x: x.reshape(-1, block, h, d)  # noqa: E731
    _, o = jax.lax.scan(
        step, jnp.zeros((h, d, d), jnp.float32), (blocks(qp), blocks(kp), blocks(vp))
    )
    return o.reshape(-1, h, d)[:t]

