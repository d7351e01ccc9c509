"""Fused SGD(momentum, weight-decay) update as a Pallas TPU kernel.

The reference's optimizer step is torch's C++ SGD loop over 34 parameter
tensors (``optimizer.step()`` at ``master/part1/part1.py:38`` with
hyperparameters at ``:98-99``). The XLA path here (optax chain in
``train/state.py``) already fuses well; this kernel is the framework's
native-op layer doing the update in ONE pass per parameter over HBM —
read p, m, g once, write p, m once, with the decayed-gradient/momentum/
step arithmetic applied in VMEM — instead of materializing the chain's
intermediate trees. Exact torch-SGD semantics:

    g_eff = g + wd * p
    m'    = mu * m + g_eff
    p'    = p - lr * m'

Arrays of any shape/size are viewed as (rows, 128) lanes. Leaves whose
size is a multiple of 8*128 hit the single-pass path directly; ragged
leaves are padded to the next tile, which costs one extra copy per
operand across the custom-call boundary (XLA cannot fuse through it) —
so the single-pass claim holds exactly for aligned leaves and
approximately for small ragged ones. ``interpret=True`` runs the same
kernel on any backend for tests.
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8
_BLOCK_ROWS = 512  # rows of 128 lanes per grid step (256 KiB fp32 per operand)


def _kernel(lr: float, mu: float, wd: float, p_ref, m_ref, g_ref, np_ref, nm_ref):
    p = p_ref[:]
    g = g_ref[:] + wd * p
    m = mu * m_ref[:] + g
    nm_ref[:] = m
    np_ref[:] = p - lr * m


def _update_leaf(
    p: jax.Array,
    m: jax.Array,
    g: jax.Array,
    *,
    lr: float,
    mu: float,
    wd: float,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    orig_shape, orig_size, orig_dtype = p.shape, p.size, p.dtype
    tile = SUBLANES * LANES
    pad = (-orig_size) % tile
    rows = (orig_size + pad) // LANES

    def prep(x):
        return jnp.pad(x.astype(jnp.float32).reshape(-1), (0, pad)).reshape(
            rows, LANES
        )

    p2, m2, g2 = prep(p), prep(m), prep(g)
    block_rows = min(_BLOCK_ROWS, rows)
    grid = (pl.cdiv(rows, block_rows),)
    block = pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))

    new_p, new_m = pl.pallas_call(
        partial(_kernel, lr, mu, wd),
        # vma=frozenset(): outputs carry no device-varying axes, so the
        # enclosing shard_map's replication checker can keep running.
        out_shape=(
            jax.ShapeDtypeStruct(p2.shape, jnp.float32, vma=frozenset()),
            jax.ShapeDtypeStruct(m2.shape, jnp.float32, vma=frozenset()),
        ),
        grid=grid,
        in_specs=[block, block, block],
        out_specs=(block, block),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(p2, m2, g2)

    def unprep(x):
        return x.reshape(-1)[:orig_size].reshape(orig_shape).astype(orig_dtype)

    return unprep(new_p), unprep(new_m)


class FusedSGD(NamedTuple):
    """Optimizer with torch-SGD semantics backed by the fused kernel.

    Replaces the optax chain when ``TrainConfig.fused_optimizer`` is set.
    State is the momentum pytree alone (same structure as params).
    """

    learning_rate: float
    momentum: float
    weight_decay: float
    interpret: bool = False

    def init(self, params: Any) -> Any:
        return jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)

    def apply(self, params: Any, momentum: Any, grads: Any) -> tuple[Any, Any]:
        flat_p, treedef = jax.tree.flatten(params)
        flat_m = treedef.flatten_up_to(momentum)
        flat_g = treedef.flatten_up_to(grads)
        out = [
            _update_leaf(
                p,
                m,
                g,
                lr=self.learning_rate,
                mu=self.momentum,
                wd=self.weight_decay,
                interpret=self.interpret,
            )
            for p, m, g in zip(flat_p, flat_m, flat_g)
        ]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        return new_p, new_m
