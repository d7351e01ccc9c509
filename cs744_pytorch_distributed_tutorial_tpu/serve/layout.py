"""How the paged KV pools lie on the device, read off the compiled programs.

The pools are ``[num_pages, page_size, Hkv*D]`` because of what this
module checks. The TPU compiler picks an array's layout from its shape:
for the 4-D ``[num_pages, page_size, Hkv, D]`` it made ``num_pages`` the
minor-most dimension (row-major would pad ``(12, 64)`` to ``(16, 128)``),
and every program that scattered rows into a pool or ran the paged
kernel over it then converted the whole pool to row-major on the way in
and back on the way out: two pool-sized ``copy`` ops a pool a program,
three quarters of a serving chip's time. Nothing in the Python says so;
the compiled module does.

``compile_programs`` compiles the engine's decode step and its prefill
(one bucket, or the one chunk program of a chunked engine) from shapes
alone, so it runs on the chip and, given the
sharding of a described device, through the compile-only topology on a
CPU box (no chip, a few seconds)::

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    programs = compile_programs(engine, 512, SingleDeviceSharding(topo.devices[0]))
    print(audit(programs["decode"], engine))

(the engine's model built with ``flash_interpret=False`` and the config
with ``paged_attention_impl="kernel"``, or a CPU process compiles the
gather path). ``tests/test_serve_layout.py`` and
``tests_chip/test_kernels.py`` hold the engine to ``audit``'s three
numbers.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

_COPY = re.compile(r"= \w+\[([\d,]+)\]\{([\d,]*)[^}]*\} copy\(")
# a scatter's result dims and its updates operand
_SCATTER = re.compile(
    r"= \w+\[([\d,]+)\]\{[^}]*\} scatter\(%[\w.-]+, %[\w.-]+, "
    r"%([\w.-]+)\)"
)


@dataclass
class PoolLayout:
    """What one compiled program does to the data pools."""

    # (dims, minor_to_major) of every ``copy`` whose result has as many
    # elements as one data pool, or as a scanned stack of them
    pool_copies: list[tuple[tuple[int, ...], tuple[int, ...]]]
    temp_bytes: int  # memory_analysis().temp_size_in_bytes
    # the smallest data pool (one layer's, where they are stacked): K and
    # V are alike, a sparse-attention model's index_key_pages is smaller
    pool_bytes: int
    # major_to_minor of each data pool as the program takes it
    entry_layouts: list[tuple[int, ...]]
    # (dims, updates' dims) of every ``scatter`` into a data pool, or a
    # scanned stack of them: the updates' leading dims count the indices,
    # the rest are the window each index writes (a row or a page)
    pool_writes: list[tuple[tuple[int, ...], tuple[int, ...]]]

    @property
    def row_major(self) -> bool:
        return all(
            lay == tuple(range(len(lay))) for lay in self.entry_layouts
        )


def _dims(text: str) -> tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


def _data_pools(tree: Any) -> list[Any]:
    return [
        leaf
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree)
        if "scale" not in path[-1].key
    ]


def compile_programs(
    engine: Any, bucket: int, sharding: Any = None
) -> dict[str, Any]:
    """``{"decode": ..., "prefill": ...}``: the engine's decode step and
    its prefill+commit for ``bucket`` (with ``ServeConfig.prefill_chunk``
    set: its one chunk program, and ``bucket`` is not read), compiled
    from shapes (no array is made or donated). ``sharding`` places every
    argument; None is the default device."""
    cfg = engine.cfg

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def shapes(tree):
        return jax.tree.map(lambda x: s(x.shape, x.dtype), tree)

    params, pages = shapes(engine.params), shapes(engine._pages)
    key = shapes(engine._sample_root)
    i32 = jnp.int32
    # every program takes one packed int32 vector and the key
    decode = engine._decode_step.lower(
        params, pages, s((engine._decode_arg_len(),), i32), key
    ).compile()
    if cfg.prefill_chunk:
        prefill = engine._chunk_fn().lower(
            params, pages,
            s((engine._program_arg_len(
                cfg.prefill_chunk, engine._chunk_scalars()
            ),), i32), key,
        ).compile()
    else:
        prefill = engine._prefill_fn(bucket).lower(
            params, pages, s((engine._program_arg_len(bucket, 3),), i32),
            key,
        ).compile()
    return {"decode": decode, "prefill": prefill}


def audit(compiled: Any, engine: Any) -> PoolLayout:
    """Read one compiled program of ``compile_programs`` (both take the
    pools as their second argument)."""
    pools = _data_pools(engine._pages)

    def layer_shape(pool):
        return pool.shape[1:] if engine._scanned else pool.shape

    sizes = {math.prod(layer_shape(p)) for p in pools} | {
        math.prod(p.shape) for p in pools
    }
    text = compiled.as_text()
    copies = []
    for dims, minor_to_major in _COPY.findall(text):
        shape = _dims(dims)
        if math.prod(shape) in sizes:
            copies.append((shape, _dims(minor_to_major)))
    writes = []
    for dims, updates in _SCATTER.findall(text):
        shape = _dims(dims)
        if math.prod(shape) in sizes:
            found = re.search(
                rf"%{re.escape(updates)} = \w+\[([\d,]*)\]", text
            )
            writes.append((shape, _dims(found.group(1))))
    formats = _data_pools(compiled.input_formats[0][1])
    return PoolLayout(
        pool_copies=copies,
        temp_bytes=int(compiled.memory_analysis().temp_size_in_bytes),
        pool_bytes=min(
            math.prod(layer_shape(p)) * p.dtype.itemsize for p in pools
        ),
        entry_layouts=[tuple(f.layout.major_to_minor) for f in formats],
        pool_writes=writes,
    )
