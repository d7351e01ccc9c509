"""jaxpr-walking machinery shared by the trace audits.

Everything here operates on a ``ClosedJaxpr`` from ``jax.make_jaxpr`` and
knows three things the audits need:

- recursive equation iteration with SCAN MULTIPLIERS: a ``fori_loop``
  lowers to ``scan(length=k)``, so an eqn inside the body executes ``k``
  times per step and its collective/flop cost must be counted ``k`` times;
- collective classification and per-device bytes-on-wire: jax 0.4.x under
  the legacy ``shard_map`` shim rewrites ``psum`` to ``psum2`` when the
  replication checker is on, and ``lax.psum_scatter`` binds a primitive
  named ``reduce_scatter`` — both are folded back to their canonical
  class here;
- bf16 taint propagation for the dtype-upcast audit.

Bytes-on-wire per device for one collective over a group of ``n``:

=================  ==========================================
``psum``           ``2(n-1)/n *`` payload (reduce-scatter + all-gather
                   decomposition, the ring lower bound)
``all_gather``     ``(n-1) *`` payload (the payload IS the local shard)
``reduce_scatter`` ``(n-1)/n *`` payload (payload is the full input)
``all_to_all``     ``(n-1)/n *`` payload (keep 1/n, send the rest)
``ppermute``       ``len(perm)/n *`` payload — each listed edge has one
                   sender, so the per-device average send is the edge
                   count over the group size (a full ring is factor 1,
                   a single star edge is 1/n)
=================  ==========================================

These are the same formulas ``parallel/buckets.sync_bytes_per_step``
uses analytically — TA003's cross-check closes the loop between the two.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Iterator

import numpy as np

#: primitive name -> canonical collective class
COLLECTIVE_CLASS = {
    "psum": "psum",
    "psum2": "psum",  # legacy shard_map's check_rep rewrite of psum
    # jax 0.9.0, check_vma=True: lax.psum/pmean of a varying value, and
    # the transpose of pvary (every replicated parameter's gradient)
    "psum_invariant": "psum",
    "pmax": "pmax",
    "pmin": "pmin",
    "all_gather": "all_gather",
    "all_gather_invariant": "all_gather",  # jax 0.9.0: varying -> invariant
    "reduce_scatter": "reduce_scatter",  # what lax.psum_scatter binds
    "psum_scatter": "reduce_scatter",
    "ppermute": "ppermute",
    "all_to_all": "all_to_all",
}

MATMUL_PRIMS = {"dot_general", "conv_general_dilated"}

#: sub-jaxpr-carrying call primitives (for expensive-op containment)
_CALL_PRIMS = {"pjit", "scan", "while", "cond", "custom_jvp_call",
               "custom_vjp_call", "custom_vjp_call_jaxpr", "closed_call",
               "core_call", "xla_call", "remat", "checkpoint", "shard_map"}


def sub_jaxprs(value: Any) -> Iterator[Any]:
    """Yield every (open) jaxpr hiding inside one eqn-param value."""
    vals = value if isinstance(value, (list, tuple)) else [value]
    for item in vals:
        if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
            yield item.jaxpr  # ClosedJaxpr
        elif hasattr(item, "eqns"):
            yield item  # Jaxpr


def closed_sub_jaxprs(value: Any) -> Iterator[Any]:
    """Yield ClosedJaxpr values (which carry consts) inside eqn params."""
    vals = value if isinstance(value, (list, tuple)) else [value]
    for item in vals:
        if hasattr(item, "jaxpr") and hasattr(item, "consts"):
            yield item


def iter_eqns(jaxpr, mult: int = 1) -> Iterator[tuple[Any, int]]:
    """Depth-first ``(eqn, multiplier)`` pairs over a jaxpr and all its
    sub-jaxprs. ``multiplier`` is the product of enclosing scan lengths —
    the number of times the eqn executes per call of the outer jaxpr."""
    for eqn in jaxpr.eqns:
        yield eqn, mult
        inner = mult
        if eqn.primitive.name == "scan":
            inner = mult * int(eqn.params.get("length", 1))
        for value in eqn.params.values():
            for sub in sub_jaxprs(value):
                yield from iter_eqns(sub, inner)


def aval_bytes(aval) -> int:
    size = int(math.prod(getattr(aval, "shape", ())))
    try:
        itemsize = np.dtype(aval.dtype).itemsize
    except TypeError:  # extended dtypes (PRNG keys)
        itemsize = getattr(aval.dtype, "itemsize", 4)
    return size * itemsize


def aval_elems(aval) -> int:
    return int(math.prod(getattr(aval, "shape", ())))


def collective_axis_names(eqn) -> tuple[str, ...]:
    """The mesh axes a collective eqn reduces/permutes over. psum-family
    eqns carry ``axes``; the rest ``axis_name`` — sometimes a bare string
    (``all_to_all``), sometimes a tuple."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        axes = (axes,)
    return tuple(str(a) for a in axes)


@dataclasses.dataclass(frozen=True)
class CollectiveEqn:
    """One collective equation instance found in a trace."""

    cls: str  # canonical class ("psum", "all_gather", ...)
    primitive: str
    mult: int  # enclosing scan-length product
    axes: tuple[str, ...]
    group_size: int
    payload_bytes: int  # sum of input aval bytes, one execution
    payload_elems: int
    perm_len: int | None  # ppermute only
    wire_bytes: float  # mult * per-device send bytes

    @property
    def trivial(self) -> bool:
        """Scalar-payload or group-of-one collectives: loss pmeans,
        telemetry-norm psums, size-1-axis reductions. Excluded from
        schedule counts; their wire bytes are ~0 anyway."""
        return self.payload_elems <= 1 or self.group_size <= 1


def _wire_factor(cls: str, group: int, perm_len: int | None) -> float:
    if group <= 1:
        return 0.0
    if cls in ("psum", "pmax", "pmin"):
        return 2.0 * (group - 1) / group
    if cls == "all_gather":
        return float(group - 1)
    if cls in ("reduce_scatter", "all_to_all"):
        return (group - 1) / group
    if cls == "ppermute":
        return (perm_len if perm_len is not None else group) / group
    return 0.0


def collect_collectives(
    closed_jaxpr, axis_sizes: dict[str, int]
) -> list[CollectiveEqn]:
    """Every collective eqn in the trace, scan-multiplied, with its
    per-device bytes-on-wire computed from eqn shapes and ``axis_sizes``
    (the mesh's ``{axis_name: size}``)."""
    out: list[CollectiveEqn] = []
    for eqn, mult in iter_eqns(closed_jaxpr.jaxpr):
        cls = COLLECTIVE_CLASS.get(eqn.primitive.name)
        if cls is None:
            continue
        axes = collective_axis_names(eqn)
        group = 1
        for a in axes:
            group *= int(axis_sizes.get(a, 1))
        payload = sum(aval_bytes(v.aval) for v in eqn.invars)
        elems = sum(aval_elems(v.aval) for v in eqn.invars)
        perm = eqn.params.get("perm")
        perm_len = len(perm) if perm is not None else None
        factor = _wire_factor(cls, group, perm_len)
        out.append(
            CollectiveEqn(
                cls=cls,
                primitive=eqn.primitive.name,
                mult=mult,
                axes=axes,
                group_size=group,
                payload_bytes=payload,
                payload_elems=elems,
                perm_len=perm_len,
                wire_bytes=mult * factor * payload,
            )
        )
    return out


def cond_branch_schedules(
    closed_jaxpr, axis_sizes: dict[str, int]
) -> list[tuple[Any, int, list[dict[str, int]]]]:
    """``(eqn, mult, per-branch collective counts)`` for every ``cond``
    equation (both ``lax.cond`` and ``lax.switch`` lower to it) anywhere
    in the trace.

    Unlike :func:`schedule_counts`, scalar-payload collectives are NOT
    filtered here: a size-1 ``psum`` present in only one branch still
    hangs the ranks that took the other branch — only group-of-one
    (single-device) collectives are ignored. Counts are scan-multiplied
    *within* the branch; the returned ``mult`` is the enclosing
    multiplier of the ``cond`` itself."""
    out: list[tuple[Any, int, list[dict[str, int]]]] = []
    for eqn, mult in iter_eqns(closed_jaxpr.jaxpr):
        if eqn.primitive.name != "cond":
            continue
        branches = eqn.params.get("branches") or ()
        schedules: list[dict[str, int]] = []
        for br in branches:
            counts: dict[str, int] = {}
            for c in collect_collectives(br, axis_sizes):
                if c.group_size <= 1:
                    continue
                counts[c.cls] = counts.get(c.cls, 0) + c.mult
            schedules.append(counts)
        if schedules:
            out.append((eqn, mult, schedules))
    return out


def schedule_counts(collectives: list[CollectiveEqn]) -> dict[str, int]:
    """Gradient-class collective counts by canonical class: non-trivial
    (payload beyond a scalar, group beyond one device) eqns, scan-
    multiplied — the shape TA003 asserts against a strategy contract."""
    counts: dict[str, int] = {}
    for c in collectives:
        if c.trivial:
            continue
        counts[c.cls] = counts.get(c.cls, 0) + c.mult
    return counts


def total_wire_bytes(collectives: list[CollectiveEqn]) -> float:
    return sum(c.wire_bytes for c in collectives)


# ------------------------------------------------------------ source frames
def eqn_frames(eqn, limit: int = 6) -> list[tuple[str, str, int]]:
    """User-code ``(file, function, line)`` frames of an eqn's trace
    point, outermost-first, with site-packages internals dropped."""
    tb = getattr(getattr(eqn, "source_info", None), "traceback", None)
    out: list[tuple[str, str, int]] = []
    if tb is None:
        return out
    for f in tb.frames:
        fname = f.file_name
        if "site-packages" in fname or fname.startswith("<"):
            continue
        out.append((fname, f.function_name, int(f.line_num)))
        if len(out) >= limit:
            break
    return out


# ------------------------------------------------------------- bf16 taint
def tainted_f32_matmuls(closed_jaxpr) -> list[tuple[Any, int]]:
    """f32 dot/conv eqns reachable from bf16 values — the silent-upcast
    shape TA001 hunts: a mixed-precision model where one block forgot its
    cast and a matmul runs at 4 bytes/element.

    Taint is seeded per (sub-)jaxpr at every bf16-dtyped var (params cast
    to bf16, activations, cotangents) and propagates forward through
    every eqn; an f32-OUTPUT dot/conv with a tainted input is flagged.
    A pure-f32 trace has no bf16 vars, so no taint and no findings — the
    audit self-gates on mixed precision actually being in play."""
    flagged: list[tuple[Any, int]] = []

    def visit(jaxpr, mult: int) -> None:
        tainted: set[Any] = set()

        def is_bf16(v) -> bool:
            return str(getattr(v.aval, "dtype", "")) == "bfloat16"

        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            if is_bf16(v):
                tainted.add(v)
        for eqn in jaxpr.eqns:
            # Literals (hasattr ``val``) are unhashable and never tainted.
            in_taint = any(
                is_bf16(v) or (not hasattr(v, "val") and v in tainted)
                for v in eqn.invars
                if hasattr(v, "aval")
            )
            if in_taint:
                for o in eqn.outvars:
                    tainted.add(o)
            if (
                eqn.primitive.name in MATMUL_PRIMS
                and in_taint
                and str(eqn.outvars[0].aval.dtype) == "float32"
            ):
                flagged.append((eqn, mult))
            inner = mult
            if eqn.primitive.name == "scan":
                inner = mult * int(eqn.params.get("length", 1))
            for value in eqn.params.values():
                for sub in sub_jaxprs(value):
                    visit(sub, inner)

    visit(closed_jaxpr.jaxpr, 1)
    return flagged


# ------------------------------------------------------------ trace consts
def large_trace_constants(
    closed_jaxpr, min_bytes: int = 2**20
) -> list[tuple[tuple[int, ...], str, int]]:
    """``(shape, dtype, nbytes)`` of constants baked into the trace —
    arrays captured by closure instead of passed as arguments. Each one
    is duplicated into every compiled executable and re-hashed on every
    trace; above ``min_bytes`` that is an accident, not a literal."""
    found: list[tuple[tuple[int, ...], str, int]] = []

    def add_consts(consts) -> None:
        for c in consts:
            nbytes = getattr(c, "nbytes", 0)
            if nbytes and nbytes >= min_bytes:
                found.append(
                    (
                        tuple(getattr(c, "shape", ())),
                        str(getattr(c, "dtype", "?")),
                        int(nbytes),
                    )
                )

    add_consts(getattr(closed_jaxpr, "consts", ()))

    def visit(jaxpr) -> None:
        for eqn in jaxpr.eqns:
            for value in eqn.params.values():
                for sub_closed in closed_sub_jaxprs(value):
                    add_consts(sub_closed.consts)
                for sub in sub_jaxprs(value):
                    visit(sub)

    visit(closed_jaxpr.jaxpr)
    return found


# ------------------------------------------------------------- dead eqns
def _contains_expensive(jaxpr) -> bool:
    for eqn, _ in iter_eqns(jaxpr):
        if (
            eqn.primitive.name in MATMUL_PRIMS
            or eqn.primitive.name in COLLECTIVE_CLASS
        ):
            return True
    return False


def dead_expensive_eqns(
    closed_jaxpr, min_bytes: int = 2**20
) -> list[tuple[Any, int]]:
    """Eqns whose outputs reach no jaxpr output — computed, then thrown
    away. Tracing leaves a handful of dead SCALAR ops behind (AD
    residual bookkeeping, shard_map rewrite noise) that XLA deletes for
    free, so only expensive dead work is flagged: matmuls/convs,
    collectives, calls containing them, or any dead eqn materializing
    ``min_bytes`` or more. Effectful eqns (callbacks, prints) are live
    by definition."""
    flagged: list[tuple[Any, int]] = []

    def visit(jaxpr, mult: int) -> None:
        live: set[Any] = set()
        for v in jaxpr.outvars:
            if hasattr(v, "count"):
                live.add(v)
        for eqn in reversed(jaxpr.eqns):
            is_live = bool(getattr(eqn, "effects", None)) or any(
                o in live for o in eqn.outvars
            )
            if is_live:
                for v in eqn.invars:
                    if hasattr(v, "count"):
                        live.add(v)
            else:
                name = eqn.primitive.name
                out_bytes = sum(aval_bytes(o.aval) for o in eqn.outvars)
                expensive = (
                    name in MATMUL_PRIMS
                    or name in COLLECTIVE_CLASS
                    or out_bytes >= min_bytes
                    or (
                        name in _CALL_PRIMS
                        and any(
                            _contains_expensive(sub)
                            for value in eqn.params.values()
                            for sub in sub_jaxprs(value)
                        )
                    )
                )
                if expensive:
                    flagged.append((eqn, mult))
        for eqn in jaxpr.eqns:
            inner = mult
            if eqn.primitive.name == "scan":
                inner = mult * int(eqn.params.get("length", 1))
            for value in eqn.params.values():
                for sub in sub_jaxprs(value):
                    visit(sub, inner)

    visit(closed_jaxpr.jaxpr, 1)
    return flagged
