"""The comparison that decides ``correct``: the program's numbers
against the plain reference's, each under a limit of its own.

Limits live in ``perfbench/limits/<workload>.json`` and were set from
readings on the chip (PERF.md gives the readings for each).
"""

from __future__ import annotations

import json
import os
import statistics
from pathlib import Path
from typing import Any, Mapping

LIMITS_DIR = Path(__file__).resolve().parent / "limits"


def probing() -> bool:
    """``PERFBENCH_PROBE=1`` makes a run also read the control and the
    planted faults against the reference (after the window, beside the
    program's own numbers, under no limit): how the limits were set."""
    return bool(os.environ.get("PERFBENCH_PROBE"))


def probe_variants(n_dev: int):
    """(name, reference keywords) of the control and of each fault a
    training cell can have, when probing; nothing otherwise."""
    if not probing():
        return []
    out = [
        ("control_fp8", {"quant": "fp8"}),
        ("control_int8", {"quant": "int8"}),
        ("fault_half_batch", {"half_batch": True}),
    ]
    if n_dev > 1:
        out.append(("fault_no_exchange", {"exchange": False}))
    # PERFBENCH_PROBE=1 reads them all; a comma list of names, those.
    wanted = os.environ["PERFBENCH_PROBE"].split(",")
    return out if wanted == ["1"] else [v for v in out if v[0] in wanted]


def dump_probe(run, readings: Mapping[str, Any]) -> None:
    """With ``PERFBENCH_PROBE_DUMP=<dir>``, a probing run leaves every
    reading it compared (losses, per-leaf norms of the program, the
    reference, the control and the faults) there as JSON, for choosing
    the numbers and their limits off the chip."""
    where = os.environ.get("PERFBENCH_PROBE_DUMP")
    if not (probing() and where):
        return
    os.makedirs(where, exist_ok=True)
    with open(os.path.join(where, f"{run.cell['name']}.{run.seed}.json"), "w", encoding="utf-8") as f:
        json.dump(readings, f)


def load_limits(workload: str) -> dict[str, float]:
    with open(LIMITS_DIR / f"{workload}.json", encoding="utf-8") as f:
        return {k: v for k, v in json.load(f).items() if not k.startswith("_")}


def leaf_norms(tree_flat: Mapping[str, Any]) -> dict[str, float]:
    """Euclidean norm of each leaf of a flat ``{path: array}`` dict, in
    float32, in one jitted call."""
    import jax
    import jax.numpy as jnp

    fn = jax.jit(lambda d: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in d.items()})
    return {k: float(v) for k, v in jax.device_get(fn(tree_flat)).items()}


def loss_gap(program: list[float], reference: list[float]) -> float:
    """Widest relative gap between the steps' losses."""
    return max(abs(p - r) / abs(r) for p, r in zip(program, reference, strict=True))


def moving_leaves(ref_grad_norms: Mapping[str, float]) -> set[str]:
    """Leaves whose first gradient is more than a thousandth of the median
    leaf's. The others move by round-off alone under Adam, and are left
    out of the change (by this rule on the reference, not by name)."""
    med = statistics.median(ref_grad_norms.values())
    return {k for k, v in ref_grad_norms.items() if v > 1e-3 * med}


def leaf_gaps(program: Mapping[str, float], reference: Mapping[str, float], keep=None) -> dict[str, float]:
    """Each leaf's gap between the program's norm and the reference's (not
    the norm of a difference), against the reference's norm of that leaf
    or of the median leaf, whichever is larger."""
    keys = [k for k in reference if keep is None or k in keep]
    med = statistics.median(reference[k] for k in keys)
    return {k: abs(program[k] - reference[k]) / max(reference[k], med) for k in keys}


def norm_gap(program: Mapping[str, float], reference: Mapping[str, float], keep=None):
    """The worst leaf's gap, and which leaf it is."""
    gaps = leaf_gaps(program, reference, keep)
    where = max(gaps, key=lambda k: (gaps[k], k))
    return gaps[where], where


def judge(values: Mapping[str, float], limits: Mapping[str, float], notes=None) -> dict[str, Any]:
    numbers = {}
    for name, limit in limits.items():
        if name not in values:
            raise KeyError(f"limit for {name!r} but the run compared no such number")
        v = float(values[name])
        numbers[name] = {"value": v, "limit": limit, "ok": bool(v == v and v <= limit)}
    for name, v in values.items():
        if name not in numbers:  # read and shown, not held to a limit
            numbers[name] = {"value": float(v), "limit": None, "ok": True}
    out = {"correct": all(n["ok"] for n in numbers.values()), "numbers": numbers}
    if notes:
        out["notes"] = notes
    return out


def training_numbers(prog: Mapping[str, Any], ref: Mapping[str, Any]) -> tuple[dict[str, float], dict[str, str]]:
    """``prog`` and ``ref`` each hold ``losses`` (one per step),
    ``grad_norms`` (first gradient as the optimizer gets it, per leaf) and
    ``update_norms`` (norm of the parameters' change after the steps)."""
    keep = moving_leaves(ref["grad_norms"])
    g, g_leaf = norm_gap(prog["grad_norms"], ref["grad_norms"])
    u, u_leaf = norm_gap(prog["update_norms"], ref["update_norms"], keep)
    values = {
        "loss_gap": loss_gap(prog["losses"], ref["losses"]),
        "first_loss_gap": loss_gap(prog["losses"][:1], ref["losses"][:1]),
        "grad_norm_gap": g,
        "update_norm_gap": u,
        "grad_median_gap": statistics.median(leaf_gaps(prog["grad_norms"], ref["grad_norms"]).values()),
    }
    return values, {"grad_norm_gap": g_leaf, "update_norm_gap": u_leaf}
