"""Weights made from the seed, on the device, in one jitted call.

The benchmark makes the weights and hands them to the program and to the
plain reference alike, so the reference takes nothing the program made.
Leaves are named by slash-joined paths that mirror the program's
parameter trees (they are data keys, not imports), so a tree the program
shows as a template can be filled by path.

Distributions are chosen so that no leaf is degenerate: norm scales sit
around 1 (the program's own init zeroes the last BatchNorm scale of each
block, which would make most first gradients exactly zero), biases are
small and non-zero, kernels use the fan-in variance their family uses.
"""

from __future__ import annotations

from typing import Any, Mapping

import numpy as np


def seed31(seed: int) -> int:
    """The driver's seeds exceed 32 signed bits; fold them into 31."""
    return int(seed) % 2147483647


def resnet18_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    widths, stages = cfg["widths"], cfg["stage_sizes"]
    shapes: dict[str, tuple[int, ...]] = {
        "Conv_0/kernel": (3, 3, 3, widths[0]),
        "BatchNorm_0/scale": (widths[0],),
        "BatchNorm_0/bias": (widths[0],),
    }
    cin, blk = widths[0], 0
    for s, (n, cout) in enumerate(zip(stages, widths)):
        for b in range(n):
            p = f"BasicBlock_{blk}"
            stride = 2 if (s > 0 and b == 0) else 1
            shapes[f"{p}/Conv_0/kernel"] = (3, 3, cin, cout)
            shapes[f"{p}/Conv_1/kernel"] = (3, 3, cout, cout)
            for j in (0, 1):
                shapes[f"{p}/BatchNorm_{j}/scale"] = (cout,)
                shapes[f"{p}/BatchNorm_{j}/bias"] = (cout,)
            if stride != 1 or cin != cout:
                shapes[f"{p}/Conv_2/kernel"] = (1, 1, cin, cout)
                shapes[f"{p}/BatchNorm_2/scale"] = (cout,)
                shapes[f"{p}/BatchNorm_2/bias"] = (cout,)
            cin, blk = cout, blk + 1
    shapes["Dense_0/kernel"] = (widths[-1], cfg["num_classes"])
    shapes["Dense_0/bias"] = (cfg["num_classes"],)
    return shapes


def gpt2_shapes(cfg: Mapping[str, Any]) -> dict[str, tuple[int, ...]]:
    d, f, v, t = cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"], cfg["n_positions"]
    shapes: dict[str, tuple[int, ...]] = {
        "tok_embed/embedding": (v, d),
        "pos_embed/embedding": (t, d),
    }
    for i in range(cfg["n_layer"]):
        p = f"block_{i}"
        for ln in ("ln1", "ln2"):
            shapes[f"{p}/{ln}/scale"] = (d,)
            shapes[f"{p}/{ln}/bias"] = (d,)
        for w in ("q", "k", "v", "attn_out"):
            shapes[f"{p}/attn/{w}/kernel"] = (d, d)
        shapes[f"{p}/mlp_in/kernel"] = (d, f)
        shapes[f"{p}/mlp_in/bias"] = (f,)
        shapes[f"{p}/mlp_out/kernel"] = (f, d)
        shapes[f"{p}/mlp_out_bias"] = (d,)
    shapes["ln_f/scale"] = (d,)
    shapes["ln_f/bias"] = (d,)
    shapes["lm_head/kernel"] = (d, v)
    return shapes


SHAPES = {"resnet18": resnet18_shapes, "gpt2": gpt2_shapes}


def _draw(key, path: str, shape, family: str):
    import jax
    import jax.numpy as jnp

    leaf = path.rsplit("/", 1)[-1]
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf == "scale":
        return 1.0 + 0.1 * n
    if leaf in ("bias", "mlp_out_bias"):
        return 0.02 * n
    if family == "resnet18":
        fan_in = int(np.prod(shape[:-1]))
        return n * np.float32(np.sqrt(2.0 / fan_in))
    if path.startswith("pos_embed"):
        return 0.01 * n
    return 0.02 * n


def make_weights(family: str, cfg: Mapping[str, Any], seed: int, dtype="float32", sharding=None):
    """All leaves of one model, as a flat ``{path: array}`` dict."""
    import jax
    import jax.numpy as jnp

    shapes = SHAPES[family](cfg)
    names = sorted(shapes)

    def build(key):
        out = {}
        for i, name in enumerate(names):
            out[name] = _draw(
                jax.random.fold_in(key, i), name, shapes[name], family
            ).astype(jnp.dtype(dtype))
        return out

    jitted = jax.jit(build, out_shardings=sharding) if sharding is not None else jax.jit(build)
    return jitted(jax.random.key(seed31(seed)))


def path_of(keypath) -> str:
    """Slash-joined names of a jax key path (dict keys and attributes)."""
    parts = []
    for k in keypath:
        for attr in ("key", "name", "idx"):
            if hasattr(k, attr):
                parts.append(str(getattr(k, attr)))
                break
    return "/".join(parts)


def fill_tree(template, flat: Mapping[str, Any], prefix: str = ""):
    """A tree shaped like ``template`` with leaves taken from ``flat`` by
    path; a missing path or a wrong shape is an error, not a default."""
    import jax

    def pick(kp, leaf):
        name = path_of(kp)
        if prefix:
            name = name[len(prefix) + 1:] if name.startswith(prefix + "/") else name
        if name not in flat:
            raise KeyError(f"the benchmark makes no weight for leaf {name!r}")
        w = flat[name]
        if tuple(w.shape) != tuple(leaf.shape):
            raise ValueError(f"leaf {name!r}: made {w.shape}, program wants {leaf.shape}")
        return w

    out = jax.tree_util.tree_map_with_path(pick, template)
    n_leaves = len(jax.tree_util.tree_leaves(template))
    if n_leaves != len(flat):
        raise ValueError(f"program tree has {n_leaves} leaves, benchmark made {len(flat)}")
    return out


def flatten_tree(tree) -> dict[str, Any]:
    import jax

    return {path_of(kp): leaf for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}
