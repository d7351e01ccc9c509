"""The general traffic generators: one per kind of input, each reading a
traffic file's parameters and a seed. A new mix is a new data file.
"""

from __future__ import annotations

import math
from typing import Any, Mapping

import numpy as np


def spread_lengths(spec: Mapping[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-points of ``n`` equal shares of the stated
    distribution (uniform, log-uniform or fixed, from ``lo`` to ``hi``
    inclusive): the distribution in ``n`` classes, with no draw."""
    lo, hi = int(spec["lo"]), int(spec["hi"])
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = lo + q * (hi + 1 - lo)
    elif spec["dist"] == "loguniform":
        x = np.exp(math.log(lo) + q * (math.log(hi + 1) - math.log(lo)))
    elif spec["dist"] == "fixed":
        x = np.full(n, lo, np.float64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(x.astype(np.int64), lo, hi)


def request_pool(traffic: Mapping[str, Any], seed: int):
    """The requests a run may send, in the order clients take them.

    The stated length distributions are cut into ``length_classes``
    classes of (prompt, answer) lengths, paired once from ``lengths_seed``.
    The pool is round after round of all the classes, each round in an
    order of its own from the seed, with token ids of its own. So every
    seed, and every stretch of a window that is a few rounds long, holds
    the same sizes: the seed changes the inputs and the order, not the
    amount of work (a window serves some hundreds of requests; drawn one
    by one, their mean answer length alone moved the rate by 2.5%)."""
    n, classes = int(traffic["pool_requests"]), int(traffic["length_classes"])
    pairing = np.random.default_rng(int(traffic["lengths_seed"])).permutation(classes)
    answer = spread_lengths(traffic["output_len"], classes)[pairing]
    prompt = np.minimum(spread_lengths(traffic["prompt_len"], classes), int(traffic["max_total_len"]) - answer)
    rng = np.random.default_rng(seed)
    order = np.concatenate([rng.permutation(classes) for _ in range(-(-n // classes))])[:n]
    ids = rng.integers(0, int(traffic["token_id_below"]), int(prompt[order].sum()), dtype=np.int32)
    cuts = np.cumsum(prompt[order])[:-1]
    return np.split(ids, cuts), [int(answer[i]) for i in order]


def poisson_arrivals(rate_rps: float, n: int, rng: np.random.Generator, burst: Mapping[str, Any] | None = None) -> np.ndarray:
    """Arrival offsets in seconds of an open loop: exponential gaps at
    ``rate_rps``; with ``burst`` = {"every_s", "size"}, that many extra
    requests arrive together at each multiple of ``every_s``. (For the
    open-loop cells to come; arithmetic as serve/loadgen.py has it.)"""
    t = np.cumsum(rng.exponential(1.0 / rate_rps, n))
    if burst:
        k = np.arange(1, int(t[-1] // burst["every_s"]) + 1) * burst["every_s"]
        t = np.sort(np.concatenate([t, np.repeat(k, int(burst["size"]))]))[:n]
    return t


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), NaN when empty."""
    if len(values) == 0:
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def lm_tokens(seed: int, rows: int, seq_len: int, id_below: int) -> np.ndarray:
    """[rows, seq_len + 1] token ids, every row different."""
    return np.random.default_rng(seed).integers(0, id_below, (rows, seq_len + 1), dtype=np.int32)


def cifar_rows(seed, rows: int, image: int = 32, classes: int = 10, base_rows: int = 65536):
    """CIFAR-shaped uint8 images and labels, every row different.

    A window needs a million rows; drawing each byte would take longer
    than the window. A base of ``base_rows`` random rows is drawn, and each
    further tile is the base XOR a random pattern of its own, which keeps
    rows distinct and byte statistics uniform. What then takes the time is
    the first touch of fresh memory (2 s a GiB on the chip's host, PR 24),
    so a driver asks for the rows it will feed and no more."""
    rng = np.random.default_rng(seed)
    base_n = min(rows, base_rows)
    base = rng.integers(0, 256, (base_n, image, image, 3), dtype=np.uint8)
    images = np.empty((rows, image, image, 3), np.uint8)
    images[:base_n] = base
    lo = base_n
    while lo < rows:
        n = min(base_n, rows - lo)
        pattern = rng.integers(0, 256, (1, image, image, 3), dtype=np.uint8)
        np.bitwise_xor(base[:n], pattern, out=images[lo:lo + n])
        lo += n
    labels = rng.integers(0, classes, rows, dtype=np.int32)
    return images, labels


def series_summary(stamps, compiles_in_window: int, unit_steps: str = "steps") -> dict[str, Any]:
    """Summary of the time between consecutive fences of a window."""
    gaps = np.diff(np.asarray(stamps, np.float64)) * 1e3
    if len(gaps) == 0:
        return {"n": 0, "compiles_in_window": compiles_in_window}
    i = int(np.argmax(gaps))
    return {
        "n": int(len(gaps)),
        "of": unit_steps,
        "gap_ms_median": float(np.median(gaps)),
        "gap_ms_p99": float(np.percentile(gaps, 99)),
        "gap_ms_max": float(gaps[i]),
        "max_at": i,
        "gaps_over_2x_median": int(np.sum(gaps > 2 * np.median(gaps))),
        "excess_ms_over_median": float(np.sum(np.maximum(gaps - np.median(gaps), 0.0))),
        "compiles_in_window": int(compiles_in_window),
    }
