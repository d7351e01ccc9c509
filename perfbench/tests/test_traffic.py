"""The generators are pure functions of the seed."""

import json
from pathlib import Path

import numpy as np

from perfbench import traffic as T

SERVE = json.loads((Path(__file__).resolve().parents[1] / "traffic" / "serve-closed.json").read_text())


def test_request_pool_is_a_pure_function_of_the_seed():
    a, an = T.request_pool(SERVE, 3000000019)
    b, bn = T.request_pool(SERVE, 3000000019)
    assert an == bn and all(np.array_equal(x, y) for x, y in zip(a, b))
    c, cn = T.request_pool(SERVE, 3000000020)
    assert not all(np.array_equal(x, y) for x, y in zip(a, c) if len(x) == len(y))


def test_every_seed_gets_the_same_sizes_in_another_order():
    a, an = T.request_pool(SERVE, 1)
    b, bn = T.request_pool(SERVE, 2)
    assert sorted(zip(map(len, a), an)) == sorted(zip(map(len, b), bn))
    assert list(zip(map(len, a), an)) != list(zip(map(len, b), bn))


def test_every_round_of_the_pool_holds_every_class_once():
    prompts, answers = T.request_pool(SERVE, 11)
    k = SERVE["length_classes"]
    sizes = list(zip(map(len, prompts), answers))
    assert len(set(sizes[:k])) == k
    assert all(sorted(sizes[i:i + k]) == sorted(sizes[:k]) for i in range(0, len(sizes) - k + 1, k))
    # the classes follow the stated distributions: their means sit at the
    # distributions' own (uniform 16..128: 72; log-uniform 64..896: 315)
    assert abs(np.mean(answers[:k]) - 72) < 1 and abs(np.mean([len(p) for p in prompts[:k]]) - 315) < 8


def test_lengths_within_the_stated_ranges():
    prompts, answers = T.request_pool(SERVE, 7)
    assert min(map(len, prompts)) >= 1 and max(map(len, prompts)) <= SERVE["prompt_len"]["hi"]
    assert min(answers) >= SERVE["output_len"]["lo"] and max(answers) <= SERVE["output_len"]["hi"]
    assert max(len(p) + a for p, a in zip(prompts, answers)) <= SERVE["max_total_len"]
    assert max(int(p.max()) for p in prompts) < SERVE["token_id_below"]


def test_cifar_rows_differ_and_repeat_by_seed():
    x, y = T.cifar_rows(5, 300, base_rows=64)
    x2, _ = T.cifar_rows(5, 300, base_rows=64)
    assert np.array_equal(x, x2) and x.dtype == np.uint8 and y.max() < 10
    assert len({r.tobytes() for r in x}) == 300


def test_lm_tokens():
    t = T.lm_tokens(2**31 + 5, 8, 16, 100)
    assert t.shape == (8, 17) and t.max() < 100
    assert np.array_equal(t, T.lm_tokens(2**31 + 5, 8, 16, 100))


def test_poisson_arrivals_rate_and_bursts():
    rng = np.random.default_rng(0)
    t = T.poisson_arrivals(10.0, 2000, rng)
    assert abs(len(t) / t[-1] - 10.0) < 1.0 and np.all(np.diff(t) >= 0)
    tb = T.poisson_arrivals(10.0, 2000, np.random.default_rng(0), {"every_s": 5.0, "size": 20})
    assert np.sum(np.isclose(tb % 5.0, 0.0)) >= 20


def test_series_summary_finds_the_stall():
    stamps = np.cumsum([0.1] * 50)
    stamps[30:] += 0.7
    s = T.series_summary(stamps, 0)
    assert s["n"] == 49 and s["max_at"] == 29
    assert abs(s["gap_ms_max"] - 800) < 1 and abs(s["gap_ms_median"] - 100) < 1
    assert s["gaps_over_2x_median"] == 1 and abs(s["excess_ms_over_median"] - 700) < 1
