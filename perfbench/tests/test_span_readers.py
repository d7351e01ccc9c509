"""The readers that lay host spans against device operations, each
spelled out on a hand-made trace whose intersections are known: device
busy inside a span, idle inside and outside spans, children per parent,
one span's share of another. And the metric files that name them."""

import importlib

import pytest

from perfbench import harness, tracered as R
from perfbench.readers import _intervals as I

METRICS = harness.metric_files()


def read(reader, args, trace):
    return importlib.import_module(f"perfbench.readers.{reader}").read({"trace": trace}, {"args": args})


def op(lo, hi):
    return ("%fusion.1 = f32[8]{0} fusion(f32[8] %x)", "fusion_fusion_f32_8_", lo, hi)


def trace(spans=True):
    """Two engine steps on a clock in seconds. The device works 1.0-2.0,
    2.2-3.0, 3.5-4.0 and 4.1-4.4: busy 2.6, idle 0.2 + 0.5 + 0.1 = 0.8.

    step A 0.9-3.1: admit 0.9-2.1 (busy 1.0, idle 0.1), grow 2.1-2.15
    (idle 0.05), decode_prep 2.15-2.2 (idle 0.05), decode 2.2-3.0, retire
    3.0-3.1 (idle 0.1, the device done at 3.0); 3.1-3.4 between the steps
    (idle 0.3); step B 3.4-4.5: grow and decode_prep 3.4-3.5 (idle 0.1),
    decode 3.5-4.05 (4.0-4.05 idle: 0.05), retire 4.05-4.5 (4.05-4.1
    idle: 0.05; after 4.4 the device is done for good, which is no gap)."""
    ops = [op(1.0, 2.0), op(2.2, 3.0), op(3.5, 4.0), op(4.1, 4.4)]
    host = [("perfbench/engine_step", 0.9, 3.1), ("perfbench/engine_step", 3.4, 4.5)]
    if spans:
        host += [
            ("serve/step", 0.9, 3.1), ("serve/admit", 0.9, 2.1), ("serve/admit_prep", 0.9, 1.0),
            ("serve/prefill", 1.0, 2.05), ("serve/grow", 2.1, 2.15), ("serve/decode_prep", 2.15, 2.2),
            ("serve/decode", 2.2, 3.0), ("serve/retire", 3.0, 3.1),
            ("serve/step", 3.4, 4.5), ("serve/grow", 3.4, 3.45), ("serve/decode_prep", 3.45, 3.5),
            ("serve/decode", 3.5, 4.05), ("serve/retire", 4.05, 4.5),
        ]
    return R.Trace({0: ops}, host)


DECODE = ["serve/grow", "serve/decode_prep", "serve/decode", "serve/retire"]
CASES = [
    ("busy_ms_in_span", {"span_name": "serve/admit"}, 1000.0),
    ("busy_ms_in_span", {"span_name": "serve/decode"}, 1000.0 * (0.8 + 0.5) / 2),
    ("idle_ms_in_span", {"span_names": ["serve/admit"], "per_span": "serve/step"}, 1000.0 * 0.1 / 2),
    ("idle_ms_in_span", {"span_names": DECODE, "per_span": "serve/step"}, 1000.0 * (0.2 + 0.2) / 2),
    ("idle_ms_in_span", {"span_names": ["serve/step"], "per_span": "serve/step", "outside": True}, 1000.0 * 0.3 / 2),
    ("idle_ms_in_span", {"span_names": ["serve/step"], "per_span": "serve/step"}, 1000.0 * 0.5 / 2),
    ("child_span_count", {"parent": "serve/step", "child": "serve/admit", "stat": "max"}, 1.0),
    ("child_span_count", {"parent": "serve/step", "child": "serve/admit", "stat": "p50"}, 0.5),
    ("child_span_count", {"parent": "serve/step", "child": "serve/grow", "stat": "mean"}, 1.0),
    ("span_share", {"span_name": "serve/admit", "of": "serve/step"}, 100.0 * 1.2 / 3.3),
    ("span_share", {"span_name": "serve/step", "of": "serve/step"}, 100.0),
]


@pytest.mark.parametrize("reader,args,want", CASES, ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_reader_on_known_intersections(reader, args, want):
    assert read(reader, args, trace()) == pytest.approx(want)


def test_the_idle_parts_sum_to_the_whole_idle_time():
    t = trace()
    parts = [
        read("idle_ms_in_span", {"span_names": names, "per_span": "serve/step", **kw}, t)
        for names, kw in ((["serve/admit"], {}), (DECODE, {}), (["serve/step"], {"outside": True}))
    ]
    # what the accepted metric reads on the same trace (host_gap_ms_per_step.serve)
    whole = read("idle_ms_per_step", {"step_span": "perfbench/engine_step"}, t)
    assert sum(parts) == pytest.approx(whole) == pytest.approx(1000.0 * 0.8 / 2)


def test_interval_helpers():
    t = trace()
    assert I.total(I.busy_intervals(t)) == pytest.approx(2.6)
    assert t.idle_gaps() == [(2.0, 2.2), (3.0, 3.5), (4.0, 4.1)]
    assert I.overlap([(0.0, 1.0), (2.0, 3.0)], [(0.5, 2.5)]) == pytest.approx(1.0)
    assert I.spans_named(t, "serve/admit") == [(0.9, 2.1)]
    assert len(I.spans_named(t, ["serve/grow", "serve/retire"])) == 4


@pytest.mark.parametrize("reader,args", [c[:2] for c in CASES], ids=[f"{c[0]}-{i}" for i, c in enumerate(CASES)])
def test_no_span_of_the_name_reads_nothing(reader, args):
    # The parent commit's engine opens no span: the metric is left out.
    assert read(reader, args, trace(spans=False)) is None


def reads_a_serve_span(metric) -> bool:
    values = [x for v in metric["args"].values() for x in (v if isinstance(v, list) else [v])]
    return any(str(x).startswith("serve/") for x in values)


SPAN_METRICS = sorted(n for n, m in METRICS.items() if reads_a_serve_span(m))


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_each_span_metric_reads_the_spans_and_nothing_without_them(name):
    m = METRICS[name]
    reader = importlib.import_module(f"perfbench.readers.{m['reader']}")
    v = reader.read({"trace": trace()}, m)
    assert isinstance(v, float) and v >= 0.0
    assert reader.read({"trace": trace(spans=False)}, m) is None
    assert m["source"] == "program_span" and m["workloads"] == ["gpt2s-serve-closed-1chip"]


def test_the_span_metrics_are_the_eight_of_the_table():
    assert SPAN_METRICS == sorted([
        "serve_admit_ms_p50", "serve_device_ms_per_admit", "serve_admits_per_step_p95", "serve_admit_wall_share",
        "serve_decode_prep_ms_p50", "serve_idle_in_admit_ms_per_step", "serve_idle_in_decode_ms_per_step",
        "serve_idle_between_steps_ms_per_step",
    ])
