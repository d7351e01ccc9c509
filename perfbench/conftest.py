"""``tests/test_readers.py`` reads every metric file there is from one
hand-made trace. The metrics that read the engine's ``serve/*`` spans
(PR 25) find none in it, and the PR that adds a metric may only add files
to the benchmark, not edit that trace. So the spans are laid over it
here; ``tests/test_span_readers.py`` is where those readers are spelled
out. A ``benchmark`` PR moves these rows into ``test_readers.py::trace``
and deletes this file."""

import pytest

# One engine step over the hand-made trace's operations (0.0-0.9, 1.0-1.2).
SERVE_SPANS = [
    ("serve/step", 0.0, 1.2), ("serve/admit", 0.0, 0.5), ("serve/admit_prep", 0.0, 0.01),
    ("serve/prefill", 0.01, 0.5), ("serve/grow", 0.5, 0.51), ("serve/decode_prep", 0.9, 1.0),
    ("serve/decode", 1.0, 1.15), ("serve/retire", 1.15, 1.2),
]


@pytest.fixture(autouse=True)
def serve_spans_in_the_hand_made_trace(request, monkeypatch):
    module = request.module
    if module.__name__.rpartition(".")[2] != "test_readers":
        return
    plain = module.trace

    def trace(with_kernels=True):
        t = plain(with_kernels)
        if with_kernels:
            t.host_spans = t.host_spans + SERVE_SPANS
        return t

    monkeypatch.setattr(module, "trace", trace)
