"""``harness.idle``: the device's idle stretches named by the innermost
host span open over them, on synthetic intervals checked by hand and on
the recorded trace excerpt of ``test_harness_trace``."""
import pytest

import _paths  # noqa: F401
import test_harness_trace as recorded

from harness import idle, trace
from harness.trace import Event

NAMES = idle.span_names()


def test_gaps_are_the_window_less_the_busy_union():
    busy = trace._union([(2.0, 3.0), (1.0, 2.5), (5.0, 6.0), (5.5, 7.5)])
    assert busy == [(1.0, 3.0), (5.0, 7.5)]
    assert idle.gaps(busy, (0.0, 10.0)) == [(0.0, 1.0), (3.0, 5.0),
                                            (7.5, 10.0)]
    assert idle.gaps(busy, (1.5, 7.0)) == [(3.0, 5.0)]
    assert idle.gaps([], (0.0, 1.0)) == [(0.0, 1.0)]


def test_innermost_flattens_nested_spans():
    spans = [(0.0, 10.0, "loop.step"), (1.0, 6.0, "serve.round"),
             (2.0, 3.0, "serve.dispatch"), (3.0, 5.0, "serve.wait"),
             (7.0, 8.0, "loop.route"), (12.0, 13.0, "loop.step")]
    assert idle.innermost(spans) == [
        (0.0, 1.0, "loop.step"), (1.0, 2.0, "serve.round"),
        (2.0, 3.0, "serve.dispatch"), (3.0, 5.0, "serve.wait"),
        (5.0, 6.0, "serve.round"), (6.0, 7.0, "loop.step"),
        (7.0, 8.0, "loop.route"), (8.0, 10.0, "loop.step"),
        (12.0, 13.0, "loop.step")]


def _raw(window):
    spans = [(0.0, 10.0, "loop.step"), (1.0, 6.0, "serve.round"),
             (2.0, 3.0, "serve.dispatch"), (3.0, 5.0, "serve.wait"),
             (7.0, 8.0, "loop.route"), (5.2, 5.4, "not.a.phase")]
    if window:
        spans.append((0.0, 12.0, "bench.window"))
    host = [Event(n, a, b - a) for a, b, n in spans]
    ops = [Event("%fusion", 2.5, 2.0), Event("%fusion", 7.5, 3.5)]
    return {"/host:CPU": {"python": host}, "/device:TPU:0": {"XLA Ops": ops}}


@pytest.mark.parametrize("window", [True, False])
def test_idle_by_innermost_span(window):
    # the device ran [2.5, 4.5] and [7.5, 11]; the window is the
    # bench.window span [0, 12], or else the first to the last op; a span
    # that is no phase (inside serve.round's idle) names nothing
    out = idle.report(_raw(window), NAMES)
    if window:
        assert out["window_s"] == pytest.approx(12.0)
        assert out["idle_by_span"] == pytest.approx({
            "loop.step": 1.0 + 1.0, "serve.round": 1.0 + 1.0,
            "serve.dispatch": 0.5, "serve.wait": 0.5, "loop.route": 0.5,
            idle.NO_SPAN: 1.0})
    else:
        assert out["window_s"] == pytest.approx(8.5)
        assert out["idle_by_span"] == pytest.approx({
            "serve.wait": 0.5, "serve.round": 1.0, "loop.step": 1.0,
            "loop.route": 0.5})
    assert out["busy_s"] == pytest.approx(5.5)
    assert out["idle_s"] == pytest.approx(out["window_s"] - 5.5)
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])


def test_recorded_trace_reads_as_the_reduction_does():
    """On a chip trace the idle seconds agree with ``trace.reduce``; that
    trace holds only the benchmark's spans, all of which are named."""
    raw, window, red = recorded._reduced()
    out = idle.report(raw, NAMES, window=window)
    assert out["busy_s"] == pytest.approx(red.busy_s, abs=1e-9)
    assert out["idle_s"] == pytest.approx(sum(s for _, s in red.gaps),
                                          abs=1e-9)
    assert sum(out["idle_by_span"].values()) == pytest.approx(out["idle_s"])
    assert set(out["idle_by_span"]) <= set(trace.HOST_SPANS) | {idle.NO_SPAN}
    assert out["idle_by_span"].get("bench.step", 0) > 0.5 * out["idle_s"]
