"""The device's idle seconds, named by the innermost host span open over
them.

    PYTHONPATH=bench python3 -m harness.idle <trace dir>

Reads the newest ``.xplane.pb`` under the directory (``bench/.work/trace``
after a ``--trace 1`` run, or what ``launch/serve.py --profile-dir``
writes) through ``trace.load``. The window is the ``bench.window`` span
where the trace has one, else the first to the last device op. The device
is busy where any op of a ``/device:TPU:<n>`` plane's ``XLA Ops`` line
runs; every idle stretch between them is split over the host spans, the
program's phases (``serving.telemetry.PHASES``: ``loop.*``, ``serve.*``)
and the benchmark's own (``trace.HOST_SPANS``), by the innermost span open
at each instant: spans of one thread nest, so that is the one opened last.
Prints one JSON object: window, busy and idle seconds per chip, and idle
seconds by innermost span, largest first (``"(no span)"`` where none was
open).

``trace.reduce`` names each gap by the ``bench.*`` span over most of it
instead; this is the finer reading, made by hand from a traced run.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from harness import system, trace

Interval = Tuple[float, float]
Span = Tuple[float, float, str]
NO_SPAN = "(no span)"
WINDOW = "bench.window"


def span_names() -> frozenset:
    system.import_program()
    from repro.serving.telemetry import PHASES

    return frozenset(PHASES) | frozenset(trace.HOST_SPANS) - {WINDOW}


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The stretches of ``window`` that no interval of ``busy`` (a sorted
    union) covers: the edges between busy intervals, as ``trace.reduce``
    takes them, clipped to the window."""
    t0, t1 = window
    edges = [t0] + [t for ab in busy for t in ab] + [t1]
    out = [(max(a, t0), min(b, t1)) for a, b in zip(edges[::2], edges[1::2])]
    return [(a, b) for a, b in out if b > a]


def innermost(spans: Sequence[Span]) -> List[Span]:
    """Flatten nested spans into consecutive segments, each named by the
    innermost span open over it."""
    segs: List[Span] = []
    stack: List[Tuple[float, str]] = []          # (end, name)
    t = None
    for s0, s1, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= s0:     # close what ended before
            end, nm = stack.pop()
            if t < end:
                segs.append((t, end, nm))
            t = max(t, end)
        if stack and t < s0:
            segs.append((t, s0, stack[-1][1]))
        stack.append((s1, name))
        t = s0
    while stack:
        end, nm = stack.pop()
        if t < end:
            segs.append((t, end, nm))
        t = max(t, end)
    return segs


def idle_by_span(idle: Sequence[Interval],
                 segs: Sequence[Span]) -> Dict[str, float]:
    """Seconds of each idle stretch under each innermost-span segment
    (both lists sorted by start)."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= a:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < b:
            c = min(b, segs[k][1]) - max(a, segs[k][0])
            if c > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + c
                covered += c
            k += 1
        if b - a > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a - covered)
    return out


def report(raw: dict, names: frozenset,
           window: Optional[Interval] = None) -> dict:
    """Idle seconds by innermost span in ``raw`` (``trace.load``'s form),
    inside ``window`` or, where it is None, the ``bench.window`` span or
    else the first to the last device op."""
    host = [e for p, lines in raw.items() if not p.startswith("/device:")
            for evs in lines.values() for e in evs
            if e.name in names or e.name == WINDOW]
    chips = [[(e.start, e.start + e.dur) for n in trace.OP_LINES
              for e in lines.get(n, [])]
             for p, lines in raw.items() if p.startswith("/device:TPU:")]
    if window is None:
        win = [e for e in host if e.name == WINDOW]
        every = [t for ops in chips for op in ops for t in op]
        window = ((win[0].start, win[0].start + win[0].dur) if win else
                  (min(every), max(every)) if every else (0.0, 0.0))
    segs = innermost([(e.start, e.start + e.dur, e.name) for e in host
                      if e.name != WINDOW])
    t0, t1 = window
    busy_s = idle_s = 0.0
    by: Dict[str, float] = {}
    for ops in chips:
        busy = trace._union([(max(a, t0), min(b, t1)) for a, b in ops
                             if b > t0 and a < t1])
        idle = gaps(busy, window)
        busy_s += sum(b - a for a, b in busy)
        idle_s += sum(b - a for a, b in idle)
        for k, v in idle_by_span(idle, segs).items():
            by[k] = by.get(k, 0.0) + v
    n = max(len(chips), 1)
    return {
        "window_s": t1 - t0,
        "chips": len(chips),
        "busy_s": busy_s / n,
        "idle_s": idle_s / n,
        "idle_by_span": dict(sorted(((k, v / n) for k, v in by.items()),
                                    key=lambda kv: -kv[1])),
    }


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = trace.latest_xplane(argv[0])
    if path is None:
        print(f"no .xplane.pb under {argv[0]}", file=sys.stderr)
        return 1
    print(json.dumps(report(trace.load(path), span_names())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
