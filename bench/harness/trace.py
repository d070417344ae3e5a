"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read.

Device planes (``/device:TPU:<n>``) carry a line of XLA modules (one event
per jitted program run) and a line of XLA ops. The reduction keeps, per
chip, inside the window:

- ``busy_s``: the union of the op intervals;
- ``programs``: device seconds per jitted program, keyed
  ``<phase>:<module>``: the module name without its numeric suffix
  (``jit__lambda(2093..)`` -> ``jit__lambda``) and the phase of the served
  path that ``programs.json`` gives it, by name or, for a module whose
  name says nothing (``jit__unknown``), by the kernels called inside it;
- ``kernels``: per kernel, its call events (the op whose HLO instruction
  is named after the kernel); each event's name is the instruction's HLO
  text, with the shapes of its operands and results;
- ``gaps``: idle intervals between ops, each named by the host span of the
  benchmark (``bench.step``, ``bench.submit``, ``bench.wait``) that covers
  most of it.

Host and device events share the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_SPANS = ("bench.step", "bench.submit", "bench.wait", "bench.window")

_SUFFIX = re.compile(r"\(-?\d+\)$")
_OP_NAME = re.compile(r"^%([A-Za-z_][A-Za-z0-9_]*?)(?:\.\d+)? = ")


@dataclasses.dataclass
class Event:
    name: str
    start: float            # seconds, trace clock
    dur: float


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float           # mean over chips
    chips: int
    programs: Dict[str, float]          # summed over chips
    kernels: Dict[str, List[Event]]
    gaps: List[Tuple[str, float]]       # (host span, seconds), longest first


def latest_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    return files[-1] if files else None


def load(path: str, kernel_names: List[str] = ()):
    """Raw events: {plane name: {line name: [Event]}}. An op event keeps its
    whole HLO text only where it is a kernel call (its shapes are read);
    other ops keep only the instruction's name, so that the millions of
    ops of a long window fit in memory."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in pd.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            ops = line.name in OP_LINES
            evs = []
            for e in line.events:
                name = e.name
                if ops and kernel_of(name, kernel_names) is None:
                    name = name.partition(" = ")[0]
                evs.append(Event(name, e.start_ns * 1e-9,
                                 e.duration_ns * 1e-9))
            lines[line.name] = evs
        out[plane.name] = lines
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _clip(evs: List[Event], t0: float, t1: float) -> List[Event]:
    return [e for e in evs if e.start < t1 and e.start + e.dur > t0]


def _module_phase(name: str, kernels_inside: set, table: dict) -> str:
    for phase, names in table["phases"].items():
        if name in names:
            return phase
    for kernel, phase in table.get("by_kernel", {}).items():
        if kernel in kernels_inside:
            return phase
    return "other"


def kernel_of(op_name: str, kernel_names: List[str]) -> Optional[str]:
    """The kernel an op event is a call of (its HLO instruction is named
    after the kernel), or None."""
    m = _OP_NAME.match(op_name)
    if not m:
        return None
    base = m.group(1)
    return base if base in kernel_names else None


def reduce(raw: dict, kernel_names: List[str], table: dict,
           window: Optional[Tuple[float, float]] = None) -> Reduced:
    host = [e for lines in raw.values() for evs in lines.values()
            for e in evs if e.name in HOST_SPANS]
    if window is None:
        win = [e for e in host if e.name == "bench.window"]
        if not win:
            raise ValueError("trace has no bench.window span")
        t0, t1 = win[0].start, win[0].start + win[0].dur
    else:
        t0, t1 = window
    devices = [p for p in raw if p.startswith("/device:TPU:")]
    programs: Dict[str, float] = {}
    kernels: Dict[str, List[Event]] = {k: [] for k in kernel_names}
    busy_total, gaps = 0.0, []
    spans = sorted(((e.start, e.start + e.dur, e.name) for e in host
                    if e.name != "bench.window"))
    for dev in devices:
        lines = raw[dev]
        mod = sorted(_clip([e for n in MODULE_LINES
                            for e in lines.get(n, [])], t0, t1),
                     key=lambda e: e.start)
        op = sorted(_clip([e for n in OP_LINES for e in lines.get(n, [])],
                          t0, t1), key=lambda e: e.start)
        calls = []                      # (start, kernel) of kernel calls
        for e in op:
            k = kernel_of(e.name, kernel_names)
            if k is not None:
                kernels[k].append(e)
                calls.append((e.start, k))
        starts = [c[0] for c in calls]
        for e in mod:
            lo = bisect.bisect_left(starts, e.start)
            hi = bisect.bisect_right(starts, e.start + e.dur)
            inside = {k for _, k in calls[lo:hi]}
            name = _SUFFIX.sub("", e.name)
            key = f"{_module_phase(name, inside, table)}:{name}"
            programs[key] = programs.get(key, 0.0) + e.dur
        busy = _union([(max(e.start, t0), min(e.start + e.dur, t1))
                       for e in op])
        busy_total += sum(b - a for a, b in busy)
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_host_span(spans, a, b), b - a))
    n = max(len(devices), 1)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(t1 - t0, busy_total / n, len(devices), programs, kernels,
                   gaps)


def _host_span(spans, a: float, b: float) -> str:
    """The host span that covers most of [a, b] (spans are sorted and do
    not overlap)."""
    best, cover = "outside_bench_spans", 0.0
    i = max(bisect.bisect_right(spans, (a, float("inf"), "")) - 1, 0)
    for s0, s1, name in spans[i:]:
        if s1 <= a:
            continue
        if s0 >= b:
            break
        c = min(s1, b) - max(s0, a)
        if c > cover:
            best, cover = name, c
    return best

