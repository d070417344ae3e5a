"""The trace reduction on a small recorded trace: a quarter second of a
traced ``sc2-3b.decode-batch`` window on a TPU v5e (device modules and ops
of chip 0, and the benchmark's host spans; op names other than kernel
calls cut to the instruction name)."""
import gzip
import json
import os

import _paths  # noqa: F401

from harness import readers, registry, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_excerpt.json.gz")
KERNELS = ["int8_matmul", "tree_attention_partial"]


def _raw():
    with gzip.open(DATA, "rt") as f:
        d = json.load(f)
    raw = {plane: {line: [trace.Event(n, s, dur) for n, s, dur in evs]
                   for line, evs in lines.items()}
           for plane, lines in d["planes"].items()}
    return raw, tuple(d["window"])


def _reduced():
    raw, window = _raw()
    table = registry.load_json_file("programs.json")
    return raw, window, trace.reduce(raw, KERNELS, table, window=window)


def test_busy_is_the_union_of_op_intervals():
    raw, (t0, t1), red = _reduced()
    ops = raw["/device:TPU:0"]["XLA Ops"]
    # by hand: walk the intervals in start order, extending the current run
    busy, end = 0.0, t0
    for e in sorted(ops, key=lambda e: e.start):
        a, b = max(e.start, t0), min(e.start + e.dur, t1)
        if b <= end:
            continue
        busy += b - max(a, end)
        end = b
    assert abs(red.busy_s - busy) < 1e-9
    assert 0 < red.busy_s <= red.window_s
    idle = sum(s for _, s in red.gaps)
    assert abs(idle + red.busy_s - red.window_s) < 1e-9


def test_programs_and_kernels_are_named_not_categories():
    raw, (t0, t1), red = _reduced()
    mods = [e for e in raw["/device:TPU:0"]["XLA Modules"]
            if e.start < t1 and e.start + e.dur > t0]
    assert abs(sum(red.programs.values()) - sum(e.dur for e in mods)) < 1e-9
    phases = {k.split(":", 1)[0] for k in red.programs}
    assert "draft" in phases and "verify" in phases
    assert not any("while" in k or "fusion" in k for k in red.programs)
    calls = [e for e in raw["/device:TPU:0"]["XLA Ops"]
             if e.name.startswith("%int8_matmul")
             and e.start < t1 and e.start + e.dur > t0]
    assert len(red.kernels["int8_matmul"]) == len(calls) > 0


def test_breakdown_lists_programs_kernels_and_idle_by_host_span():
    _, _, red = _reduced()
    b = readers.breakdown(red)
    names = [n for n, _ in b["device_ops"]]
    assert len(names) <= 10 and any(n.startswith("kernel:") for n in names)
    assert all(v > 0 for _, v in b["device_ops"])
    assert {n for n, _ in b["idle_gaps"]} <= {
        "bench.step", "bench.submit", "bench.wait", "outside_bench_spans"}
