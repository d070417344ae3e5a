"""Cut the excerpt that ``test_harness_trace.py`` reads from a traced run.

    python3 bench/tests/make_trace_excerpt.py bench/.work/trace \
        bench/tests/data/trace_excerpt.json.gz [--at 5.0] [--span 0.25]

Keeps ``--span`` seconds starting ``--at`` seconds into the run's
``bench.window``: chip 0's module and op events that overlap it (op names
cut as ``trace.load`` cuts them) and the benchmark's host spans, with
times in seconds from the excerpt's start.
"""
import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import readers, trace  # noqa: E402

DEVICE = "/device:TPU:0"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir")
    ap.add_argument("out")
    ap.add_argument("--at", type=float, default=5.0)
    ap.add_argument("--span", type=float, default=0.25)
    a = ap.parse_args()
    names = [m.TRACE_NAME for m in readers.kernel_modules().values()]
    raw = trace.load(trace.latest_xplane(a.trace_dir), names)
    host = [e for lines in raw.values() for evs in lines.values()
            for e in evs if e.name in trace.HOST_SPANS]
    win = next(e for e in host if e.name == "bench.window")
    t0 = win.start + a.at
    t1 = t0 + a.span

    def keep(evs):
        return [[e.name, e.start - t0, e.dur] for e in evs
                if e.start < t1 and e.start + e.dur > t0]

    lines = {n: keep(raw[DEVICE].get(n, []))
             for n in trace.MODULE_LINES + trace.OP_LINES}
    spans = keep([e for e in host if e.name != "bench.window"])
    out = {"window": [0.0, a.span],
           "planes": {DEVICE: lines, "/host:CPU": {"bench": spans}}}
    with gzip.open(a.out, "wt") as f:
        json.dump(out, f)
    print(f"{a.out}: " + ", ".join(f"{n} {len(v)}" for n, v in
                                   lines.items()) + f", host spans {len(spans)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
