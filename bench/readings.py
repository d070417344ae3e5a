#!/usr/bin/env python3
"""Correctness readings of one cell, on the chip at the cell's own size, in
one process: what the correctness limit is set from.

    python3 bench/readings.py --workload sc2-3b.decode-batch --seconds 51 \
        --seeds 101,102,103 --controls int8,fp8

Set-up runs once, then one window of the cell's own traffic (every run
offers the same requests, so every seed's window serves the same tokens;
the seed only draws the sample that is checked). After the window the
server is freed, and every finished request is scored once against the
plain reference: its served tokens (the program's reading) and, for each
control precision, the token that precision ranks first at each position
of the same sequence (the control's reading). The first JSON line reports
the window and those per-request sums, from which the mean gap of any
seed's sample follows. Then, for each seed, the benchmark's own check
(``run.judge``) runs on that seed's sample with the program and with each
control in its place, and one JSON line reports its checks and whether it
found the sample correct. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run as R


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8,fp8")
    args = ap.parse_args(argv)
    from harness import correct, measure, registry, traffic, window

    spec = registry.benchmark()
    cell = registry.workload(spec, args.workload)
    c = registry.load_json("configs", cell["config"])
    mix = registry.load_json("mixes", cell["traffic"])
    jax = R.configure_jax()
    devs = R.devices(jax, cell["chips"])
    counter = R.CompileCounter()
    params, srv, sched, loop, _ = R.setup(c, mix, jax, counter)
    R.log(f"readings: setup done in {time.perf_counter() - R.T_START:.1f}s")
    offered = traffic.schedule(mix, c["vocab_size"])
    tel0, st0 = srv.telemetry_totals(), srv.stats.copy()
    c0 = counter.snapshot()
    win = window.run(sched, loop, offered, args.seconds)
    c1 = counter.snapshot()
    tel1, st1 = srv.telemetry_totals(), srv.stats.copy()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    rounds = float((tel1["rounds"] - tel0["rounds"]).sum())
    acc = float((tel1["accepted"] - tel0["accepted"]).sum())
    started = [r for r in win.records if r.admitted is not None]
    done = sorted((r for r in started if r.request.done),
                  key=lambda r: r.offered.index)
    faults = correct.shape_faults(win.records, c["vocab_size"])
    seqs = [(r.offered.prompt, list(r.request.generated)) for r in done]
    row = {"window": measure.summary(measure.end_to_end(win), args.seconds),
           "steps": st1["steps"] - st0["steps"],
           "accepted_per_slot_round": acc / rounds if rounds else None,
           "memory_peak_bytes": int(peak),
           "programs_in_window": [c1[0] - c0[0], c1[1] - c0[1]],
           "offered_requests": len(win.records),
           "started_requests": len(started),
           "completed_requests": len(done), "faults": faults,
           "done": [[r.offered.index, len(r.offered.prompt),
                     len(r.request.generated)] for r in done]}
    del srv, sched, loop
    gc.collect()
    mod = registry.load_module("references", c["reference"])
    ref = mod.Reference(c)
    controls = [p for p in args.controls.split(",") if p]
    for p in [None] + controls:
        t = time.perf_counter()
        got = correct.gaps(ref, params, seqs,
                           mod.Reference(c, p) if p else None)
        got["score_s"] = time.perf_counter() - t
        row[p or "program"] = got
    print(json.dumps(row), flush=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        picked = [(r.offered.prompt, list(r.request.generated))
                  for r in correct.sample(win.records, seed)]
        for p in [None] + controls:
            checks, wrong = R.judge(c, cell, params, picked, faults, p)
            print(json.dumps({"seed": seed, "scored": p or "program",
                              "correct": bool(picked) and not faults
                              and not wrong, "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
