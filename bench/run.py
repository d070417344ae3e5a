#!/usr/bin/env python3
"""Run one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload sc2-3b.decode-batch --seed 12345 \
        --seconds 51 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/mixes/<traffic>.json``). Set-up makes the weights on the device
from the configuration's own seed, builds the server as the serving entry
point does, and warms every program the window can call. The window then
drives the server for ``--seconds`` on the wall clock while the load
generator offers the mix's requests, the same in every run. Afterwards a
sample of the finished requests, drawn from ``--seed``, is checked against
the plain reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` runs
the window under the profiler and reports its per-layer metrics
(``bench/metrics/<metric>.py``). The last stdout line is one JSON object;
the numbers compared for ``correct`` are also the last lines on stderr.
The run fails, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(BENCH, ".work")
CACHE = os.path.join(WORK, "jax_cache")
TRACE_DIR = os.path.join(WORK, "trace")
sys.path.insert(0, BENCH)


class Fail(Exception):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Programs compiled and loaded from the persistent cache."""

    def __init__(self):
        import jax

        self.compiled = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event: str, duration: float, **_) -> None:
        # recorded for every program, whether compiled or read from the
        # persistent cache (a hit also records cache_hits)
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += duration

    def snapshot(self):
        """(programs compiled, programs loaded from the cache)."""
        return (self.compiled - self.hits, self.hits)


class GcClock:
    """Time the interpreter spends collecting garbage while it is open."""

    def __init__(self):
        self.total = self.longest = 0.0
        self._t = None
        gc.callbacks.append(self._phase)

    def _phase(self, phase: str, _info) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.total += d
            self.longest = max(self.longest, d)
            self._t = None

    def close(self) -> "GcClock":
        gc.callbacks.remove(self._phase)
        return self


def configure_jax():
    """The persistent compile cache lives at a fixed path in the checkout,
    whatever the environment says, and keeps every program."""
    os.makedirs(CACHE, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise Fail(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Fail as e:
        log(f"bench: FAILED: {e}")
        return 1
    print(json.dumps(result))
    return 0


def run(args, hooks=None) -> dict:
    """One run of one cell. ``hooks`` (tests only) may replace the device
    check ("devices"), the compile-cache set-up ("jax"), the configuration
    ("c") and mix ("mix"), break the timed path ("break": called with the
    server and loop before the window), and put a control in the
    program's place for the correctness check ("control": a precision of
    the reference)."""
    from harness import registry

    hooks = hooks or {}
    if not (registry.REPO_DIR / "src" / "repro").is_dir():
        raise Fail("the program (src/repro) is not in this checkout")
    spec = registry.benchmark()
    cell = registry.workload(spec, args.workload)
    c = hooks.get("c") or registry.load_json("configs", cell["config"])
    mix = hooks.get("mix") or registry.load_json("mixes", cell["traffic"])
    jax = hooks["jax"]() if "jax" in hooks else configure_jax()
    devs = hooks.get("devices", devices)(jax, cell["chips"])
    counter = CompileCounter()
    return cell_run(args, spec, cell, c, mix, jax, devs, counter, hooks)


def setup(c: dict, mix: dict, jax, counter):
    """Weights, server and warm-up: everything the window needs. Returns
    (params, srv, sched, loop, parts)."""
    from harness import system, warm, weights

    parts = {"start": time.perf_counter() - T_START}
    t = time.perf_counter()
    cfg = system.model_config(c)
    mesh = system.mesh(c)
    params = weights.make(c, system.param_shardings(cfg, mesh))
    jax.block_until_ready(params)
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        system.program_param_shapes(cfg))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    if want != got:
        raise Fail("the benchmark's weights do not match the layout the "
                   "server expects")
    parts["init"] = time.perf_counter() - t
    t = time.perf_counter()
    n0 = counter.snapshot()
    srv = system.server(c, cfg, params, mesh)
    sched, loop = system.loop(srv, c["server"]["max_batch"])
    parts["warm_steps"] = warm.admission(srv, sched, loop, mix,
                                         c["vocab_size"])
    parts["warm_variants"] = warm.variants(srv)
    n1 = counter.snapshot()
    parts["warm"] = time.perf_counter() - t
    parts["compiled"], parts["loaded"] = n1[0] - n0[0], n1[1] - n0[1]
    return params, srv, sched, loop, parts


def cell_run(args, spec, cell, c, mix, jax, devs, counter, hooks) -> dict:
    from harness import correct, measure, readers, traffic, window

    params, srv, sched, loop, parts = setup(c, mix, jax, counter)
    offered = traffic.schedule(mix, c["vocab_size"])
    setup_s = time.perf_counter() - T_START
    log(f"bench: device {devs[0].device_kind} x{len(devs)}; "
        f"workload {cell['name']} seed {args.seed}")
    log(f"bench: setup {setup_s:.3f}s = start {parts['start']:.3f}s + init "
        f"{parts['init']:.3f}s + warm {parts['warm']:.3f}s "
        f"({parts['warm_steps']} warm-up steps, {parts['warm_variants']} "
        f"variants; {parts['compiled']} programs compiled, "
        f"{parts['loaded']} loaded from the cache; whole process "
        f"{counter.snapshot()[0]} compiled, {counter.hits} loaded, "
        f"{counter.compile_s:.1f}s in compile events)")

    if "break" in hooks:
        hooks["break"](srv, loop)
    tel0 = srv.telemetry_totals()
    st0 = srv.stats.copy()
    c0 = counter.snapshot()
    gc_clock = GcClock()
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
        with jax.profiler.TraceAnnotation("bench.window"):
            win = window.run(sched, loop, offered, args.seconds, trace=True)
        jax.profiler.stop_trace()
    else:
        win = window.run(sched, loop, offered, args.seconds)
    gc_clock.close()
    c1 = counter.snapshot()
    tel1 = srv.telemetry_totals()
    st1 = srv.stats.copy()
    in_window = (c1[0] - c0[0], c1[1] - c0[1])
    log(f"bench: window {args.seconds}s, {win.steps} steps, "
        f"{len(win.records)} requests offered; programs compiled in the "
        f"window {in_window[0]}, loaded {in_window[1]}")
    log(host_split(win, srv.stats.get("device_wait", 0.0)
                   - st0.get("device_wait", 0.0), gc_clock))

    e2e = measure.end_to_end(win)
    stats = {k: st1.get(k, 0) - st0.get(k, 0) for k in st1}
    tel = {k: (tel1[k] - tel0[k]) for k in tel1}
    mem = [d.memory_stats() or {} for d in devs]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}

    run_data = readers.RunData(
        c=c, mix=mix, cell=cell, win=win, e2e=e2e, stats=stats, tel=tel,
        device=device, flops=measure.delivered_flops(c, win))
    breakdown = None
    if args.trace:
        trace_sum = readers.reduce_trace(TRACE_DIR)
        run_data.trace = trace_sum
        device["busy_s"] = trace_sum.busy_s
        device["window_s"] = trace_sum.window_s
        breakdown = readers.breakdown(trace_sum)
        metrics = readers.per_layer(spec, cell["name"], run_data)
    else:
        metrics = readers.end_to_end(spec, cell["name"], run_data, setup_s)

    # the program's state goes before the reference runs
    done = [(r.offered.prompt, list(r.request.generated))
            for r in correct.sample(win.records, args.seed)]
    faults = correct.shape_faults(win.records, c["vocab_size"])
    admitted = sum(1 for r in win.records if r.admitted is not None)
    del srv, sched, loop
    gc.collect()
    checks, wrong = judge(c, cell, params, done, faults,
                          hooks.get("control"))
    ok = bool(done) and not faults and not wrong
    result = {"correct": ok, "attempted": admitted,
              "failed": len(faults) + wrong,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for k, v in checks.items():
        log(f"check {k}: {v['value']} limit {v['limit']}")
    return result


def host_split(win, device_wait: float, gc_clock: GcClock) -> str:
    """Where the window's wall time went, by the host clock: steps, the
    part of them the server spent blocked on the device, the longest step
    and when it began, and garbage collection."""
    import numpy as np

    st = np.asarray(win.step_s or [0.0]) * 1e3
    q = np.percentile(st, [50, 90, 99])
    i = int(st.argmax())
    return (f"bench: steps {st.sum() / 1e3:.3f}s of the window, device "
            f"wait {device_wait:.3f}s, host {st.sum() / 1e3 - device_wait:.3f}s"
            f"; step ms p50 {q[0]:.3f} p90 {q[1]:.3f} p99 {q[2]:.3f} "
            f"max {st[i]:.3f} (step {i}, {st[:i].sum() / 1e3:.3f}s in); "
            f"gc {gc_clock.total:.3f}s, longest {gc_clock.longest:.3f}s")


def judge(c, cell, params, seqs, faults, control=None):
    """The checks of ``seqs`` and the number of sampled requests they find
    wrong. ``control``: score, in place of the served tokens, the tokens
    the reference at that precision ranks first."""
    from harness import correct, registry

    limits = registry.load_json("limits", cell["name"])
    mod = registry.load_module("references", c["reference"])
    ref = mod.Reference(c)
    low = mod.Reference(c, control) if control else None
    t = time.perf_counter()
    got = correct.gaps(ref, params, seqs, low)
    lim = limits["mean_gap"]["limit"]
    log(f"bench: reference over {len(seqs)} requests, {got['tokens']} "
        f"{'served' if low is None else control + ' control'} tokens, "
        f"{time.perf_counter() - t:.2f}s; tokens off the reference argmax: "
        f"{got['differ']}; widest gap {got['widest_gap']}")
    for f in faults:
        log(f"bench: malformed: {f}")
    checks = {
        "sampled_requests": {"value": len(seqs), "limit": 1},
        "malformed_requests": {"value": len(faults), "limit": 0},
        "mean_gap": {"value": got["mean_gap"], "limit": lim},
    }
    wrong = len(seqs) if got["mean_gap"] > lim else 0
    return checks, wrong


if __name__ == "__main__":
    sys.exit(main())
