"""The program's phase spans in a profiler trace of a served window, at a
size a CPU test can hold: a tiny ``cascade_fused`` and ``tree_fused``
server under the benchmark's window (``bench.step`` around every loop
step) leave ``loop.*`` and ``serve.*`` spans in the ``.xplane.pb``, on the
same clock and nested inside the benchmark's steps, and ``trace.load``
reads them."""
import _paths  # noqa: F401
import jax
import pytest
import tiny_run

from harness import system, trace, traffic, window

PHASE = {
    "cascade_fused": {"loop.step", "loop.admit", "loop.route", "serve.round",
                      "serve.plan", "serve.dispatch", "serve.wait",
                      "serve.commit"},
    "tree_fused": {"loop.step", "loop.admit", "loop.route", "serve.round",
                   "serve.dispatch", "serve.drain", "serve.wait"},
}


def _inside(inner, outers) -> bool:
    return any(o.start <= inner.start and
               inner.start + inner.dur <= o.start + o.dur for o in outers)


@pytest.mark.parametrize("mode", sorted(PHASE))
def test_program_spans_nest_inside_the_benchmark_steps(mode, tmp_path):
    from repro.models import model as M

    c, mix = tiny_run.tiny(mode)
    cfg = system.model_config(c)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    srv = system.server(c, cfg, params, None)
    sched, loop = system.loop(srv, c["server"]["max_batch"])
    offered = traffic.schedule(mix, c["vocab_size"])[:4]
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            win = window.run(sched, loop, offered, 3.0, trace=True)
    assert win.steps > 0
    raw = trace.load(trace.latest_xplane(str(tmp_path)))
    evs = [e for lines in raw.values() for es in lines.values() for e in es]
    by = {}
    for e in evs:
        by.setdefault(e.name, []).append(e)
    assert PHASE[mode] <= set(by), sorted(by)
    # one loop.step per benchmark step, each inside it on the trace clock
    assert len(by["loop.step"]) == len(by["bench.step"]) == win.steps
    assert all(_inside(e, by["bench.step"]) for e in by["loop.step"])
    for name in PHASE[mode] - {"loop.step"}:
        assert all(_inside(e, by["loop.step"]) for e in by[name]), name
    # the server's phases run in its round, or in the loop's drain before
    # an admission; a dense admission's prefill dispatches and waits inside
    # loop.admit
    rounds = by["serve.round"] + by.get("loop.drain", [])
    for name in PHASE[mode] & {"serve.plan", "serve.dispatch", "serve.wait",
                               "serve.commit", "serve.drain"}:
        outer = rounds + (by["loop.admit"] if name in (
            "serve.dispatch", "serve.wait") else [])
        assert all(_inside(e, outer) for e in by[name]), name
