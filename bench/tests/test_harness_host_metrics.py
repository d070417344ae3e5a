"""The readers of the program's host phase counters and of the fused
round's ``draft`` scope: each reads its number where the program has the
counter or the scope, and reports nothing where it has not (a program
without the phase spans, a run without a trace, a trace whose ops carry no
scope)."""
import os

import _paths  # noqa: F401
import pytest

from harness import readers, registry, scopes, trace

# a program from before the phase spans: no loop/dispatch/plan counters
OLD_STATS = {"steps": 10, "tokens": 40, "host_syncs": 10, "device_wait": 1.5}
STATS = dict(OLD_STATS, loop_time=2.0, dispatch_time=0.2, plan_time=0.1)
READ = {"host_ms.round": 50.0, "dispatch_ms.round": 20.0,
        "plan_ms.round": 10.0}

OP = ('events {{ metadata_id: {id} offset_ps: {at} duration_ps: {dur} }}')
META = ('event_metadata {{ key: {id} value {{ id: {id} name: "%fusion.{id} '
        '= f32[2] fusion()" {stats} }} }}')


def _xplane(root, scoped: bool) -> str:
    """A trace of one chip: two ops in a 10 us window, the first (2 us,
    nested over a 1 us op) in the round's draft stage, the second (1 us)
    in its verify stage, each scope in the ops' metadata."""
    from jax.profiler import ProfileData

    def stats(path):
        return (f'stats {{ metadata_id: 1 str_value: "{path}" }}'
                if scoped else "")

    ops = [OP.format(id=1, at=1_000_000, dur=2_000_000),
           OP.format(id=3, at=1_500_000, dur=1_000_000),
           OP.format(id=2, at=4_000_000, dur=1_000_000)]
    meta = [META.format(id=1, stats=stats("jit(round_core)/jit(main)/draft/"
                                          "cond/branch_1_fun/while")),
            META.format(id=3, stats=stats("jit(round_core)/jit(main)/draft/"
                                          "cond/branch_1_fun/while/body")),
            META.format(id=2, stats=stats("jit(round_core)/jit(main)/verify/"
                                          "dot_general"))]
    text = (
        'planes { id: 1 name: "/device:TPU:0" '
        'lines { id: 1 name: "XLA Ops" timestamp_ns: 1000 '
        + " ".join(ops) + " } " + " ".join(meta)
        + ' stat_metadata { key: 1 value { id: 1 name: "tf_op" } } } '
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
        'timestamp_ns: 1000 events { metadata_id: 1 offset_ps: 0 '
        'duration_ps: 10000000 } } event_metadata { key: 1 value { id: 1 '
        'name: "bench.window" } } }')
    d = os.path.join(root, "plugins", "profile", "run")
    os.makedirs(d)
    with open(os.path.join(d, "host.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(root)


def _run(stats, busy_s=None):
    red = (None if busy_s is None else
           trace.Reduced(10e-6, busy_s, 1, {}, {}, []))
    return readers.RunData(c={}, mix={}, cell={}, win=None, e2e={},
                           stats=stats, tel={}, device={}, flops=0.0,
                           trace=red)


@pytest.mark.parametrize("name", sorted(READ))
def test_counter_readers(name):
    mod = registry.load_module("metrics", name)
    assert mod.read(_run(STATS)) == pytest.approx(READ[name])
    assert mod.read(_run(OLD_STATS)) is None
    assert mod.read(_run(dict(STATS, steps=0))) is None


def test_draft_share_of_the_fused_round(tmp_path, monkeypatch):
    mod = registry.load_module("metrics", "draft_busy_share.fused")
    # no trace, and a trace directory without an .xplane.pb
    assert mod.read(_run(STATS)) is None
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "none")
    assert mod.read(_run(STATS, busy_s=3e-6)) is None
    # ops that carry no scope: a program without the named scopes
    monkeypatch.setattr(scopes, "TRACE_DIR", _xplane(tmp_path / "a", False))
    assert mod.read(_run(STATS, busy_s=3e-6)) is None
    # the draft stage's union (2 us, the nested op inside it) over busy
    monkeypatch.setattr(scopes, "TRACE_DIR", _xplane(tmp_path / "b", True))
    assert mod.read(_run(STATS, busy_s=3e-6)) == pytest.approx(200 / 3)
    got = scopes.by_stage(trace.latest_xplane(str(tmp_path / "b")))
    assert got == pytest.approx({"draft": 2e-6, "verify": 1e-6})
