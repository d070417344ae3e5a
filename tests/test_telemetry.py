"""Zero-sync serving telemetry (docs/observability.md): registry/exporter
unit coverage plus the end-to-end invariants the design promises —

  - every accepted token the device tallies is accounted for host-side:
    ``accepted == delivered + overshoot + unrouted + discarded + leftover``
    (the reconciliation identity), in all four proposal modes;
  - overshoot tokens trimmed at retire are EXCLUDED from per-request
    token counts and TPOT;
  - telemetry on vs off changes NO runtime dispatch/sync counter
    (the buffer rides existing executables — the static side of the same
    claim lives in test_dispatch_contracts.py);
  - the Prometheus text rendering, /metrics endpoint and JSONL sink are
    well-formed;
  - every host phase is a profiler span that adds its seconds to its own
    counter, and a blocking dispatch splits into ``serve.dispatch`` and
    ``serve.wait`` seconds that add up to the interval DyTC prices.
"""
import dataclasses
import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from repro.config import get_config
from repro.core.dsia import layer_sparsity
from repro.models import model as M
from repro.serving.exporters import JsonlSink, MetricsHTTPServer
from repro.serving.scheduler import Request, RequestScheduler, ServeLoop
from repro.serving.server import BatchedSpecServer
from repro.serving.telemetry import (
    PHASES,
    Histogram,
    MetricsRegistry,
    PhaseSpans,
    StatsView,
    span,
)

CFG = dataclasses.replace(get_config("vicuna-7b").reduced(), num_layers=3)
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
SPEC = layer_sparsity(CFG, 0.5)
PROMPT = np.arange(1, 9, dtype=np.int32) % CFG.vocab_size


def _server(mode, **kw):
    kwargs = dict(max_batch=2, max_len=64, draft_k=4, tree_expansions=3,
                  adaptive=False, donate=True)
    if mode != "cascade_fused":
        kwargs["draft_spec"] = SPEC
    kwargs.update(kw)
    return BatchedSpecServer(CFG, PARAMS, mode=mode, **kwargs)


# ------------------------------------------------------------ registry units
def test_counter_gauge_get_or_create_by_labels():
    reg = MetricsRegistry()
    reg.counter("hits", slot=0).inc()
    reg.counter("hits", slot=0).inc(2)
    reg.counter("hits", slot=1).inc()
    assert reg.counter("hits", slot=0).value == 3
    assert reg.counter("hits", slot=1).value == 1
    reg.gauge("depth").set(7)
    assert reg.gauge("depth").value == 7
    snap = reg.snapshot()
    assert snap["counters"]['hits{slot="0"}'] == 3
    assert snap["gauges"]["depth"] == 7


def test_stats_view_int_semantics():
    reg = MetricsRegistry()
    sv = StatsView(reg)
    assert sv["steps"] == 0 and isinstance(sv["steps"], int)
    sv["steps"] += 3
    sv["dispatch_time"] += 0.25
    assert sv["steps"] == 3 and isinstance(sv["steps"], int)
    assert sv["dispatch_time"] == pytest.approx(0.25)
    assert isinstance(sv["dispatch_time"], float)
    # the view materializes every mapped counter at zero so a fresh
    # registry snapshot is complete (dashboards see all-zero, not absent)
    assert reg.counter("serve_host_syncs_total").value == 0
    assert set(sv.copy()) == set(iter(sv))
    assert sv.get("not_a_stat", "d") == "d" and "steps" in sv


def test_histogram_bucket_property():
    """Left-closed buckets: an observation equal to edge[i] lands in the
    bucket that edge OPENS (index i+1); below it stays in bucket i. No
    sample is lost or double-counted across the full edge sweep."""
    edges = Histogram.log_edges(1e-4, 512.0)
    assert edges == sorted(edges) and len(set(edges)) == len(edges)
    h = Histogram(list(edges))
    total = 0
    for i, e in enumerate(edges):
        assert h.bucket_index(e) == i + 1            # edge opens bucket i+1
        assert h.bucket_index(e * (1 - 1e-12)) == i  # just below: bucket i
        h.observe(e)
        total += 1
    h.observe(0.0)                                   # below lowest edge
    h.observe(float(edges[-1]) * 4)                  # above highest edge
    total += 2
    assert sum(h.counts) == h.count == total
    assert h.counts[0] == 1 and h.counts[-1] == 2    # top edge + overflow
    # middle buckets got exactly one sample each (their opening edge)
    assert all(c == 1 for c in h.counts[1:-1])


def test_render_prometheus_histogram_cumulative():
    reg = MetricsRegistry()
    hist = reg.histogram("lat_seconds", edges=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        hist.observe(v)
    reg.counter("reqs", mode="x").inc(2)
    text = reg.render_prometheus()
    assert "# TYPE lat_seconds histogram" in text
    assert "# TYPE reqs counter" in text
    assert 'reqs{mode="x"} 2' in text
    les = []
    for line in text.splitlines():
        if line.startswith("lat_seconds_bucket"):
            les.append(float(line.rsplit(" ", 1)[1]))
    assert les == sorted(les)                        # cumulative => monotone
    assert les[-1] == 5                              # +Inf == count
    assert "lat_seconds_count 5" in text
    assert 'le="+Inf"' in text


# ------------------------------------------------------------- phase spans
def test_span_counts_its_seconds_and_nests():
    reg = MetricsRegistry()
    sv = StatsView(reg)
    spans = PhaseSpans(reg)
    with spans("serve.round") as outer:
        with spans("serve.wait") as inner:
            time.sleep(0.01)
        with spans("serve.commit"):
            pass
    assert outer.t0 <= inner.t0 < inner.t1 <= outer.t1
    assert sv["device_wait"] == pytest.approx(inner.t1 - inner.t0)
    assert sv["device_wait"] >= 0.01
    assert sv["round_time"] == pytest.approx(outer.t1 - outer.t0)
    assert sv["round_time"] >= sv["device_wait"] + sv["commit_time"]
    # a span left by an exception still counts, and re-raises
    with pytest.raises(KeyError):
        with spans("loop.admit", request_id=7, slot=1):
            raise KeyError
    assert sv["admit_time"] > 0
    # every phase has its stats key; a bare span counts into its counter
    assert set(PHASES.values()) <= set(sv)
    c = reg.counter("other_seconds_total")
    with span("other.phase", c, slot=0) as s:
        pass
    assert c.value == pytest.approx(s.t1 - s.t0)


SPLIT_MODES = [
    ("chain_fused", {"round_mode": "split"}),
    ("tree_fused", {"round_mode": "split"}),
    ("cascade_fused", {}),
]


@pytest.mark.parametrize("mode,kw", SPLIT_MODES)
def test_dispatch_plus_wait_is_the_priced_interval(mode, kw):
    """A host-synced dispatch splits into serve.dispatch (call, argument
    transfer, enqueue) and serve.wait (block_until_ready, the device_wait
    counter); together they are the call-to-result interval DyTC's cost
    trackers observe, to within the few microseconds between the two."""
    srv = _server(mode, **kw)
    srv.add_request(0, PROMPT)
    srv.step()                                       # compiles
    priced = []
    observe, observe_target = srv.costs.observe, srv.costs.observe_target

    def obs(config, seconds, tokens=1):
        priced.append(seconds)
        observe(config, seconds, tokens=tokens)

    def obs_target(seconds, tokens=1):
        priced.append(seconds)
        observe_target(seconds, tokens=tokens)

    srv.costs.observe, srv.costs.observe_target = obs, obs_target
    st0 = srv.stats.copy()
    for _ in range(3):
        srv.step()
    d = {k: srv.stats[k] - st0[k] for k in st0}
    assert len(priced) == d["host_syncs"] >= 3     # every sync is priced
    split = d["device_wait"] + d["dispatch_time"]
    assert d["device_wait"] > 0 and d["dispatch_time"] > 0
    assert -1e-9 <= sum(priced) - split <= 1e-3 * len(priced)
    assert d["round_time"] >= split


@pytest.mark.parametrize("paged", [False, True])
def test_dense_admission_prefill_is_dispatch_then_wait(paged):
    """A dense admission's prefill runs as serve.dispatch then serve.wait
    (its first token is read back), so its device time is ``device_wait``
    and not host time; it is no round's sync, so ``host_syncs`` stays 0."""
    kw = {"paged": True, "page_size": 16} if paged else {}
    srv = _server("cascade_fused", **kw)
    srv.add_request(0, PROMPT)                       # compiles
    st0 = srv.stats.copy()
    spans = PhaseSpans(srv.metrics)
    with spans("loop.admit", request_id=1, slot=1):
        srv.add_request(1, PROMPT[::-1].copy())
    d = {k: srv.stats[k] - st0[k] for k in st0}
    assert d["device_wait"] > 0 and d["dispatch_time"] > 0
    assert d["admit_time"] >= d["device_wait"] + d["dispatch_time"]
    assert d["host_syncs"] == 0 and d["steps"] == 0


# --------------------------------------------------------------- exporters
def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("serve_rounds_total").inc(4)
    with MetricsHTTPServer(reg, port=0) as srv:
        assert srv.port > 0
        base = f"http://127.0.0.1:{srv.port}"
        assert srv.url == base + "/metrics"
        with urllib.request.urlopen(srv.url) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        assert "serve_rounds_total 4" in body
        with urllib.request.urlopen(base + "/metrics.json") as r:
            snap = json.loads(r.read().decode())
        assert snap["counters"]["serve_rounds_total"] == 4
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")


def test_jsonl_sink(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c").inc()
    sink_path = tmp_path / "metrics.jsonl"
    with JsonlSink(str(sink_path)) as sink:
        sink.write({"kind": "round", "n": 1})
        sink.write_registry(reg, step=2)
    lines = [json.loads(x) for x in sink_path.read_text().splitlines()]
    assert lines[0] == {"kind": "round", "n": 1}
    assert lines[1]["kind"] == "metrics_snapshot" and lines[1]["step"] == 2
    assert lines[1]["metrics"]["counters"]["c"] == 1


# -------------------------------------------------- end-to-end reconciliation
MODES = [
    ("chain_fused", {"round_mode": "single", "sync_every": 2}),
    ("chain_fused", {"round_mode": "split"}),
    ("tree_fused", {"round_mode": "single"}),
    ("legacy", {}),
    ("cascade_fused", {}),
]


@pytest.mark.parametrize("mode,kw", MODES)
def test_mode_accepted_matches_delivered(mode, kw):
    """Per-slot device/host telemetry tallies must equal the token stream
    the server actually returned — in every proposal mode."""
    srv = _server(mode, **kw)
    srv.add_request(0, PROMPT)
    toks = []
    for _ in range(5):
        toks += srv.step().get(0, [])
    toks += srv.flush().get(0, [])
    tot = srv.telemetry_totals()
    assert int(tot["accepted"][0]) == len(toks)
    assert int(tot["accepted"][1]) == 0              # empty slot stays silent
    assert int(tot["rounds"][0]) == 5
    assert int(tot["budget_hist"][0].sum()) == 5     # one budget pick / round
    summ = srv.metrics_summary()
    assert summ["mode"] == mode and summ["rounds"] == 5
    assert summ["accepted_per_slot"][0] == len(toks)
    if mode == "cascade_fused":
        # every level's routed/observed/accept rows are populated
        assert np.asarray(tot["casc_obs"]).sum() > 0
        acc = summ["cascade_acceptance"]
        assert len(acc) == len(srv.bank)
        assert all(a is None or 0.0 <= a <= 1.0 for a in acc)


@pytest.mark.parametrize("mode,kw", [
    ("cascade_fused", {}),
    ("tree_fused", {"round_mode": "single"}),
])
def test_registry_series_are_slot_sums_of_telemetry_totals(mode, kw):
    """``fold_telemetry`` folds each drained per-slot delta into registry
    series summed over slots: unlabelled totals, the budget histogram by
    ``budget`` and the cascade tallies by ``level``, each equal to the
    matching sum of the per-slot ``telemetry_totals()`` arrays."""
    srv = _server(mode, **kw)
    srv.add_request(0, PROMPT)
    srv.add_request(1, PROMPT[::-1].copy())
    for _ in range(5):
        srv.step()
    srv.flush()
    tot = srv.telemetry_totals()
    got = srv.metrics.snapshot()["counters"]
    assert (tot["accepted"] > 0).all()               # both slots served
    for key in ("rounds", "accepted", "drafted", "pld_tokens",
                "pld_hit_rounds"):
        name = {"rounds": "serve_slot_rounds_total",
                "accepted": "serve_slot_accepted_tokens_total",
                "drafted": "serve_slot_drafted_tokens_total",
                "pld_tokens": "serve_slot_pld_tokens_total",
                "pld_hit_rounds": "serve_slot_pld_hit_rounds_total"}[key]
        assert got.get(name, 0) == int(tot[key].sum()), key
    hist = tot["budget_hist"].sum(axis=0)            # (B, K+1) -> by budget
    assert hist.sum() == tot["rounds"].sum() > 0
    for j, v in enumerate(hist.tolist()):
        assert got.get(f'serve_draft_budget_rounds_total{{budget="{j}"}}',
                       0) == v, j
    casc = [("casc_routed", "serve_cascade_routed_rounds_total"),
            ("casc_obs", "serve_cascade_obs_total"),
            ("casc_accept", "serve_cascade_accept_total")]
    if mode != "cascade_fused":
        assert not any(n.startswith(s) for _, s in casc for n in got)
        return
    assert np.asarray(tot["casc_obs"]).sum() > 0
    for key, name in casc:
        per_level = np.asarray(tot[key]).sum(axis=1)     # (L, B) -> by level
        for lvl, v in enumerate(per_level.tolist()):
            assert got.get(f'{name}{{level="{lvl}"}}', 0) == v, (key, lvl)
    assert not any("slot=" in n for n in got)        # no per-slot series


@pytest.mark.parametrize("mode,kw", MODES)
def test_telemetry_onoff_runtime_parity(mode, kw):
    """Runtime side of the transparency contract: telemetry on vs off must
    produce identical round_dispatches/host_syncs AND identical tokens."""
    runs = {}
    for telem in (True, False):
        srv = _server(mode, telemetry=telem, **kw)
        srv.add_request(0, PROMPT)
        toks = []
        for _ in range(4):
            toks += srv.step().get(0, [])
        toks += srv.flush().get(0, [])
        runs[telem] = (toks, srv.stats["round_dispatches"],
                       srv.stats["host_syncs"], srv.stats["target_calls"])
    assert runs[True] == runs[False]


def test_serve_loop_overshoot_reconciliation():
    """The pipelined loop: device-tallied accepted tokens reconcile exactly
    with delivered + trimmed overshoot + unrouted + discarded + leftover,
    and trimmed tokens never inflate per-request counts or TPOT."""
    srv = _server("chain_fused", round_mode="single", sync_every=3,
                  max_len=96)
    sched = RequestScheduler(2)
    loop = ServeLoop(srv, sched)
    for i in range(4):
        sched.submit(Request(prompt=np.arange(1, 7 + i, dtype=np.int32),
                             max_new_tokens=9))
    reqs = loop.run(max_steps=200)
    assert len(reqs) == 4
    assert all(len(r.generated) == 9 for r in reqs)  # trimmed to the cap
    leftover = srv.flush()
    tot = srv.telemetry_totals()
    snap = srv.metrics.snapshot()["counters"]
    delivered = sum(len(r.generated) for r in reqs)
    accounted = (delivered
                 + snap.get("serve_overshoot_tokens_total", 0)
                 + snap.get("serve_unrouted_tokens_total", 0)
                 + snap.get("serve_discarded_tokens_total", 0)
                 + sum(len(v) for v in leftover.values()))
    assert int(tot["accepted"].sum()) == accounted
    # overshoot is excluded from the delivered-token counter ...
    assert snap["serve_request_tokens_total"] == delivered == 4 * 9
    # ... and from TPOT: any finite tpot stays consistent with delivered-1
    for r in reqs:
        assert r.ttft is not None and r.ttft >= 0
        if r.tpot is not None:
            assert r.tpot >= 0
    # loop-phase seconds + occupancy gauges came out of the same run: the
    # step holds its phases
    st = srv.stats
    for key in ("loop_time", "loop_drain_time", "admit_time", "route_time",
                "retire_time", "round_time", "dispatch_time", "device_wait",
                "drain_time"):
        assert st[key] > 0, key
    assert st["loop_time"] >= (st["loop_drain_time"] + st["admit_time"]
                               + st["round_time"] + st["route_time"]
                               + st["retire_time"])
    # every dispatch runs inside a round or, for a dense admission's
    # prefill, inside loop.admit
    assert st["round_time"] + st["admit_time"] >= st["dispatch_time"]
    gauges = srv.metrics.snapshot()["gauges"]
    assert gauges["serve_queue_depth"] == 0
    assert gauges["serve_slots_occupied"] == 0


def test_discarded_tokens_counted_on_slot_rebind():
    srv = _server("chain_fused", round_mode="single", sync_every=1)
    srv.add_request(0, PROMPT)
    srv.step()
    srv.flush()
    pend = srv._out_buf.get(0, [])
    srv.add_request(0, PROMPT)                       # rebind with buf pending
    snap = srv.metrics.snapshot()["counters"]
    assert snap.get("serve_discarded_tokens_total", 0) == len(pend)


# --------------------------------------------- optional property-based sweep
def test_histogram_random_observations_are_conserved():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, strategies as st

    edges = Histogram.log_edges(1e-3, 8.0)

    @given(st.lists(st.floats(min_value=0, max_value=32.0,
                              allow_nan=False), max_size=64))
    def check(vals):
        h = Histogram(list(edges))
        for v in vals:
            h.observe(v)
        assert sum(h.counts) == h.count == len(vals)
        for v in vals:
            i = h.bucket_index(v)
            assert (i == 0 or edges[i - 1] <= v)
            assert (i == len(edges) or v < edges[i])

    check()
