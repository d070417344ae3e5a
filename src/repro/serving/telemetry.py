"""Zero-sync serving telemetry: the host registry and the device buffer.

Two halves, one discipline (docs/observability.md):

Host half — ``MetricsRegistry``
    Counters, gauges and log-bucketed histograms with Prometheus text
    exposition (``render_prometheus``) and a JSON-able ``snapshot``. It
    absorbs the server's legacy ``stats`` dict through ``StatsView`` (a
    MutableMapping over registry counters keyed by the old names), so
    every existing ``srv.stats["round_dispatches"]``-style read keeps
    working while the same numbers become scrapeable. ``span`` times the
    host phases of the loop and the server (``PHASES``): each is a
    profiler trace event, on the device ops' clock, and a seconds counter.

Device half — the round telemetry buffer
    PRs 5–7 made the steady serving round ONE donated dispatch with ZERO
    host syncs between rounds, so per-round instrumentation must not read
    anything back. The buffer is a fixed-shape dict of small device arrays
    (per-slot accepted/drafted token counts, chosen draft budgets, PLD
    hits, per-(level, slot) cascade routing + acceptance tallies) that is
    carried and DONATED through the round executables exactly like the
    server's ``dstate`` — ``accumulate_round`` / ``accumulate_cascade``
    are pure jnp updates composed into the jitted round at the jit
    boundary, never a callback. The host reads the buffer only at the
    existing ``sync_every``/flush/admission drain points, where the
    blocked-on round outputs already guarantee the buffer is resolved, so
    ``round_dispatches`` and ``host_syncs`` stay bit-identical with
    telemetry on (tests/test_telemetry.py, tests/test_dispatch_contracts
    .py prove it at runtime AND on the compiled HLO).

Rounds that host-sync anyway (split / legacy, and the cascade's bounded
per-level dispatches) accumulate the SAME schema host-side from arrays
they already materialized — the device carry is reserved for exactly the
rounds that have no sync to piggyback on. ``merge_totals`` folds the two
halves into one cumulative view, drained as deltas into the registry.
"""
from __future__ import annotations

import bisect
import contextlib
import math
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "StatsView",
    "STATS_METRICS",
    "PHASES",
    "PhaseSpans",
    "span",
    "profiler_trace",
    "telemetry_schema",
    "init_device_telemetry",
    "init_host_telemetry",
    "accumulate_round",
    "accumulate_cascade",
    "merge_totals",
    "fold_telemetry",
]


# =========================================================== host registry
def _label_key(labels: Dict[str, Any]) -> Tuple[Tuple[str, str], ...]:
    return tuple(sorted((k, str(v)) for k, v in labels.items()))


def _render_labels(labels: Tuple[Tuple[str, str], ...]) -> str:
    if not labels:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + inner + "}"


class Counter:
    """A monotonically increasing value (fractional increments allowed:
    the phase ``*_time`` stats are second-counters)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, v: float = 1.0) -> None:
        self.value += v


class Gauge:
    """A point-in-time value (queue depth, slot occupancy)."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)


class Histogram:
    """A log-bucketed histogram with left-closed buckets.

    ``edges`` are the finite bucket boundaries; observations land in
    ``(-inf, e0), [e0, e1), ..., [e_{n-1}, +inf)`` via ``bisect_right`` on
    the precomputed edge list — no float ``log`` at observe time, so a
    value exactly equal to an edge deterministically lands in the bucket
    the edge OPENS (never lost, never double-counted; pinned by the
    property test in tests/test_telemetry.py). Prometheus exposition
    renders the standard cumulative ``le`` form.
    """

    __slots__ = ("edges", "counts", "sum", "count")

    def __init__(self, edges: List[float]) -> None:
        if sorted(edges) != list(edges) or len(set(edges)) != len(edges):
            raise ValueError("histogram edges must be strictly increasing")
        self.edges = list(edges)
        self.counts = [0] * (len(edges) + 1)
        self.sum = 0.0
        self.count = 0

    @staticmethod
    def log_edges(lo: float, hi: float, base: float = 2.0) -> List[float]:
        """Geometric bucket edges ``lo, lo*base, ...`` up to (and
        including the first edge >=) ``hi``."""
        if lo <= 0 or base <= 1 or hi <= lo:
            raise ValueError("need 0 < lo < hi and base > 1")
        edges, e = [], lo
        # ~ceil(log_base(hi/lo)) + 1 edges, built multiplicatively so the
        # edge values are stable products (no log/pow roundtrip)
        for _ in range(int(math.log(hi / lo, base)) + 2):
            edges.append(e)
            if e >= hi:
                break
            e *= base
        return edges

    def bucket_index(self, v: float) -> int:
        return bisect.bisect_right(self.edges, v)

    def observe(self, v: float) -> None:
        self.counts[self.bucket_index(v)] += 1
        self.sum += v
        self.count += 1


# legacy BatchedSpecServer.stats key -> registry counter name. StatsView
# keeps every existing stats read/mutation working against the registry.
# The seconds keys are the host phases' counters (``PHASES``): the span of
# each phase adds to them, nothing else does.
STATS_METRICS: Dict[str, str] = {
    "steps": "serve_rounds_total",
    "tokens": "serve_tokens_total",
    "target_calls": "serve_target_calls_total",
    "draft_dispatches": "serve_draft_dispatches_total",
    "drafted_tokens": "serve_drafted_tokens_total",
    "rescore_dispatches": "serve_rescore_dispatches_total",
    "round_dispatches": "serve_round_dispatches_total",
    "host_syncs": "serve_host_syncs_total",
    "loop_time": "serve_loop_seconds_total",
    "loop_drain_time": "serve_loop_drain_seconds_total",
    "admit_time": "serve_admit_seconds_total",
    "route_time": "serve_route_seconds_total",
    "retire_time": "serve_retire_seconds_total",
    "round_time": "serve_round_seconds_total",
    "plan_time": "serve_plan_seconds_total",
    "dispatch_time": "serve_dispatch_seconds_total",
    "device_wait": "serve_device_wait_seconds_total",
    "commit_time": "serve_commit_seconds_total",
    "drain_time": "serve_drain_seconds_total",
}

# integer-semantics stats keys: reads come back as int so existing
# ``== 8``-style pins and dict reprs stay exact
_INT_STATS = {
    "steps", "tokens", "target_calls", "draft_dispatches", "drafted_tokens",
    "rescore_dispatches", "round_dispatches", "host_syncs",
}

_LATENCY_EDGES = Histogram.log_edges(1e-4, 512.0)   # 100us .. ~512s


class MetricsRegistry:
    """Counters + gauges + histograms, keyed by (name, labels).

    One registry per server; exporters (``serving.exporters``) render it
    as Prometheus text or JSONL snapshots. Creation is get-or-create so
    hot paths just call ``registry.counter(...).inc()``."""

    def __init__(self) -> None:
        self._counters: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Counter] = {}
        self._gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Gauge] = {}
        self._hists: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], Histogram] = {}

    # ------------------------------------------------------------- factories
    def counter(self, name: str, **labels: Any) -> Counter:
        key = (name, _label_key(labels))
        c = self._counters.get(key)
        if c is None:
            c = self._counters[key] = Counter()
        return c

    def gauge(self, name: str, **labels: Any) -> Gauge:
        key = (name, _label_key(labels))
        g = self._gauges.get(key)
        if g is None:
            g = self._gauges[key] = Gauge()
        return g

    def histogram(
        self, name: str, edges: Optional[List[float]] = None, **labels: Any
    ) -> Histogram:
        key = (name, _label_key(labels))
        h = self._hists.get(key)
        if h is None:
            h = self._hists[key] = Histogram(
                list(_LATENCY_EDGES) if edges is None else edges
            )
        return h

    # --------------------------------------------------------------- export
    def render_prometheus(self) -> str:
        """Prometheus text exposition (format 0.0.4), stable ordering."""
        lines: List[str] = []
        typed: set = set()

        def _head(name: str, kind: str) -> None:
            if name not in typed:
                typed.add(name)
                lines.append(f"# TYPE {name} {kind}")

        for (name, labels), c in sorted(self._counters.items()):
            _head(name, "counter")
            lines.append(f"{name}{_render_labels(labels)} {_num(c.value)}")
        for (name, labels), g in sorted(self._gauges.items()):
            _head(name, "gauge")
            lines.append(f"{name}{_render_labels(labels)} {_num(g.value)}")
        for (name, labels), h in sorted(self._hists.items()):
            _head(name, "histogram")
            # prometheus 'le' buckets are right-closed cumulative; our raw
            # buckets are left-closed — le=edges[i] accumulates every raw
            # bucket strictly below edge i (counts[0..i]), and since a
            # sample exactly ON an edge lands in the bucket the edge opens,
            # it is excluded from that le and included in the next: the
            # exposition stays a valid monotone cumulative either way
            for i, e in enumerate(h.edges):
                lines.append(
                    f"{name}_bucket{_merge_le(labels, e)} {sum(h.counts[: i + 1])}"
                )
            lines.append(f'{name}_bucket{_merge_le(labels, "+Inf")} {h.count}')
            lines.append(f"{name}_sum{_render_labels(labels)} {_num(h.sum)}")
            lines.append(f"{name}_count{_render_labels(labels)} {h.count}")
        return "\n".join(lines) + "\n"

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able snapshot: rendered-name -> value/summary."""

        def nm(name: str, labels: Tuple[Tuple[str, str], ...]) -> str:
            return name + _render_labels(labels)

        return {
            "counters": {
                nm(n, la): c.value for (n, la), c in sorted(self._counters.items())
            },
            "gauges": {
                nm(n, la): g.value for (n, la), g in sorted(self._gauges.items())
            },
            "histograms": {
                nm(n, la): {
                    "edges": h.edges, "counts": h.counts,
                    "sum": h.sum, "count": h.count,
                }
                for (n, la), h in sorted(self._hists.items())
            },
        }


def _num(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _merge_le(labels: Tuple[Tuple[str, str], ...], le: Any) -> str:
    return _render_labels(tuple(sorted(labels + (("le", str(le)),))))


class StatsView:
    """MutableMapping facade: the legacy ``server.stats`` dict, backed by
    registry counters (``STATS_METRICS``). Reads, ``+=`` mutations, and
    dict-style iteration all operate on the live registry, so the stats
    the tests pin and the /metrics endpoint exports cannot drift apart."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self._registry = registry
        for name in STATS_METRICS.values():
            registry.counter(name)          # materialize at zero

    def __getitem__(self, key: str):
        v = self._registry.counter(STATS_METRICS[key]).value
        return int(v) if key in _INT_STATS else v

    def __setitem__(self, key: str, value: float) -> None:
        self._registry.counter(STATS_METRICS[key]).value = float(value)

    def __contains__(self, key: str) -> bool:
        return key in STATS_METRICS

    def __iter__(self) -> Iterator[str]:
        return iter(STATS_METRICS)

    def __len__(self) -> int:
        return len(STATS_METRICS)

    def get(self, key: str, default=None):
        return self[key] if key in STATS_METRICS else default

    def items(self):
        return [(k, self[k]) for k in STATS_METRICS]

    def copy(self) -> Dict[str, float]:
        return {k: self[k] for k in STATS_METRICS}

    def __repr__(self) -> str:
        return f"StatsView({self.copy()!r})"


# ============================================================ host spans
# span name -> the ``stats`` key of the phase counter its seconds add to
# (docs/observability.md has the tree). The spans nest: loop.step holds
# the loop.* phases and serve.round; serve.round holds the serve.* ones.
PHASES: Dict[str, str] = {
    "loop.step": "loop_time",
    "loop.drain": "loop_drain_time",
    "loop.admit": "admit_time",
    "loop.route": "route_time",
    "loop.retire": "retire_time",
    "serve.round": "round_time",
    "serve.plan": "plan_time",
    "serve.dispatch": "dispatch_time",
    "serve.wait": "device_wait",
    "serve.commit": "commit_time",
    "serve.drain": "drain_time",
}


class span:
    """``with span("serve.wait", counter): ...`` — one host phase.

    Opens a ``jax.profiler.TraceAnnotation(name, **args)``, so the span
    lands in the profiler's own trace on the clock of the device ops (the
    annotation costs about a microsecond when no trace is being
    captured), and on exit adds its wall seconds to ``counter`` (a
    pre-resolved registry ``Counter``; a lookup per call would cost more
    than the span).
    ``t0``/``t1`` are the ``perf_counter`` readings at entry and exit."""

    __slots__ = ("_ann", "_counter", "t0", "t1")

    def __init__(self, name: str, counter: Counter, **args: Any) -> None:
        self._ann = TraceAnnotation(name, **args)
        self._counter = counter

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        self.t1 = time.perf_counter()
        self._counter.value += self.t1 - self.t0
        self._ann.__exit__(*exc)


class PhaseSpans:
    """The ``PHASES`` spans bound to their counters in one registry:
    ``spans("serve.wait")`` is ``span("serve.wait", <its counter>)``."""

    def __init__(self, registry: MetricsRegistry) -> None:
        self._counters = {
            name: registry.counter(STATS_METRICS[key])
            for name, key in PHASES.items()
        }

    def __call__(self, name: str, **args: Any) -> span:
        return span(name, self._counters[name], **args)


@contextlib.contextmanager
def profiler_trace(log_dir: Optional[str]) -> Iterator[None]:
    """Optional ``jax.profiler.trace`` hook: profiles the wrapped region
    into ``log_dir`` when a directory is given (the XPlane file, and a
    Perfetto trace that shows the host spans above beside the device
    ops); a no-op otherwise."""
    if not log_dir:
        yield
        return
    import jax

    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        yield


# ====================================================== device telemetry
def telemetry_schema(
    batch: int, budget_max: int, levels: int = 0
) -> Dict[str, Tuple[Tuple[int, ...], Any]]:
    """The fixed-shape buffer layout (docs/observability.md), shared by
    the device buffer and its host-side numpy twin:

      rounds          (B,)            live rounds per slot
      accepted        (B,)            committed tokens per slot (n_acc sums)
      drafted         (B,)            NEURAL drafted tokens per slot
      pld_tokens      (B,)            PLD-proposed tokens per slot
      pld_hit_rounds  (B,)            rounds with >= 1 PLD proposal
      budget_hist     (B, budget_max+1)  chosen draft budget / tree
                                      expansion count histogram (column j =
                                      rounds the Eq. 5 routing picked j)
      casc_routed     (L, B)          rounds level l participated in
      casc_obs        (L, B)          Eq. 4 observations of level l's first
                                      token (row 0 = target judging the
                                      strongest level — the bank's
                                      slot_key(l) tally)
      casc_accept     (L, B)          ... of which accepted

    Every array leads with the batch (or (level, batch)) dim and is i32 —
    small, fixed-shape, donation-friendly. Cascade rows exist only for
    cascade servers (``levels > 0``)."""
    B, K = batch, budget_max
    schema: Dict[str, Tuple[Tuple[int, ...], Any]] = {
        "rounds": ((B,), np.int32),
        "accepted": ((B,), np.int32),
        "drafted": ((B,), np.int32),
        "pld_tokens": ((B,), np.int32),
        "pld_hit_rounds": ((B,), np.int32),
        "budget_hist": ((B, K + 1), np.int32),
    }
    if levels:
        schema["casc_routed"] = ((levels, B), np.int32)
        schema["casc_obs"] = ((levels, B), np.int32)
        schema["casc_accept"] = ((levels, B), np.int32)
    return schema


def init_device_telemetry(schema: Dict[str, Tuple[Tuple[int, ...], Any]]):
    """Fresh all-zero device buffer (a dict of jnp arrays, ready to be
    carried + donated through the round executables)."""
    import jax.numpy as jnp

    return {k: jnp.zeros(shape, dtype) for k, (shape, dtype) in schema.items()}


def init_host_telemetry(
    schema: Dict[str, Tuple[Tuple[int, ...], Any]]
) -> Dict[str, np.ndarray]:
    """The numpy twin, accumulated by rounds that host-sync anyway."""
    return {k: np.zeros(shape, dtype) for k, (shape, dtype) in schema.items()}


def accumulate_round(telem: dict, out: dict, live) -> dict:
    """Pure-jnp buffer update for one fused chain/tree round — composed
    into the SAME jitted executable as the round (the server wraps
    ``chain_round``/``tree_round`` with this at the jit boundary), so the
    round stays one dispatch and the buffer rides the donation.

    ``out`` is the round's output dict (``acc``/``n_acc`` plus the
    per-slot ``drafted``/``pld_have``/``budget`` diagnostics the engine
    exposes for exactly this purpose); dead slots contribute zeros by the
    engine's masking."""
    import jax.numpy as jnp

    t = dict(telem)
    li = live.astype(jnp.int32)
    B, K1 = t["budget_hist"].shape
    t["rounds"] = t["rounds"] + li
    t["accepted"] = t["accepted"] + out["n_acc"].astype(jnp.int32)
    t["drafted"] = t["drafted"] + out["drafted"].astype(jnp.int32)
    t["pld_tokens"] = t["pld_tokens"] + out["pld_have"].astype(jnp.int32)
    t["pld_hit_rounds"] = t["pld_hit_rounds"] + (
        (out["pld_have"] > 0) & live
    ).astype(jnp.int32)
    # one-hot broadcast rather than a scatter-add: scatters can lower to a
    # per-update loop, which would add a scan the transparency contract
    # (assert_telemetry_transparent) forbids
    col = jnp.clip(out["budget"], 0, K1 - 1)
    hit = (col[:, None] == jnp.arange(K1)[None, :]).astype(jnp.int32)
    t["budget_hist"] = t["budget_hist"] + hit * li[:, None]
    return t


def accumulate_cascade(
    telem: dict,
    *,
    live,
    n_acc,
    count,
    pld_have,
    budget,
    routed,
    probe_ok,
    probe_valid,
    rescorer_rows: Tuple[int, ...],
    drafter_row: int,
    obs_row: int,
) -> dict:
    """Pure-jnp buffer update composed into the cascade's LAST rescore
    dispatch (``cascade_rescore_verify`` — the one that also carries the
    folded target verify). The cascade round is bounded at L dispatches
    with a host sync per dispatch, but the buffer still rides the donated
    final dispatch so every mode drains through one schema.

    Row bookkeeping (see ``DraftBank``): ``rescorer_rows`` are the level
    indices that rescored this round (they share one routing decision),
    ``drafter_row`` participates whenever a neural budget was granted, and
    ``obs_row`` is the level whose first token THIS dispatch judged (the
    strongest rescorer prices level ``obs_row = its index + 1``).
    Intermediate rescorers' verdicts and the target-facing row 0 are
    accumulated host-side by the server from the same arrays it already
    materializes for the Eq. 4 trackers."""
    import jax.numpy as jnp

    t = dict(telem)
    li = live.astype(jnp.int32)
    B, K1 = t["budget_hist"].shape
    t["rounds"] = t["rounds"] + li
    t["accepted"] = t["accepted"] + n_acc.astype(jnp.int32)
    t["drafted"] = t["drafted"] + jnp.clip(
        count.astype(jnp.int32) - pld_have.astype(jnp.int32) - 1, 0, None
    ) * li
    t["pld_tokens"] = t["pld_tokens"] + pld_have.astype(jnp.int32) * li
    t["pld_hit_rounds"] = t["pld_hit_rounds"] + (
        (pld_have > 0) & live
    ).astype(jnp.int32)
    col = jnp.clip(budget, 0, K1 - 1)   # one-hot add, not scatter (no scan)
    hit = (col[:, None] == jnp.arange(K1)[None, :]).astype(jnp.int32)
    t["budget_hist"] = t["budget_hist"] + hit * li[:, None]
    routed_i = (routed & live).astype(jnp.int32)
    cr = t["casc_routed"]
    for r in rescorer_rows:
        cr = cr.at[r].add(routed_i)
    cr = cr.at[drafter_row].add(((budget > 0) & live).astype(jnp.int32))
    t["casc_routed"] = cr
    pv = probe_valid.astype(jnp.int32)
    t["casc_obs"] = t["casc_obs"].at[obs_row].add(pv)
    t["casc_accept"] = t["casc_accept"].at[obs_row].add(
        (probe_valid & probe_ok).astype(jnp.int32)
    )
    return t


def merge_totals(
    device: Optional[dict], host: Dict[str, np.ndarray]
) -> Dict[str, np.ndarray]:
    """Cumulative totals = resolved device buffer + host twin. Call only
    at a drain point: the server guarantees the device buffer belongs to
    an already-completed round there, so reading it is a plain D2H copy,
    not a new sync point."""
    out = {k: v.copy() for k, v in host.items()}
    if device is not None:
        for k, v in device.items():
            out[k] = out[k] + np.asarray(v)
    return out


def fold_telemetry(
    registry: MetricsRegistry,
    delta: Dict[str, np.ndarray],
    prefix: str = "serve",
) -> None:
    """Fold a drained per-slot delta into registry counters, summed over
    slots: unlabelled totals, the budget histogram by ``budget``, and the
    cascade tallies by ``level``. The per-slot arrays stay readable
    through ``telemetry_totals``."""
    totals = {
        "rounds": f"{prefix}_slot_rounds_total",
        "accepted": f"{prefix}_slot_accepted_tokens_total",
        "drafted": f"{prefix}_slot_drafted_tokens_total",
        "pld_tokens": f"{prefix}_slot_pld_tokens_total",
        "pld_hit_rounds": f"{prefix}_slot_pld_hit_rounds_total",
    }
    for key, name in totals.items():
        arr = delta.get(key)
        if arr is not None and arr.any():
            registry.counter(name).inc(int(arr.sum()))
    bh = delta.get("budget_hist")
    if bh is not None:
        for j, v in enumerate(bh.sum(axis=0).tolist()):
            if v:
                registry.counter(
                    f"{prefix}_draft_budget_rounds_total", budget=j
                ).inc(v)
    per_level = {
        "casc_routed": f"{prefix}_cascade_routed_rounds_total",
        "casc_obs": f"{prefix}_cascade_obs_total",
        "casc_accept": f"{prefix}_cascade_accept_total",
    }
    for key, name in per_level.items():
        arr = delta.get(key)
        if arr is None:
            continue
        for lvl, v in enumerate(arr.sum(axis=1).tolist()):
            if v:
                registry.counter(name, level=lvl).inc(v)
