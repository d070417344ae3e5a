"""What a metric reader sees, and the loop over a cell's metrics.

Every metric, end-to-end or per-layer, is read by ``metrics/<name>.py``:
``read(run) -> float | None``. ``None`` means the reader found nothing to
read, and the metric is left out of the result line. ``run`` is a
``RunData``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from harness import registry, trace


@dataclasses.dataclass
class RunData:
    c: dict                      # configuration file
    mix: dict                    # traffic mix file
    cell: dict                   # BENCHMARK.json workload entry
    win: object                  # window.Window
    e2e: dict                    # measure.end_to_end
    stats: Dict[str, float]      # server counters, window delta
    tel: Dict[str, object]       # device telemetry totals, window delta
    device: dict
    flops: float                 # target-forward operations delivered
    setup_s: float = 0.0
    trace: Optional[trace.Reduced] = None

    def peaks(self) -> dict:
        table = registry.load_json_file("peaks.json")
        kind = self.device["kind"]
        if kind not in table["devices"]:
            raise KeyError(f"device {kind!r} is not in bench/peaks.json")
        return table["devices"][kind]


def kernel_modules() -> dict:
    return {n: registry.load_module("kernels", n)
            for n in registry.names("kernels", ".py")}


def reduce_trace(log_dir: str) -> trace.Reduced:
    path = trace.latest_xplane(log_dir)
    if path is None:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    names = [m.TRACE_NAME for m in kernel_modules().values()]
    table = registry.load_json_file("programs.json")
    return trace.reduce(trace.load(path, names), names, table)


def _read(names: List[dict], run: RunData) -> dict:
    out = {}
    for m in names:
        v = registry.load_module("metrics", m["name"]).read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def end_to_end(spec: dict, cell: str, run: RunData, setup_s: float) -> dict:
    run.setup_s = setup_s
    return _read(registry.metrics_for(spec, "end_to_end", cell), run)


def per_layer(spec: dict, cell: str, run: RunData) -> dict:
    return _read(registry.metrics_for(spec, "per_layer", cell), run)


def breakdown(red: trace.Reduced, top: int = 10) -> dict:
    """Device seconds by jitted program and by kernel (not by HLO op
    category), and idle seconds by what the host was doing."""
    rows = dict(red.programs)
    for name, evs in red.kernels.items():
        if evs:
            rows[f"kernel:{name}"] = sum(e.dur for e in evs)
    idle: Dict[str, float] = {}
    for span, s in red.gaps:
        idle[span] = idle.get(span, 0.0) + s
    return {
        "device_ops": sorted(([k, v] for k, v in rows.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }
