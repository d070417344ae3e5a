"""Device seconds under the named scopes of the jitted round programs.

The program marks the stages of its fused round with ``jax.named_scope``
(``prefill``, ``pld``, ``draft``, ``verify``, ``commit``). A scope changes
no module or instruction name; it reaches the trace only as the scope path
in a stat of each ``XLA Ops`` op (``SCOPE_STAT``, for example
``jit(round_core)/jit(main)/draft/cond/branch_1_fun/while/body/...``),
kept on the op's event metadata, which ``jax.profiler.ProfileData`` does
not expose. So this module reads the ``.xplane.pb`` with a message class
for the part of the XPlane schema it needs (tensorflow/tsl
``profiler/protobuf/xplane.proto``; the field numbers are that file's).

``seconds`` reads the newest trace under the benchmark's trace directory,
where ``run.py`` writes it, in one pass over the ``XLA Ops`` lines of the
device planes. A stage's seconds are the union of the intervals of its
ops inside the ``bench.window`` span (ops nest: a loop's event holds its
body's), summed over chips and divided by their number, as
``trace.reduce`` counts ``busy_s``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

from harness import registry, trace

TRACE_DIR = registry.BENCH_DIR / ".work" / "trace"
SCOPE_STAT = "tf_op"
STAGES = ("prefill", "pld", "draft", "verify", "commit")

# (message, [(field, number, type, repeated, message type)]); types are
# FieldDescriptorProto's: 3 int64, 4 uint64, 1 double, 9 string, 12 bytes,
# 11 message. XStat's values form its "value" oneof.
_SCHEMA = [
    ("XSpace", [("planes", 1, 11, True, "XPlane")]),
    ("XPlane", [("id", 1, 3, False, None), ("name", 2, 9, False, None),
                ("lines", 3, 11, True, "XLine"),
                ("event_metadata", 4, 11, True, "EventMetadataEntry"),
                ("stat_metadata", 5, 11, True, "StatMetadataEntry")]),
    ("EventMetadataEntry", [("key", 1, 3, False, None),
                            ("value", 2, 11, False, "XEventMetadata")]),
    ("StatMetadataEntry", [("key", 1, 3, False, None),
                           ("value", 2, 11, False, "XStatMetadata")]),
    ("XLine", [("id", 1, 3, False, None), ("name", 2, 9, False, None),
               ("timestamp_ns", 3, 3, False, None),
               ("events", 4, 11, True, "XEvent")]),
    ("XEvent", [("metadata_id", 1, 3, False, None),
                ("offset_ps", 2, 3, False, None),
                ("duration_ps", 3, 3, False, None),
                ("stats", 4, 11, True, "XStat")]),
    ("XStat", [("metadata_id", 1, 3, False, None),
               ("double_value", 2, 1, False, None),
               ("uint64_value", 3, 4, False, None),
               ("int64_value", 4, 3, False, None),
               ("str_value", 5, 9, False, None),
               ("bytes_value", 6, 12, False, None),
               ("ref_value", 7, 4, False, None)]),
    ("XEventMetadata", [("id", 1, 3, False, None), ("name", 2, 9, False, None),
                        ("stats", 5, 11, True, "XStat")]),
    ("XStatMetadata", [("id", 1, 3, False, None), ("name", 2, 9, False, None)]),
]


@functools.lru_cache(maxsize=1)
def xspace_class():
    from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA:
        m = fd.message_type.add(name=msg)
        if msg == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, ftype, repeated, mtype in fields:
            f = m.field.add(name=name, number=number, type=ftype,
                            label=3 if repeated else 1)
            if mtype:
                f.type_name = f".bench_xplane.{mtype}"
            if msg == "XStat" and number > 1:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def read_xspace(path: str):
    space = xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    return space


def stat_value(stat, names: Dict[int, str]):
    """A stat's value; a ``ref_value`` names another stat metadata."""
    kind = stat.WhichOneof("value")
    if kind == "ref_value":
        return names.get(stat.ref_value)
    return getattr(stat, kind) if kind else None


def seconds(stage: str, log_dir=None) -> Optional[float]:
    """Device seconds per chip under ``stage`` in the window, or None where
    there is no trace or no op carries the stage's scope."""
    path = trace.latest_xplane(str(log_dir or TRACE_DIR))
    if path is None:
        return None
    return by_stage(path).get(stage)


@functools.lru_cache(maxsize=2)
def by_stage(path: str) -> Dict[str, float]:
    space = read_xspace(path)
    window = None
    chips: List[Dict[str, List[Tuple[float, float]]]] = []
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            chips.append(_stage_intervals(plane))
        elif window is None:
            window = _span(plane, "bench.window")
    if window is None or not chips:
        return {}
    t0, t1 = window
    out: Dict[str, float] = {}
    for stages in chips:
        for stage, iv in stages.items():
            clipped = [(max(a, t0), min(b, t1)) for a, b in iv
                       if b > t0 and a < t1]
            out[stage] = out.get(stage, 0.0) + sum(
                b - a for a, b in trace._union(clipped))
    return {k: v / len(chips) for k, v in out.items()}


def _span(plane, name: str) -> Optional[Tuple[float, float]]:
    ids = {e.key for e in plane.event_metadata if e.value.name == name}
    for line in plane.lines:
        for e in line.events:
            if e.metadata_id in ids:
                t = line.timestamp_ns * 1e-9 + e.offset_ps * 1e-12
                return t, t + e.duration_ps * 1e-12
    return None


def _stage_intervals(plane) -> Dict[str, List[Tuple[float, float]]]:
    names = {s.key: s.value.name for s in plane.stat_metadata}
    want = {k for k, n in names.items() if n == SCOPE_STAT}
    stage_of: Dict[int, Optional[str]] = {}
    for entry in plane.event_metadata:
        path = next((stat_value(s, names) for s in entry.value.stats
                     if s.metadata_id in want), None)
        stage_of[entry.key] = _stage(path)
    out: Dict[str, List[Tuple[float, float]]] = {}
    for line in plane.lines:
        if line.name not in trace.OP_LINES:
            continue
        base = line.timestamp_ns * 1e-9
        for e in line.events:
            stage = stage_of.get(e.metadata_id)
            if stage is not None:
                t = base + e.offset_ps * 1e-12
                out.setdefault(stage, []).append(
                    (t, t + e.duration_ps * 1e-12))
    return out


def _stage(path) -> Optional[str]:
    """The outermost stage scope in an op's scope path, or None."""
    if not isinstance(path, str):
        return None
    for part in path.split("/"):
        if part in STAGES:
            return part
    return None
