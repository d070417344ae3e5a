"""The measured window: the load generator and the serving loop in one
process, on the wall clock.

Between loop steps the generator submits every request whose due time has
passed, stamping ``submitted_at`` with the due time (so TTFT counts the
wait a stall imposes on later requests) and recording how late it ran.
The loop is ``ServeLoop.step_once``, as served. Nothing else runs in the
window; all bookkeeping is host timestamps read afterwards.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional

from harness import system


@dataclasses.dataclass
class Record:
    offered: object              # traffic.Offered
    request: object              # repro Request (arrivals, generated, done)
    due: float                   # perf_counter time it was due
    submitted: float             # perf_counter time the generator sent it
    admitted: Optional[float] = None   # start of the step that admitted it


@dataclasses.dataclass
class Window:
    t0: float
    t_end: float
    seconds: float
    records: List[Record]
    steps: int
    step_s: List[float]          # wall time of each loop step


def _span(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def run(sched, loop, offered: list, seconds: float,
        trace: bool = False) -> Window:
    pending = deque(offered)
    records: Dict[int, Record] = {}
    queued: List[Record] = []
    step_s: List[float] = []
    steps = 0
    t0 = time.perf_counter()
    t_end = t0 + seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if pending and t0 + pending[0].due_s <= now:
            with _span(trace, "bench.submit"):
                while pending and t0 + pending[0].due_s <= now:
                    o = pending.popleft()
                    req = system.request(o.prompt, o.max_new_tokens)
                    req.submitted_at = t0 + o.due_s
                    rec = Record(o, req, t0 + o.due_s, time.perf_counter())
                    records[req.request_id] = rec
                    queued.append(rec)
                    sched.submit(req)
        if sched.busy:
            t_step = time.perf_counter()
            with _span(trace, "bench.step"):
                loop.step_once()
            step_s.append(time.perf_counter() - t_step)
            steps += 1
            if queued:
                waiting = {id(r) for r in sched.queue}
                for rec in queued:
                    if id(rec.request) not in waiting:
                        rec.admitted = t_step
                queued = [r for r in queued if r.admitted is None]
        elif pending:
            with _span(trace, "bench.wait"):
                time.sleep(max(min(t0 + pending[0].due_s, t_end)
                               - time.perf_counter(), 0.0))
        else:
            with _span(trace, "bench.wait"):
                time.sleep(max(t_end - time.perf_counter(), 0.0))
    return Window(t0, t_end, seconds, list(records.values()), steps,
                  step_s)
