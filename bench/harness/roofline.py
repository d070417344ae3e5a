"""A kernel's share of its roofline over the traced window: the least
time the chip could take for every call (the larger of operations over
the peak rate and bytes over the memory bandwidth, from each call's own
shapes) over the calls' summed device time."""
from __future__ import annotations

from harness import hlo, readers


def share(run, kernel_file: str):
    red = run.trace
    if red is None:
        return None
    mod = readers.kernel_modules()[kernel_file]
    events = red.kernels.get(mod.TRACE_NAME) or []
    spent = sum(e.dur for e in events)
    if not spent:
        return None
    peaks = run.peaks()
    bound = 0.0
    for e in events:
        ops, nbytes = mod.cost(*hlo.shapes(e.name))
        bound += max(ops / peaks[mod.PEAK], nbytes / peaks["hbm_bytes_s"])
    return 100.0 * bound / spent
