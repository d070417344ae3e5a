"""Device time by phase of the served path (``programs.json``), from the
trace's programs keyed ``<phase>:<module>``."""
from __future__ import annotations


def seconds(red, phase: str) -> float:
    """Device seconds of one phase, per chip."""
    s = sum(v for k, v in red.programs.items()
            if k.split(":", 1)[0] == phase)
    return s / max(red.chips, 1)


def busy_share(run, phase: str):
    red = run.trace
    if red is None or not red.busy_s:
        return None
    s = seconds(red, phase)
    return 100.0 * s / red.busy_s if s else None
