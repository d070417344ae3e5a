"""Tokens committed per live slot-round in the window (device telemetry:
accepted / rounds, summed over slots)."""


def read(run):
    rounds = float(run.tel["rounds"].sum())
    if not rounds:
        return None
    return float(run.tel["accepted"].sum()) / rounds
