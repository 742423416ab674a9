"""The program's own spans and counters, for the readers of per-layer
metrics that the program measures from inside.

``repro.telemetry.snapshot()`` is the program's in-process table: per
span name ``count``, ``total_s``, ``self_s`` and ``max_s``, and counters.
One ``bench/run.py`` process runs one cell, so the table is that run's.
"""
from __future__ import annotations

from bench.lib import program


def snapshot() -> dict | None:
    """The program's table, or None where the program keeps none."""
    try:
        telemetry = program.repro().telemetry
    except AttributeError:
        return None
    return telemetry.snapshot()


def span_total_s(name: str) -> float | None:
    """Seconds spent in span ``name`` over the run; None where absent."""
    snap = snapshot()
    row = snap and snap["spans"].get(name)
    return row["total_s"] if row else None


def counter(name: str) -> float | None:
    """Counter ``name``; None where the program keeps no such counter."""
    snap = snapshot()
    return snap and snap["counters"].get(name)
