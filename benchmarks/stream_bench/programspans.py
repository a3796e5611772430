"""The program's own spans and counters (``repro.telemetry``) inside a
run's measured window, for the per-layer readers in ``metrics/``.

A span belongs to the window when it starts in
``[window.t_start, window.t_close]``; a counter event when it is stamped
at or after ``t_start`` (the program stamps a lazily flushed device
count with its dispatch's time). A recorder ring that dropped a record
inside the window makes the reading ``None``, and so does a program
without the recorder, so a reader leaves its metric out instead of
reporting a partial number or raising."""
from __future__ import annotations

from typing import List, Optional


def _telemetry():
    try:
        from repro import telemetry
    except ImportError:
        return None
    return telemetry


def _window_ns(rec):
    w = rec["window"]
    return int(w["t_start"] * 1e9), int(w["t_close"] * 1e9)


def spans(rec, name: str) -> Optional[List]:
    """The window's records of span ``name`` (possibly empty)."""
    telemetry = _telemetry()
    if telemetry is None:
        return None
    lo, hi = _window_ns(rec)
    records, dropped = telemetry.records(name, lo, hi)
    return None if dropped else records


def span_names(prefix: str) -> Optional[List[str]]:
    """Names of the spans recorded so far that start with ``prefix``."""
    telemetry = _telemetry()
    if telemetry is None:
        return None
    return [n for n in telemetry.names()[0] if n.startswith(prefix)]


def counter_total(rec, name: str) -> Optional[float]:
    """Sum of counter ``name``'s events stamped since the window opened."""
    telemetry = _telemetry()
    if telemetry is None:
        return None
    events, dropped = telemetry.records(name, _window_ns(rec)[0])
    return None if dropped else sum(e.n for e in events)


def total_ms(records) -> float:
    return sum(r.t1_ns - r.t0_ns for r in records) / 1e6


def mean_ms(records) -> Optional[float]:
    return total_ms(records) / len(records) if records else None
