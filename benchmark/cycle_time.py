"""Step times and counts from an ops window's cycles, for the readers in
``metrics/``. Only cycles that completed count; a query window has none,
and a reader over it returns nothing."""

from __future__ import annotations

from typing import Optional


def done(w) -> list:
    return [c for c in w.cycles if c.error is None]


def mean_ms(w, step: str) -> Optional[float]:
    """Mean time of the step called ``step``, in ms."""
    times = [b - a for c in done(w) for name, a, b in c.steps
             if name == step]
    return 1000.0 * sum(times) / len(times) if times else None


def creates(w) -> int:
    """Creates completed in the window."""
    return sum(1 for c in done(w) for name, _, _ in c.steps
               if name == "create")
