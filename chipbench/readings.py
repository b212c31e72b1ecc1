"""Readings, and the quantile every metric here uses.

A *reading* is one unit of work timed on the host clock from outside the
program, ending where the result is in host memory.  An end-to-end rate
is all the work of the window over all its time; the readings check the
size of the unit: an untraced run with fewer than :data:`MIN_READINGS`
is refused, because a window that holds one or two long units ends at a
different share of work every run.  That is the fault the first
benchmark of this repository was turned down for.
"""

from __future__ import annotations

MIN_READINGS = 12


class TooFewReadings(ValueError):
    """An untraced run ended with fewer than MIN_READINGS readings."""


def quantile(values, q: float) -> float:
    """Linear-interpolated quantile of ``values`` (``q`` in [0, 1])."""
    ys = sorted(float(v) for v in values)
    if not ys:
        raise ValueError("quantile of no values")
    pos = q * (len(ys) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(ys) - 1)
    return ys[lo] + (ys[hi] - ys[lo]) * (pos - lo)


def require_readings(readings, *, need: int = MIN_READINGS) -> None:
    """Refuses a window that held fewer than ``need`` readings."""
    if len(readings) < need:
        raise TooFewReadings(
            f"{len(readings)} readings in the window, {need} needed: give "
            f"the run a longer window or the cell smaller units")


def describe(readings) -> dict:
    """Count and quartiles of the readings, for the earlier output line."""
    if not readings:
        return {"count": 0}
    return {"count": len(readings), "min": min(readings),
            "q1": quantile(readings, 0.25), "median": quantile(readings, 0.5),
            "q3": quantile(readings, 0.75), "max": max(readings)}
