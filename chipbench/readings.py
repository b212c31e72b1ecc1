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

import statistics

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


def spread(values, *, trim: bool = True) -> float | None:
    """The spread of some runs' values of one metric, as a share of their
    median: the distance between the first and the third quartile as
    ``statistics.quantiles(values, n=4)`` gives them, over the median.
    With ``trim`` the run farthest from the median is left out where
    that narrows the spread (the check's own rule for whether a bound is
    too tight; without it, its rule for too loose); three runs or fewer
    all stay, since two values' quartiles lie outside them.  ``None`` for
    fewer than two values or a median of 0.  For ``chipbench/steady.py``:
    nothing that times a cell reads it."""
    ys = sorted(float(v) for v in values)
    mid = statistics.median(ys) if ys else 0.0
    if len(ys) < 2 or not mid:
        return None
    q1, _q2, q3 = statistics.quantiles(ys, n=4)
    out = (q3 - q1) / abs(mid)
    if trim and len(ys) > 3:
        far = max(ys, key=lambda y: abs(y - mid))
        rest = list(ys)
        rest.remove(far)
        narrower = spread(rest, trim=False)
        if narrower is not None:
            out = min(out, narrower)
    return out


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
