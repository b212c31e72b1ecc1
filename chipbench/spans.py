"""The program's own spans in a reduced trace.

``defer_tpu.obs.span`` writes a ``TraceAnnotation`` named
``chipbench:<layer>.<phase>`` while a profiler session runs, so
``chipbench/trace.py::load`` collects it beside the harness's spans, as
``(<layer>.<phase>, start, end)`` in ``TraceReduction.spans``.  A program
without them (the parent of the PR that added them) leaves none, and
every function here then returns nothing.

How idle time is split among them is ``chipbench/trace.py::idle_split``
(``TraceReduction.idle_gaps`` since PR 52): each instant of a gap goes to
the innermost span over it.
"""

from __future__ import annotations


def durations(red, name: str) -> list[float]:
    """Seconds of every occurrence of the span ``name`` that lies inside
    the window of the reduction ``red``."""
    lo, hi = red.window
    return [e - s for n, s, e in red.spans
            if n == name and s >= lo and e <= hi]
