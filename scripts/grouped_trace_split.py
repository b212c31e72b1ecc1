"""A traced run of a routed-expert cell with its grouped products split
by operand shape and by program: what the ``breakdown``'s top-ten list
cannot give (it sums ``ragged-dot-none`` over gate, up and down, over
the steps and the prefill).  Chip only.

    python scripts/grouped_trace_split.py CELL SEED OUT.json

runs ``python -m chipbench --workload CELL --seed SEED --trace 1`` in
this process with ``chipbench.trace.reduce_trace`` wrapped: every device
event whose operation is a ``ragged-dot`` (none since PR 56) or one of
the kernels ``grouped_experts`` / ``grouped_rows`` is keyed by its result and operand shapes (the event's name is
its line of the compiled text) and by the program run that encloses it
(``jit_device_decode`` / ``jit_device_prefill``).  ``OUT.json`` holds,
a key, the count, the summed and the median seconds, and the runs of
each program inside the window; the cell's own result line is printed
as ever.
"""

from __future__ import annotations

import json
import re
import statistics
import sys
import time

_T_START = time.perf_counter()

_WANTED = re.compile(r"ragged-dot|grouped_experts|grouped_rows")


def _split(red) -> dict:
    dev = red.devices[0]
    lo, hi = red.window
    runs = sorted((s, e, n) for n, s, e in dev.modules if s >= lo and e <= hi)

    def program(at: float) -> str:
        for s, e, n in runs:
            if s <= at <= e:
                return re.sub(r"\(.*", "", n)
        return "outside"

    by: dict[str, list[float]] = {}
    for name, s, e in dev.ops:
        if s < lo or e > hi or not _WANTED.search(name):
            continue
        head = name.split(", metadata", 1)[0]
        shapes = " ".join(re.findall(r"\w+\[[\d,]*\]", head)[:6])
        kind = _WANTED.search(name).group()
        by.setdefault(f"{program(s)} | {kind} | {shapes}", []).append(e - s)
    programs: dict[str, list[float]] = {}
    for s, e, n in runs:
        programs.setdefault(re.sub(r"\(.*", "", n), []).append(e - s)
    return {
        "window_s": red.window_s, "busy_s": red.busy_s,
        "programs": {n: {"runs": len(v), "sum_s": sum(v),
                         "median_s": statistics.median(v)}
                     for n, v in programs.items()},
        "grouped": {k: {"count": len(v), "sum_s": sum(v),
                        "median_us": 1e6 * statistics.median(v)}
                    for k, v in sorted(by.items())}}


def main(argv) -> int:
    cell, seed, out = argv
    import chipbench.trace as trace
    from chipbench.harness import main as bench

    reduce_trace = trace.reduce_trace

    def wrapped(*a, **kw):
        red = reduce_trace(*a, **kw)
        with open(out, "w") as f:
            json.dump(_split(red), f, indent=1)
        return red

    trace.reduce_trace = wrapped
    return bench(["--workload", cell, "--seed", seed, "--seconds", "40",
                  "--trace", "1"], t_start=_T_START)


if __name__ == "__main__":
    sys.path.insert(0, ".")
    sys.exit(main(sys.argv[1:]))
