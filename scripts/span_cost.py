"""What one ``defer_tpu.obs.span`` enter/exit costs with the tracer off
and no profiler session: the always-on price of a named phase.

    python scripts/span_cost.py            # one JSON line, ns a span

jax is imported first, as in every process that runs a decode loop, so
the inert ``TraceAnnotation`` is part of the price.  Run it in two trees
to compare them (docs/OBSERVABILITY.md, "Program spans").
"""

import json
import sys
import time

import jax  # noqa: F401 — the annotation is made only where jax is loaded

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from defer_tpu.obs import span, tracer  # noqa: E402


#: what is timed: (key, layer, phase, args).  The engine's ``device``
#: carries the count of passes in front of its flight since PR 69
#: (``obs/trace.py::PAUSE_BEHIND_KEY``): 0 takes the phase's own watch, 1
#: the count's; a tree from before takes the dict for plain ``args``
SPANS = (("decode.scatter", "decode", "scatter", None),
         ("decode.dispatch", "decode", "dispatch", None),
         ("engine.device", "engine", "device", None),
         ("engine.device passes=0", "engine", "device",
          {"passes": 0, "step": 7}),
         ("engine.device passes=1", "engine", "device",
          {"passes": 1, "step": 7}))


def _best(fn, n: int, repeats: int) -> float:
    """Nanoseconds a call of ``fn``, the loop's own and the call's taken
    off (a loop over a function that does nothing, timed the same way):
    the best of ``repeats`` runs of ``n``."""
    def nothing():
        pass

    def best_of(f):
        runs = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n):
                f()
            runs.append((time.perf_counter() - t0) / n)
        return min(runs)

    return round(1e9 * (best_of(fn) - best_of(nothing)), 1)


def delivery_cost(n: int, repeats: int, rows: int = 2) -> dict:
    """What ``ContinuousBatchEngine._deliver`` costs a step on the host
    with ``rows`` live slots that never finish, the device's part a stub
    that is ready: its three spans, the histogram and each row's
    bookkeeping (since PR 69 its waypoints too).  Run it in two trees:
    the difference over ``rows`` is what a live row a step pays."""
    import numpy as np

    from defer_tpu.models.gpt import gpt_tiny
    from defer_tpu.serve import engine as eng_mod

    class Ready(np.ndarray):            # a step's ids, already on the host
        def block_until_ready(self):
            return self

    g = gpt_tiny(seq_len=16)
    eng = eng_mod.ContinuousBatchEngine(g, g.init(jax.random.key(0)),
                                        num_stages=1, width=rows)
    slots = []
    for i in range(rows):
        req = eng_mod.DecodeRequest(np.zeros(1, np.int32), 1 << 40)
        slots.append((i, eng_mod._Slot(req), 1))
    ids = np.zeros(rows, np.int32).view(Ready)
    flight = eng_mod._Flight(ids, slots, time.perf_counter())

    def one():
        eng._deliver(flight)
        for _i, s, _fed in slots:       # or a list grows by n x repeats
            s.out.clear()

    for _ in range(20):
        one()
    return {"deliver_ns": _best(one, n, repeats), "rows": rows}


def main(n: int = 100_000, repeats: int = 7) -> None:
    assert not tracer().enabled
    best = {}
    for key, layer, phase, args in SPANS:
        def one(layer=layer, phase=phase, args=args):
            with span(layer, phase, args):
                pass
        for _ in range(20):     # past the phase's unjudged occurrences
            one()
        best[key] = _best(one, n, repeats)
    print(json.dumps({"span_ns": best, "n": n, "repeats": repeats,
                      "engine": delivery_cost(n // 5, repeats),
                      "platform": jax.default_backend()}))


if __name__ == "__main__":
    main()
