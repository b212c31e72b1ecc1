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


def main(n: int = 100_000, repeats: int = 7) -> None:
    assert not tracer().enabled
    best = {}
    for layer, phase in (("decode", "scatter"), ("decode", "dispatch")):
        for _ in range(20):     # past the phase's unjudged occurrences
            with span(layer, phase):
                pass
        runs = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(n):
                with span(layer, phase):
                    pass
            runs.append((time.perf_counter() - t0) / n)
        t0 = time.perf_counter()
        for _ in range(n):
            pass
        loop = (time.perf_counter() - t0) / n
        best[f"{layer}.{phase}"] = round(1e9 * (min(runs) - loop), 1)
    print(json.dumps({"span_ns": best, "n": n, "repeats": repeats,
                      "platform": jax.default_backend()}))


if __name__ == "__main__":
    main()
