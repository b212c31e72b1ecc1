"""Median over the window's requests of (``last_ms`` - ``first_ms``) /
(``new_tokens`` - 1), from their ``decode_done`` events
(``chipbench/request_events.py``): what a token after the first cost a
request, the passes of other requests' joins included — beside
``engine_step_ms``, which is the mean round.  A one-token answer has no
gap and is left out.  ``None`` from a tree without the event."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_counter"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench.readings import quantile
    from chipbench.request_events import finished
    gaps = [(e["last_ms"] - e["first_ms"]) / (e["new_tokens"] - 1)
            for e in finished() or () if e["new_tokens"] > 1]
    if not gaps:
        return None
    return quantile(gaps, 0.5)
