"""The 90th percentile over the window's requests of ``worst_gap_ms``,
from their ``decode_done`` events (``chipbench/request_events.py``): the
longest wait between two of a request's tokens (the longest round behind
its first id, as ``serve.decode.step_s`` recorded it).  A one-token
answer has no gap and is left out.  ``None`` from a tree without the
event."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_counter"
MOVES = "answer_ms_per_token_p90"


def read(run):
    from chipbench.readings import quantile
    from chipbench.request_events import finished
    worst = [e["worst_gap_ms"] for e in finished() or ()
             if e["new_tokens"] > 1]
    if not worst:
        return None
    return quantile(worst, 0.9)
