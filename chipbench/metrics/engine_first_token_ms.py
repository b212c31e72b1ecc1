"""Median milliseconds from a request admitted at the door to its first
generated id in host memory: ``first_ms`` of the window's ``decode_done``
events (``chipbench/request_events.py``) — the wait in the admission
queue, the join, the wait behind the step in flight, the prompt's pass
and the first step.  ``None`` from a tree without the event."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_counter"
MOVES = "answer_ms_per_token_p90"


def read(run):
    from chipbench.readings import quantile
    from chipbench.request_events import finished
    done = finished()
    if not done:
        return None
    return quantile([e["first_ms"] for e in done], 0.5)
