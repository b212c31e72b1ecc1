"""Mean milliseconds from a request's last generated id in host memory
to its answer written to the client (``delivered_ms`` - ``last_ms`` of
the window's ``decode_done`` events, ``chipbench/request_events.py``):
the answer's concatenate, ``on_done`` and the socket write, on the
engine's thread inside ``engine.delivery``.  ``None`` from a tree
without the event."""

LAYER = "front door (serve/frontdoor.py, serve/admission.py)"
SOURCE = "program_counter"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench.request_events import finished
    done = finished()
    if not done:
        return None
    return sum(e["delivered_ms"] - e["last_ms"] for e in done) / len(done)
