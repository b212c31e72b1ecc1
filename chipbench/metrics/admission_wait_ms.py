"""Mean milliseconds a served request waited in the admission queue
(admitted until the engine loop popped it): the ``admission`` bucket
of the front door's attribution for the measured tenant."""

LAYER = "front door (serve/frontdoor.py, serve/admission.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p90"


def read(run):
    return run.counters.get("admission_wait_ms_mean")
