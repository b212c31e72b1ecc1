"""Slots the window's launched steps visited over steps x ``width``
(``serve.decode.rows.launched`` over ``serve.decode.step_s``'s count):
the share of the slot-wise work — cache rows written and attended — that
a step does since its cache kernels take the list of live slots.  1.0:
every slot live, the list saves nothing."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_counter"
MOVES = "answer_ms_per_token_p50"


def read(run):
    c = run.counters
    if not c.get("step_count") or not c.get("width") \
            or c.get("rows_launched") is None:
        return None
    return c["rows_launched"] / (c["step_count"] * c["width"])
