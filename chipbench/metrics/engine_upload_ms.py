"""Mean of the ``engine.upload`` span over the traced window, in
milliseconds: the four per-slot rows of a step, host to device."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench import idle
    return idle.upload_ms(run.trace, idle.ENGINE)
