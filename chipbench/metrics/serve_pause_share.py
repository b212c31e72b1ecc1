"""The decode engine's own pauses inside the traced window over the
window, in percent: every ``engine.pause`` marker with the time the
phase before it took over the window's median of that phase
(``decode_pause_share`` for the engine's phases).  0 in a window that
held no pause."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p90"


def read(run):
    from chipbench import idle
    return idle.pause_share(run.trace, idle.ENGINE)
