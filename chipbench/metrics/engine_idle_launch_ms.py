"""Idle milliseconds of the device an engine step while the host is in
the launch and the chip has not begun: the idle time under
``engine.dispatch`` and its children ``engine.upload`` and
``engine.launch`` (``chipbench/idle.py::split``), per ``engine.step``."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench import idle
    return idle.per_round_ms(run.trace, idle.ENGINE, idle.ENGINE.launch)
