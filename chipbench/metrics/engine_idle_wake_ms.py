"""Idle milliseconds of the device an engine step while the chip is done
and the host is not back: the idle time that ``chipbench/idle.py::split``
gives to ``engine.device`` and ``engine.sync``, per ``engine.step``.  Also
prints the window's idle time by phase and the planes' causality bracket
as earlier lines of the traced run."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench import idle
    if not idle.has_spans(run.trace, idle.ENGINE):
        return None
    idle.note(run.trace, idle.ENGINE)
    return idle.per_round_ms(run.trace, idle.ENGINE, idle.ENGINE.wake)
