"""Idle milliseconds of the device a decode chunk while the chip is done
and the host is not back: the idle time that ``chipbench/idle.py::split``
gives to ``decode.sync``, per ``decode.dispatch``, the mean over the
cell's chips.  Also prints the window's idle time by phase and the
planes' causality bracket as earlier lines of the traced run."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import idle
    if not idle.has_spans(run.trace, idle.DECODE):
        return None
    idle.note(run.trace, idle.DECODE)
    return idle.per_round_ms(run.trace, idle.DECODE, idle.DECODE.wake)
