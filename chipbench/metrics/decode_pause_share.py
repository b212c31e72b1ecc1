"""The decode ring's own pauses inside the traced window over the window,
in percent: every ``decode.pause`` marker (the program's pause watch
leaves one behind a phase that took at least 10 ms and 3x longer than
the phase does) with the time that phase took over the window's median
of it.  0 in a window that held no pause."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import idle
    return idle.pause_share(run.trace, idle.DECODE)
