"""Idle milliseconds of the device a decode chunk while the host is in
the launch and the chip has not begun: the idle time under
``decode.dispatch`` and its children ``decode.upload`` and
``decode.launch`` (``chipbench/idle.py::split``), per dispatch, the mean
over the cell's chips."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import idle
    return idle.per_round_ms(run.trace, idle.DECODE, idle.DECODE.launch)
