"""Mean of the ``decode.upload`` span over the traced window, in
milliseconds: a chunk's host-to-device scalars, before the jitted call."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_span"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import idle
    return idle.upload_ms(run.trace, idle.DECODE)
