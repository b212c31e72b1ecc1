"""Weight leaves the ring placed otherwise than the device would by
default (the gauge ``decode.weights.relaid_leaves``): each is a layout
copy at the head of every dispatch that went, and a placement that
set-up pays once."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    return run.counters.get("relaid_leaves")
