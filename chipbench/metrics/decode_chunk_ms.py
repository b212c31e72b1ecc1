"""Median reading of the window in milliseconds: the host-clock time
between two consecutive ``on_tokens`` calls (``token_chunk`` decode
steps of the whole batch, tokens in host memory).  The steadier
statistic beside ``tokens_per_s``: one stall moves one reading."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "host_clock"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    return 1e3 * quantile(run.readings, 0.5) if run.readings else None
