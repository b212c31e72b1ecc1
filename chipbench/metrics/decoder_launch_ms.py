"""Mean of ``decode.dispatch_s`` over the window, in milliseconds: the
enqueue of one decode chunk program (no ``block_until_ready`` before
the clock stops), a host launch cost."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("launch_count"):
        return None
    return 1e3 * c["launch_s_sum"] / c["launch_count"]
