"""Device time inside the traced window that a chip spends in the ring
hop's operations (``collective-permute-start`` / ``-done``: issuing the
``ppermute`` of a group's activations and waiting for the neighbour's)
over the window, in percent, on the chip where it is largest.  A chip's
busy time counts the wait as work; this is the part of it that moves
nothing a step needs."""

LAYER = "ring hop (ppermute)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.trace import op_kind
    t = run.trace
    if not t or not t.window_s:
        return None
    lo, hi = t.window
    waits = [sum(max(0.0, min(e, hi) - max(s, lo)) for name, s, e in d.ops
                 if op_kind(name).startswith("collective-permute"))
             for d in t.devices]
    return 100.0 * max(waits) / t.window_s
