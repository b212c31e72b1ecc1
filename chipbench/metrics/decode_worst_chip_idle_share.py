"""One minus the union of the operation intervals over the traced steady
window on the chip that idles most, in percent
(``decode_device_idle_share`` is the mean over the cell's chips, in
which one starved stage of four shows as a quarter of its size)."""

LAYER = "device"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    if not run.trace:
        return None
    return 100.0 * max(run.trace.idle_share_by_device())
