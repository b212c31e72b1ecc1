"""One minus the union of the device's operation intervals over the
traced steady window, in percent; the mean over the chips the cell
uses (each chip's own share is printed on an earlier line)."""

LAYER = "device"
SOURCE = "device_trace"
MOVES = "answer_ms_per_token_p50"


def read(run):
    return 100.0 * run.trace.idle_share if run.trace else None
