"""The least time the chip could take for one call of the decode
attention kernel (``kv_attend``: a call's live key and value rows once,
``min(positions, window)`` of them in a window layer and all in a full
one, the layers weighted as they occur:
``roofline_window_moe.attend_call_needs``) over the kernel's device time
a call in the trace (the mean of its events inside the window, so that
window and full layers weigh as they occur), in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "kv_attend"


def read(run):
    from chipbench import roofline_window_moe as rw
    from chipbench.roofline import least_time_s
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "cache_window_bytes" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops, nbytes = rw.attend_call_needs(
        c["model_args"], rows=c["rows"], positions=c["live_positions"],
        kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return rw.share_of(least, sum(calls) / len(calls), KERNEL)
