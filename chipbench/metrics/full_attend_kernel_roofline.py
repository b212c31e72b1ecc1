"""The least time the chip could take for one call of a *full* layer's
decode attention kernel (``kv_attend_full``: every live key and value
row of a call's sequences once, its queries read and its output written
once: ``roofline_rotary_window_moe.attend_call_needs`` at the window's
mean position) over the kernel's device time a call in the trace (the
mean of its events inside the window: the rows grow with the position),
in percent.  A window layer's calls carry another name
(``kv_attend_window``)."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "kv_attend_full"


def read(run):
    from chipbench import roofline_rotary_window_moe as rr
    from chipbench.roofline import least_time_s
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "cache_full_rows_read" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops, nbytes = rr.attend_call_needs(
        c["model_args"], rows=c["rows"], live=c["live_positions"],
        kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return rr.share_of(least, sum(calls) / len(calls), KERNEL)
