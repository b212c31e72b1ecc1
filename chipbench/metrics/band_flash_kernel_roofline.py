"""The least time the chip could take for the banded flash kernel in
one window layer's prefill of one piece (4 operations a query head a
(query, key) pair of the band a value of the head; queries, keys and
values read and the output written once:
``roofline_rotary_window_moe.band_call_needs`` — at a window a
twenty-fourth of the prompt the band is 8% of the causal triangle) over
the kernel's own device time a call in the trace (the median of the
``flash_band`` events inside the window; a full layer's calls carry
another name, ``flash_grouped``), in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "flash_band"


def read(run):
    from chipbench import roofline_rotary_window_moe as rr
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "prefill_piece_rows" not in c \
            or "cache_full_rows_read" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    a = c["model_args"]
    flops, nbytes = rr.band_call_needs(
        a, rows=c["prefill_piece_rows"],
        prompt_len=c["prefill_tokens"] / c["rows"], window=a["window"],
        kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return rr.share_of(least, quantile(calls, 0.5), KERNEL)
