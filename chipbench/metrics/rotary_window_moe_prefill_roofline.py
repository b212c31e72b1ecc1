"""The least time the chip could take for one prefill of the batch of
the window-and-full, two-rotation family (every matrix on every token, 8
experts a token, banded and causal attention at their own operations,
the head on the last position:
``roofline_rotary_window_moe.prefill_needs``) over the device time of
the prefill program (``device_prefill``: one program, whose pieces are
a loop inside it) in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import roofline_rotary_window_moe as rr
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or "cache_full_rows_read" not in c:
        return None
    flops, nbytes = rr.prefill_needs(
        c["model_args"], rows=c["rows"],
        prompt_len=c["prefill_tokens"] / c["rows"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return rr.share_of(least, quantile(runs, 0.5), "a prefill")
