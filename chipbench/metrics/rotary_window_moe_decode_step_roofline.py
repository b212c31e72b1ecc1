"""The least time the chip could take for one decode step of the
window-and-full, two-rotation family (attention, router and head
weights once, the *touched* experts' once by the program's own
``experts_hit_share``, live rows ``min(positions, window)`` a window
layer and all of them a full one, logits once:
``roofline_rotary_window_moe.decode_step_needs``) over the device time
of a step in the trace, in percent.  The program's own count of its
cache buffers (the gauges ``decode.cache.window_bytes`` /
``.full_bytes``) is only held against what the configuration needs."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import roofline_rotary_window_moe as rr
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or "cache_window_bytes" not in c \
            or "experts_hit_share" not in c:
        return None
    a = c["model_args"]
    need = rr.needed_cache_bytes(a, rows=c["rows"], max_len=c["max_len"],
                                 kv_bytes=c["kv_bytes"])
    for held, needed, rows_held in (
            (c["cache_window_bytes"], need[0], min(c["max_len"], a["window"])),
            (c["cache_full_bytes"], need[1], c["max_len"])):
        if needed:
            rr.check_held(held, needed, groups=run.cell.chips,
                          rows_held=rows_held)
    flops, nbytes = rr.decode_step_needs(
        a, rows=c["rows"], positions=c["live_positions"],
        experts_hit_share=c["experts_hit_share"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return rr.share_of(
        least, quantile(runs, 0.5) / c["steps_per_reading"], "a step")
