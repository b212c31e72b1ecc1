"""The least time the chip could take for one decode step of the
delta-rule / attention hybrid's batch with routed experts (every KDA
layer's state — at the configuration's size — read once and written
once, its windows likewise, mixer, shared-expert, router and head
matrices once, the *touched* held experts' once by the program's
``experts_hit_share``, the attention layer's live rows once, logits
once: ``roofline_delta_moe.decode_step_needs``) over the device time of
a step in the trace, in percent.  The program's own counts of what it
holds (the gauges ``decode.delta.state_bytes`` /
``decode.delta.window_bytes`` / ``decode.cache.full_bytes``) are only
held against that size, and over 1.06 of a state's (1.10 of the
others') this reader raises."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_delta_moe import check_held, decode_step_needs
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or not c.get("delta_layers") \
            or "experts_hit_share" not in c:
        return None
    a = c["model_args"]
    check_held(c, a)
    flops, nbytes = decode_step_needs(
        a, rows=c["rows"], live_positions=c["live_positions"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"],
        experts_hit_share=c["experts_hit_share"],
        held_pairs_share=c.get("held_pairs_share"))
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / (quantile(runs, 0.5) / c["steps_per_reading"])
