"""The least time the chip could take for one decode step of the
double-layer, shortcut-connected family (both sublayers' attention
matrices, both dense SwiGLUs, the router over routed and zero columns
and the head once, the *touched* held experts' matrices once by the
program's own ``experts_hit_share``, 1152 B a live row a sublayer once
and its 139,264 operations, the products of the pairs that fell to held
experts by ``held_share`` under ``real_share``, the zero-compute pairs
at no cost, logits once, the larger of the memory's and the matrix
unit's time: ``roofline_shortcut_latent_moe.decode_step_needs``) over
the device time of a step in the trace, in percent.  The program's own
count of its rows (the gauges ``decode.cache.latent_bytes`` /
``.latent_positions``, both sublayers') is only held against what the
configuration needs: over 1.12 of it the reader raises."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import roofline_shortcut_latent_moe as rl
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_window_moe import share_of
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or "cache_latent_sublayers" not in c \
            or "experts_hit_share" not in c or "real_share" not in c:
        return None
    a = c["model_args"]
    rl.check_row_bytes(c["cache_latent_bytes"], c["cache_latent_positions"],
                       a, c["kv_bytes"])
    flops, nbytes = rl.decode_step_needs(
        a, rows=c["rows"], positions=c["live_positions"],
        experts_hit_share=c["experts_hit_share"],
        held_share=c["held_share"], real_share=c["real_share"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return share_of(
        least, quantile(runs, 0.5) / c["steps_per_reading"], "a step")
