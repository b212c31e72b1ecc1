"""The least time the chip could take for one call of the latent decode
attention kernel (``latent_attend``: a call's live rows once, 1152 B
each, and their two products a head at the matrix peak, the larger of
the two times: ``roofline_latent_moe.attend_call_needs``) over the
kernel's device time a call in the trace (the mean of its events inside
the window), in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "latent_attend"


def read(run):
    from chipbench import roofline_latent_moe as rl
    from chipbench.roofline import least_time_s
    from chipbench.roofline_window_moe import share_of
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "cache_latent_bytes" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops, nbytes = rl.attend_call_needs(
        c["model_args"], rows=c["rows"], positions=c["live_positions"],
        kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return share_of(least, sum(calls) / len(calls), KERNEL)
