"""The least time the chip could take for one prefill of the batch of
the double-layer, shortcut-connected family (every matrix outside the
routed experts on every token — two attention sublayers, two dense
SwiGLUs and the 768-column router a layer — the routed experts on the
pairs that fell to held experts only by the program's ``held_share``
under ``real_share``, the zero-compute pairs at no cost, causal
attention over the expanded heads at 192 + 128 a pair in both
sublayers, the head on the last position:
``roofline_shortcut_latent_moe.prefill_needs``) over the device time of
the prefill program (``device_prefill``: one program, whose pieces are a
loop inside it) in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import roofline_shortcut_latent_moe as rl
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_window_moe import share_of
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or "cache_latent_sublayers" not in c \
            or "held_share" not in c or "real_share" not in c:
        return None
    flops, nbytes = rl.prefill_needs(
        c["model_args"], rows=c["rows"],
        prompt_len=c["prefill_tokens"] / c["rows"],
        held_share=c["held_share"], real_share=c["real_share"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return share_of(least, quantile(runs, 0.5), "a prefill")
