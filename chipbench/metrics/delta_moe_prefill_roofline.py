"""The least time the chip could take for one prefill of the delta-rule
/ attention hybrid's batch with routed experts (every matrix outside
the routed experts on every token, the held experts' pairs, causal
attention in the attention layer, the KDA layers' chunked form, the
head on the last position: ``roofline_delta_moe.prefill_needs``) over
the device time of the prefill program (``device_prefill``) in the
trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_delta_moe import prefill_needs
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or not c.get("delta_layers"):
        return None
    a = c["model_args"]
    flops, nbytes = prefill_needs(
        a, rows=c["rows"], prompt_len=c["prefill_tokens"] / c["rows"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"],
        chunk=a.get("chunk", 64),
        held_pairs_share=c.get("held_pairs_share"))
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(runs, 0.5)
