"""The least time the chip could take for one prefill of the Mamba-2 /
attention / LatentMoE hybrid's batch (every matrix outside the routed
experts on every token, the routed experts on the pairs that fell to
held experts only — the program's own ``held_share`` —, causal attention
in the attention layer, the chunked recurrence's products with ``C
B^T`` a group, the head on the last position:
``roofline_ssd_latent_moe.prefill_needs``) over the device time of the
prefill program (``device_prefill``) in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_ssd_latent_moe import prefill_needs
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or not c.get("latent_moe_layers"):
        return None
    flops, nbytes = prefill_needs(
        c["model_args"], rows=c["rows"],
        prompt_len=c["prefill_tokens"] / c["rows"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"],
        held_share=c.get("held_share"))
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(runs, 0.5)
