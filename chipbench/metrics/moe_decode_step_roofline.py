"""The least time the chip could take for one OLMoE decode step of the
batch (attention, router and head weights once, the *touched* experts'
weights once by the program's own ``experts_hit_share``, live key/value
rows once: ``olmoe_decode_step_needs``) over the device time of a step
in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_moe import olmoe_decode_step_needs
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or "experts_hit_share" not in c:
        return None
    a = c["model_args"]
    flops, nbytes = olmoe_decode_step_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], vocab=a["vocab"],
        n_experts=a["num_experts"], expert_width=a["expert_hidden"],
        top_k=a["experts_per_tok"], rows=c["rows"],
        live_positions=c["live_positions"],
        experts_hit_share=c["experts_hit_share"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / (quantile(runs, 0.5) / c["steps_per_reading"])
