"""The least time the chip could take for one OLMoE prefill of the batch
(q/k/v/o and the router on every token, ``experts_per_tok`` experts a
token — not all of them —, causal attention, the head on the last
position: ``olmoe_prefill_needs``) over the device time of the prefill
program (``device_prefill``) in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_moe import olmoe_prefill_needs
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or "prefill_tokens" not in c:
        return None
    a = c["model_args"]
    flops, nbytes = olmoe_prefill_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], n_head=a["heads"],
        vocab=a["vocab"], n_experts=a["num_experts"],
        expert_width=a["expert_hidden"], top_k=a["experts_per_tok"],
        rows=c["rows"], prompt_len=c["prefill_tokens"] / c["rows"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(runs, 0.5)
