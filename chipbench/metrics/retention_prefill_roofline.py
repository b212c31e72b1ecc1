"""The least time the chip could take for one Brumby prefill of the
batch (every matrix on every token, the retention in its attention
form, the state built once, the head on the last position:
``brumby_prefill_needs``) over the device time of the prefill program
(``device_prefill``) in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_retention import (
        brumby_prefill_needs, head_dim_of)
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_prefill") if t else []
    if not runs or run.peaks is None or not c.get("retention_layers"):
        return None
    a = c["model_args"]
    flops, nbytes = brumby_prefill_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], n_head=a["heads"],
        n_kv=a["kv_heads"], mlp_width=a["mlp_hidden"], vocab=a["vocab"],
        rows=c["rows"], prompt_len=c["prefill_tokens"] / c["rows"],
        weight_bytes=c["weight_bytes"], head_dim=head_dim_of(a))
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(runs, 0.5)
