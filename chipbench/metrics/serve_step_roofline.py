"""The least time the chip could take for one engine step at the
window's mean batch (weights once, live key/value rows once) over the
device time of the step program in the trace, in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import gpt_decode_step_needs, least_time_s
    t, c = run.trace, run.counters
    runs = t.module_runs(r"jit_step") if t else []
    if not runs or run.peaks is None:
        return None
    a = c["model_args"]
    flops, nbytes = gpt_decode_step_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], vocab=a["vocab"],
        rows=c["rows"], live_positions=c["live_positions"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(runs, 0.5)
