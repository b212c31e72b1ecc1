"""The least time the chip could take for one Brumby decode step of the
batch (the layers' and the head's weights once, the retention state —
at the configuration's size — read once and written once, logits once:
``brumby_decode_step_needs``) over the device time of a step in the
trace, in percent.  The program's own count of its state (the gauge
``decode.retention.state_bytes``) is only held against that size."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_retention import (
        brumby_decode_step_needs, check_held, head_dim_of,
        needed_state_bytes)
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or not c.get("retention_layers"):
        return None
    a = c["model_args"]
    hd = head_dim_of(a)
    check_held(c.get("retention_state_bytes"), needed_state_bytes(
        n_layer=a["num_layers"], rows=c["rows"], n_kv=a["kv_heads"],
        head_dim=hd), hd)
    flops, nbytes = brumby_decode_step_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], n_head=a["heads"],
        n_kv=a["kv_heads"], mlp_width=a["mlp_hidden"], vocab=a["vocab"],
        rows=c["rows"], weight_bytes=c["weight_bytes"], head_dim=hd)
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / (quantile(runs, 0.5) / c["steps_per_reading"])
