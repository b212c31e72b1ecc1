"""A pipelined decode step's share of one chip's roofline, in percent:
the least time a chip could take for its stage's part of one step of
the whole batch (the model's needed bytes and flops of a step,
``gpt_decode_step_needs``, over the chips: a stage's weights once, its
live key/value rows once) over the device time of a step on the chip
where the decode program runs longest.  The ring passes a stage's
weights once for every group of rows that comes by, several times a
step; counting them once is what a chip that saw the whole batch at
once would need, so the share also shows what pipelining costs."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import gpt_decode_step_needs, least_time_s
    t, c = run.trace, run.counters
    if not t or run.peaks is None:
        return None
    per_chip = [t.module_runs(r"device_decode", device=i)
                for i in range(len(t.devices))]
    if not all(per_chip):
        return None
    a = c["model_args"]
    flops, nbytes = gpt_decode_step_needs(
        n_layer=a["num_layers"], n_embd=a["hidden"], vocab=a["vocab"],
        rows=c["rows"], live_positions=c["live_positions"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    chips = len(per_chip)
    least, _bound = least_time_s(flops / chips, nbytes / chips, run.peaks)
    step_s = max(quantile(runs, 0.5) for runs in per_chip) \
        / c["steps_per_reading"]
    return 100.0 * least / step_s
