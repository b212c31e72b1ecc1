"""The least time the chip could take for one routed layer's two calls
of the ``grouped_experts`` kernel in a decode step (gate-and-up, then
down: the touched experts' three matrices once by the program's
``decode.moe.experts_hit`` a layer a step, the sorted rows in, the
hidden activations out and in, the result out:
``roofline_conv_moe.routed_step_needs``) over the kernel's device time
for them in the trace, in percent: the kernel that is most of this
cell's step, at 8 rows an expert with every expert touched.  The two
calls of a layer follow each other and differ in bytes two to one, so
the time is the median over the window of *two consecutive events'*
durations added up: one of each, wherever the window cuts the series."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "grouped_experts"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_conv_moe import routed_step_needs
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("conv_layers") \
            or "experts_hit_share" not in c:
        return None
    lo, hi = t.window
    calls = sorted((s, e - s) for name, s, e in t.devices[0].ops
                   if op_kind(name) == KERNEL and s >= lo and e <= hi)
    pairs = [a[1] + b[1] for a, b in zip(calls[0::2], calls[1::2])]
    if not pairs:
        return None
    a = c["model_args"]
    flops, nbytes = routed_step_needs(
        a, c["rows"], c["weight_bytes"],
        c["experts_hit_share"] * a["num_experts"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(pairs, 0.5)
