"""The least time the chip could take for one call of the
``delta_step`` kernel (a KDA layer's state, at the configuration's
size, read once and written once: ``roofline_delta_moe
.delta_step_needs``) over the kernel's device time a call in the trace
(the median of its events inside the window), in percent: the new step
kernel's own share, bound by memory."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "delta_step"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_delta_moe import check_held, delta_step_needs
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("delta_layers"):
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    a = c["model_args"]
    check_held(c, a)
    flops, nbytes = delta_step_needs(a, c["rows"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(calls, 0.5)
