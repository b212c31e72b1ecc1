"""The least time the chip could take for one call of the ``ssd_step``
kernel with B/C groups (a Mamba-2 layer's ``H``, at the configuration's
size, read once and written once, the decay, the input, every one of the 8 groups'
``B`` and ``C`` and the output once:
``roofline_ssd_latent_moe.ssd_step_needs``) over the kernel's device
time a call in the trace (the median of its events inside the window),
in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "ssd_step"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_ssd_latent_moe import ssd_step_needs
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("latent_moe_layers"):
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops, nbytes = ssd_step_needs(c["model_args"], c["rows"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(calls, 0.5)
