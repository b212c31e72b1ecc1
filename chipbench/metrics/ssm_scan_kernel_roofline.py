"""The least time the chip could take for one call of the ``ssm_scan``
kernel (a Mamba layer's recurrence over one piece of the prefill: the
step, the input, ``B`` and ``C`` in, ``y`` and the last ``H`` out, once
each: ``roofline_hybrid_ssm.ssm_scan_needs``) over the kernel's device
time a call in the trace (the median of its events inside the window),
in percent.  The peak table has no vector-unit peak, so this is a share
of the *memory* roofline of a kernel the vector unit bounds."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "ssm_scan"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_hybrid_ssm import ssm_scan_needs
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("mamba_layers") \
            or not c.get("prefill_piece_rows"):
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops, nbytes = ssm_scan_needs(
        c["model_args"], c["prefill_piece_rows"],
        c["prefill_tokens"] / c["rows"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(calls, 0.5)
