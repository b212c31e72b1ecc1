"""The ``delta_step`` kernel's device time as a share of the decode
programs' (``device_decode``) device time inside the traced window, in
percent: what says the mechanism the configuration adds does the work
the cell was sized for (three layers in four rewrite a state of 4.19
MB a sequence every step: by the shapes ~40% of a step's bytes)."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "delta_step"


def read(run):
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or not c.get("delta_layers"):
        return None
    lo, hi = t.window
    programs = [(s, e) for name, s, e in t.devices[0].modules
                if "device_decode" in name and s >= lo and e <= hi]
    whole = sum(e - s for s, e in programs)
    if not whole:
        return None
    # the kernel's calls inside those programs' runs (a run cut by the
    # window's end leaves its calls out with it)
    kernel = sum(e - s for name, s, e in t.devices[0].ops
                 if op_kind(name) == KERNEL
                 and any(ps <= s and e <= pe for ps, pe in programs))
    return 100.0 * kernel / whole if kernel else None
