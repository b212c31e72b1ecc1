"""The operations of the banded flash kernel in one window layer's
prefill of one piece (4 a query head a (query, key) pair of the band a
value of the head: ``roofline_window_moe.band_flops``) at the matrix
peak, over the kernel's own device time a call in the trace (the median
of the ``flash_band`` events inside the window; the full layer's calls
carry another name, ``flash_grouped``), in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "flash_band"


def read(run):
    from chipbench import roofline_window_moe as rw
    from chipbench.readings import quantile
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "prefill_piece_rows" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    a = c["model_args"]
    flops = rw.band_flops(a, rows=c["prefill_piece_rows"],
                          prompt_len=c["prefill_tokens"] / c["rows"],
                          window=a["window"])
    return rw.share_of(flops / run.peaks["bf16_flops_per_s"],
                       quantile(calls, 0.5), KERNEL)
