"""The operations of the prompt's attention kernel over the expanded
heads in one layer's prefill of one piece (2 a head a (query, key) pair
of the causal triangle a value of the 192-wide key and of the 128-wide
value: ``roofline_latent_moe.flash_flops``) at the matrix peak, over the
kernel's own device time a call in the trace (the median of the
``flash_latent`` events inside the window), in percent."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "flash_latent"


def read(run):
    from chipbench import roofline_latent_moe as rl
    from chipbench.readings import quantile
    from chipbench.roofline_window_moe import share_of
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or "prefill_piece_rows" not in c \
            or "cache_latent_bytes" not in c:
        return None
    lo, hi = t.window
    calls = [e - s for name, s, e in t.devices[0].ops
             if op_kind(name) == KERNEL and s >= lo and e <= hi]
    if not calls:
        return None
    flops = rl.flash_flops(c["model_args"], rows=c["prefill_piece_rows"],
                           prompt_len=c["prefill_tokens"] / c["rows"])
    return share_of(flops / run.peaks["bf16_flops_per_s"],
                    quantile(calls, 0.5), KERNEL)
