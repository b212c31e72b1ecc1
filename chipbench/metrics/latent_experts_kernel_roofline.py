"""The least time the chip could take for one ``E`` layer's two grouped
products in a decode step (the up product, then the down product, the
squared relu between them: the touched experts' two matrices once by the
program's ``decode.moe.experts_hit`` a layer a step, the held pairs'
latent rows in, their hidden activations out and in, the result out:
``roofline_ssd_latent_moe.latent_experts_needs``) over the
``grouped_experts`` kernel's device time for them in the trace, in
percent.  The two calls of a layer follow each other, so the time is
the median over the window of *two consecutive events'* durations added
up: one of each, wherever the window cuts the series."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "grouped_experts"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_ssd_latent_moe import latent_experts_needs
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("latent_moe_layers") \
            or not c.get("held_pairs_a_layer_step"):
        return None
    lo, hi = t.window
    calls = sorted((s, e - s) for name, s, e in t.devices[0].ops
                   if op_kind(name) == KERNEL and s >= lo and e <= hi)
    pairs = [a[1] + b[1] for a, b in zip(calls[0::2], calls[1::2])]
    if not pairs:
        return None
    flops, nbytes = latent_experts_needs(
        c["model_args"], c["rows"], c["weight_bytes"],
        c["experts_hit_a_layer_step"], c["held_pairs_a_layer_step"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(pairs, 0.5)
