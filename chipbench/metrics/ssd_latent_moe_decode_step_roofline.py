"""The least time the chip could take for one decode step of the
Mamba-2 / attention / LatentMoE hybrid's batch (mixer, router, latent
projection, shared-expert and head matrices once, the *touched held*
experts' two matrices once by the program's ``experts_hit_share``, every
Mamba-2 layer's ``H`` and window — at the configuration's size, 8 B/C
groups wide — read once and written once, the attention layer's live
rows once, logits once: ``roofline_ssd_latent_moe.decode_step_needs``)
over the device time of a step in the trace, in percent.  The program's
own counts of what it holds (the gauges ``decode.ssm.state_bytes`` /
``.conv_bytes`` / ``decode.cache.full_bytes``) are only held against
that size, and over 1.10 of it this reader raises."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_ssd_latent_moe import (check_held,
                                                   decode_step_needs)
    t, c = run.trace, run.counters
    runs = t.module_runs(r"device_decode") if t else []
    if not runs or run.peaks is None or not c.get("latent_moe_layers") \
            or "experts_hit_share" not in c:
        return None
    a = c["model_args"]
    check_held(c, a)
    flops, nbytes = decode_step_needs(
        a, rows=c["rows"], live_positions=c["live_positions"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"],
        experts_hit_share=c["experts_hit_share"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / (quantile(runs, 0.5) / c["steps_per_reading"])
