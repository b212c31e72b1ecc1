"""The least time the chip could take for one KDA layer's chunked (WY)
prefill of one piece of prompts (``q``, ``k``, ``v`` in once, the
log-decay in and the output out once, the state out once; a token a
head three products with the carried state and the chunk's pairwise
sums, solve and read-out: ``roofline_delta_moe.delta_chunk_needs``)
over its device time in the trace, in percent.

**Which events.**  The chunked form is no kernel: it is plain ``jnp``
under ``lax.scan``, one scan a layer a piece, and a scan is one
``while`` event around its body's fusions.  This reader takes the
``while`` events inside the window whose result carries the state the
chunks hand on — ``f32[piece rows, heads, head_dim, head_dim]``, a
shape no other loop of the program carries — and reads the median of
their durations: the fusions of the chunked form and nothing else.  A
kernel named ``delta_chunk`` is read in their place where the trace
holds one."""

LAYER = "step program (kernels and fusions)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"
KERNEL = "delta_chunk"


def read(run):
    import re

    from chipbench.readings import quantile
    from chipbench.roofline import least_time_s
    from chipbench.roofline_delta_moe import delta_chunk_needs, kda_shape
    from chipbench.trace import op_kind
    t, c = run.trace, run.counters
    if t is None or run.peaks is None or not c.get("delta_layers") \
            or not c.get("prefill_piece_rows"):
        return None
    a = c["model_args"]
    heads, hd = kda_shape(a)
    rows = int(c["prefill_piece_rows"])
    carried = re.compile(rf"f32\[{rows},{heads},{hd},{hd}\]")
    lo, hi = t.window
    inside = [(name, e - s) for name, s, e in t.devices[0].ops
              if s >= lo and e <= hi]
    calls = [d for name, d in inside if op_kind(name) == KERNEL] or [
        d for name, d in inside if op_kind(name) == "while"
        and carried.search(name.split(" while(", 1)[0])]
    if not calls:
        return None
    flops, nbytes = delta_chunk_needs(
        a, rows, c["prefill_tokens"] / c["rows"], a.get("chunk", 64),
        c["weight_bytes"])
    least, _bound = least_time_s(flops, nbytes, run.peaks)
    return 100.0 * least / quantile(calls, 0.5)
