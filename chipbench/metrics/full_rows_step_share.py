"""The full layers' cached rows as a share of the bytes a decode step
needs, in percent: the rows the newest step's attention read in the
layers that keep every position (the program's gauge
``decode.cache.full_rows_read``, from positions and shapes) times a
row's bytes, over ``roofline_rotary_window_moe.step_bytes_by_part`` at
that step's position and the window's ``experts_hit_share``.  The
number that says which mechanism does the work: two layers in eight
hold nine tenths of the cache a step reads."""

LAYER = "step program (kernels and fusions)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    from chipbench import roofline_rotary_window_moe as rr
    c = run.counters
    if not c.get("cache_full_rows_read") or "experts_hit_share" not in c:
        return None
    a = c["model_args"]
    _n_window, n_full = rr.layer_kinds(a)
    # the step's position, as the gauge counted it
    positions = c["cache_full_rows_read"] / (n_full * c["rows"])
    parts = rr.step_bytes_by_part(
        a, rows=c["rows"], positions=positions,
        experts_hit_share=c["experts_hit_share"],
        weight_bytes=c["weight_bytes"], kv_bytes=c["kv_bytes"])
    read_rows = c["cache_full_rows_read"] * rr.row_bytes(a, c["kv_bytes"])
    return rr.share_of(read_rows, sum(parts.values()),
                       "the full layers' rows")
