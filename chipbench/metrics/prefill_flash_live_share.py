"""Grid steps of the prefill's causal attention kernel that hold a live
(query, key) block over all it walks (the gauges
``prefill.flash.live_steps`` over ``prefill.flash.grid_steps``, of the
newest call traced in set-up).  1.0: the grid lists live pairs only."""

LAYER = "step program (kernels and fusions)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("prefill_flash_grid_steps"):
        return None
    return c["prefill_flash_live_steps"] / c["prefill_flash_grid_steps"]
