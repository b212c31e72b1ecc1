"""Rows a touched expert multiplies in a decode step, a routed layer:
the program's counters ``decode.moe.assignments`` over
``decode.moe.experts_hit`` summed over the window's decode steps (128
sequences x 4 choices over 64 experts all touched: 8.0 expected).  What
holds the cell to the load it was sized for — the upper end of the step
kernel's range — and no thing to tune: the number is the router's and
the batch's, so ``better`` only says which way the kernel's bytes a row
fall."""

LAYER = "step program (kernels and fusions)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("decode.moe.experts_hit") or not c.get("conv_layers"):
        return None
    return c["decode.moe.assignments"] / c["decode.moe.experts_hit"]
