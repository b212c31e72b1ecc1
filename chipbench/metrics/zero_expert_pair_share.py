"""The (token, choice) pairs of the window's decode steps that fell to
zero-compute experts, over all the router's assignments, in percent
(the program's counters ``decode.moe.zero_assignments`` over
``decode.moe.assignments``): the share of a step's routing that costs
no product and no weight byte.  A uniform router over 512 routed and
256 zero columns gives a third; a trained one sets it by its bias.
Higher is cheaper, so ``better`` is ``higher`` — but the number is the
router's and the traffic's, not a thing to tune."""

LAYER = "step program (kernels and fusions)"
SOURCE = "program_counter"
MOVES = "tokens_per_s"


def read(run):
    c = run.counters
    if not c.get("decode.moe.assignments") \
            or "decode.moe.zero_assignments" not in c:
        return None
    return 100.0 * c["decode.moe.zero_assignments"] \
        / c["decode.moe.assignments"]
