"""Milliseconds of one engine prefill: the median ``engine.prefill`` span
of the traced window (one joined request's prompt through the prefill
programs, from the upload of its ids until its rows are written).  A
program without the span (the parent of the PR that added it) gives
nothing."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    from chipbench.readings import quantile
    from chipbench.spans import durations
    prefills = durations(run.trace, "engine.prefill") if run.trace else []
    if not prefills:
        return None
    return 1e3 * quantile(prefills, 0.5)
