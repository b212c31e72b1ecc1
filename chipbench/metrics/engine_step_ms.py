"""Mean of ``serve.decode.step_s`` over the window's steps, in
milliseconds: one engine step from the launch to the sampled ids in
host memory (the histogram's exact sum over its count; its buckets
are 9% wide, so no quantile is read from it)."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    c = run.counters
    if not c.get("step_count"):
        return None
    return 1e3 * c["step_s_sum"] / c["step_count"]
