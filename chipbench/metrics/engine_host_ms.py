"""Host milliseconds an engine step costs outside the device wait: the
gather, dispatch, sync and delivery phase histograms, summed, per step."""

LAYER = "decode engine (serve/engine.py)"
SOURCE = "program_span"
MOVES = "answer_ms_per_token_p50"


def read(run):
    c = run.counters
    if not c.get("step_count"):
        return None
    host = sum(c[f"{p}_s_sum"] for p in ("gather", "dispatch", "sync", "delivery"))
    return 1e3 * host / c["step_count"]
