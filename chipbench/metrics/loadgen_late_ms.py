"""How late the benchmark's own client sent a request against its due
time, 95th percentile, in milliseconds: a starved generator must not
be read as a fast server."""

LAYER = "benchmark client (chipbench/drivers/serve_decode.py)"
SOURCE = "host_clock"
MOVES = "answer_ms_per_token_p90"


def read(run):
    return run.counters.get("late_ms_p95")
