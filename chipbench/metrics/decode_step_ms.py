"""Device milliseconds of one decode step: the median run of the decode
chunk program (``device_decode``) in the trace over the steps it holds."""

LAYER = "decode ring (runtime/decode.py)"
SOURCE = "device_trace"
MOVES = "tokens_per_s"


def read(run):
    from chipbench.readings import quantile
    runs = run.trace.module_runs(r"device_decode") if run.trace else []
    if not runs:
        return None
    return 1e3 * quantile(runs, 0.5) / run.counters["steps_per_reading"]
