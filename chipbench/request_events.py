"""The window's finished requests, as the program's own flight recorder
kept them: one ``decode_done`` event a request that the front door
answered (``defer_tpu/serve/frontdoor.py::_record_decode``), its life in
milliseconds from admitted — ``popped_ms`` (the engine's loop took it off
the queue), ``prefill_ms`` (its prompt's pass begins to be launched),
``first_ms`` / ``last_ms`` (its first and its last generated id in host
memory), ``delivered_ms`` (its answer written) — with ``new_tokens``,
``forced_steps``, ``pass_rounds`` and ``worst_gap_ms``.

The four readers over them (``engine_first_token_ms``,
``engine_token_gap_ms``, ``engine_worst_gap_ms``, ``door_result_edge_ms``)
take exact quantiles over the records (``chipbench.readings.quantile``),
never off a histogram's 9% buckets.  They say where a request's time
goes; what makes the tail is not read here: the traced window is 8 s,
about 45 requests, and a number over the four or five beyond its p90 (or
the eleven beyond p75) swings by a third with the lengths of the answers
that happen to lie there (PERF.md section 6, PR 69).
``scripts/serve_request_table.py`` lays a 40 s window's tail out.
"""

#: the tenant ``drivers/serve_decode.py::measure`` plays the window under
#: (its warm-up's and its check's requests run under others)
WINDOW_TENANT = "bench"


def finished() -> list | None:
    """The ``data`` of every ``decode_done`` event of the window's tenant
    that the process's recorder holds, oldest first.  ``None`` where there is
    nothing to read a number from: a tree without the event (the parent
    of the PR that added it), a ring that has dropped events (the sample
    would be a cut one), a window that finished no request."""
    try:
        from defer_tpu.obs.events import EVENT_KINDS, recorder
    except ImportError:
        return None
    if "decode_done" not in EVENT_KINDS:
        return None
    ring = recorder()
    if ring.dropped:
        return None
    done = [e["data"] for e in ring.snapshot()
            if e["kind"] == "decode_done"
            and e["data"].get("tenant") == WINDOW_TENANT]
    return done or None

