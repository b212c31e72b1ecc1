"""chipbench — the on-chip benchmark of defer_tpu (see ``BENCHMARK.json``).

One command runs one cell once::

    python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one kind
of traffic or one per-layer metric is a file of its own, found by the
name ``BENCHMARK.json`` gives (``configs/``, ``traffic/``, ``drivers/``,
``metrics/``); the yardstick (arrival times, quantiles, peaks, flop and byte
functions, the plain references, the trace reduction) lives here too, so
a change to the program cannot move it.  Importing this package imports
nothing else: ``jax`` and ``defer_tpu`` load when a cell runs.
"""
