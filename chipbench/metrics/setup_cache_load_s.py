"""Seconds of set-up that went to the loads of set-up's programs from the persistent compile cache: the
compile listener's backend events that held a retrieval.

Exclusive seconds of ``defer_tpu.obs.profile.setup_breakdown()``, as the
program froze them when set-up ended (the end of the warm-up generation,
or of the engine's first busy period): with the nine other ``setup_*_s``
and the set-up line's ``first_call_s`` they sum to its ``elapsed_s``.
``None`` from a tree that has no such function, or no interval."""

LAYER = ("set-up (defer_tpu/__init__.py, runtime/decode.py, "
         "serve/engine.py, obs/profile.py)")
SOURCE = "program_counter"
MOVES = "setup_s"


def read(run):
    try:
        from defer_tpu.obs.profile import setup_breakdown
    except ImportError:
        return None
    parts = setup_breakdown()
    return parts["cache_load_s"] if parts else None
