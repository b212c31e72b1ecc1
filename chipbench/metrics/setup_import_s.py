"""Seconds of set-up that went to ``import defer_tpu`` (and ``import jax`` where the package was the
first to ask for it): the ``setup.import`` span.

Exclusive seconds of ``defer_tpu.obs.profile.setup_breakdown()``, as the
program froze them when set-up ended (the end of the warm-up generation,
or of the engine's first busy period): with the nine other ``setup_*_s``
and the set-up line's ``first_call_s`` they sum to its ``elapsed_s``.
``None`` from a tree that has no such function, or no interval."""

LAYER = ("set-up (defer_tpu/__init__.py, runtime/decode.py, "
         "serve/engine.py, obs/profile.py)")
SOURCE = "program_span"
MOVES = "setup_s"


def read(run):
    try:
        from defer_tpu.obs.profile import setup_breakdown
    except ImportError:
        return None
    parts = setup_breakdown()
    return parts["import_s"] if parts else None
