"""What every per-sequence memory of the decode engines shares, whatever
it holds: a *layer* is a dict of device buffers by key, and the *state*
of several layers a dict of tuples, one buffer a layer under each key.
The layers are never stacked into one array: XLA:TPU wraps a write into
a value that large in copies of all of it (docs/DECODE_CLIFF.md).  A
holder may keep entries of its own beside the format's in the same
dict; the format passes them through.

A format (``ops/kv_cache.py::KVCacheFormat``,
``ops/retention.py::RetentionFormat``) says what the buffers are
(``buffers(batch)``, ``keys``) and is the one place that writes and
reads them.
"""

from __future__ import annotations

import math

import jax.numpy as jnp


class LayeredState:
    """``zeros`` / ``layer`` / ``with_layer`` over a format's own
    ``buffers(batch)`` and ``keys``."""

    def zeros(self, batch: int, layers: int, lead: tuple = ()) -> dict:
        """The empty state of ``layers`` layers: a tuple of buffers under
        each key, each behind the holder's own axes ``lead``."""
        return {key: tuple(jnp.zeros(lead + s.shape, s.dtype)
                           for _ in range(layers))
                for key, s in self.buffers(batch).items()}

    def layer(self, state: dict, l: int) -> dict:
        """Layer ``l``'s buffers out of a state."""
        return {key: state[key][l] for key in self.keys}

    @staticmethod
    def with_layer(state: dict, l: int, layer: dict) -> dict:
        """``state`` with layer ``l``'s buffers replaced."""
        return dict(state, **{
            key: state[key][:l] + (buf,) + state[key][l + 1:]
            for key, buf in layer.items()})

    def state_bytes(self, batch: int, layers: int) -> int:
        """Bytes of ``layers`` layers' buffers for ``batch`` sequences."""
        return layers * sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
                            for s in self.buffers(batch).values())


def zeros_by_layer(formats, batch: int, lead: tuple = ()) -> dict:
    """The empty state of a holder whose layers each have a format of
    their own (``formats[l]`` layer ``l``'s; all of one kind, so of the
    same keys): what :meth:`LayeredState.zeros` gives where they are
    all alike."""
    shapes = [fmt.buffers(batch) for fmt in formats]
    return {key: tuple(jnp.zeros(lead + s[key].shape, s[key].dtype)
                       for s in shapes)
            for key in shapes[0]}
