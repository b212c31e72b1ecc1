"""What every per-sequence memory of the decode engines shares, whatever
it holds: a *layer* is a dict of device buffers by key, and the *state*
of several layers a dict of tuples, one entry a layer under each key: a
buffer, or None where that layer's format has no such key (a
state-space layer's ``conv`` and ``h`` beside an attention layer's ``k``
and ``v``: layers of unlike formats lie side by side, each reached
through its own format alone; a layer that keeps nothing a sequence —
a feed-forward part alone — has :class:`NoMemory`, no key at all, and
its entry in every tuple is None).  The layers are never stacked into one
array: XLA:TPU wraps a write into a value that large in copies of all
of it (docs/DECODE_CLIFF.md).  A holder may keep entries of its own
beside the formats' in the same dict; a format passes them through.

A format (``ops/kv_cache.py::KVCacheFormat``,
``ops/retention.py::RetentionFormat``, ``ops/ssm.py::SsmFormat``,
``ops/conv_window.py::ConvWindowFormat``) says
what the buffers are (``buffers(batch)``, ``keys``) and is the one
place that writes and reads them — and the one that says what they are
to an observer: the gauges of its kind (``gauges``) and what a step
read of them (``rows_read``), under names of its own.  A holder adds
up what its layers' formats say (:func:`totals`) and spells no kind.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax


def nbytes(s) -> int:
    """Bytes of one buffer (a ``ShapeDtypeStruct`` or an array)."""
    return math.prod(s.shape) * jnp.dtype(s.dtype).itemsize


class LayeredState:
    """``zeros`` / ``layer`` / ``with_layer`` over a format's own
    ``buffers(batch)`` and ``keys``."""

    #: the names of :meth:`gauges` that measure a layer and are no
    #: amount: over layers the largest stands, where the others add up
    largest = frozenset()

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
    def idle(layer: dict) -> dict:
        """``layer``'s buffers as a stage hands them on that holds no
        such layer (a shorter stage of the ring: the buffers are the
        longer stages', sharded over all, and dead storage here): each
        with one element rewritten in place.  A buffer a branch of a
        conditional only passes through is copied whole on its way out
        (XLA:TPU, every step: 49 MB a layer in the four-chip cell,
        0.11 ms of a 1.4 ms ring step); one that every branch writes in
        place is handed on where it lies."""
        return {key: lax.dynamic_update_slice(
            buf, jnp.zeros((1,) * buf.ndim, buf.dtype), (0,) * buf.ndim)
            for key, buf in layer.items()}

    @staticmethod
    def with_layer(state: dict, l: int, layer: dict) -> dict:
        """``state`` with layer ``l``'s buffers replaced."""
        return dict(state, **{
            key: state[key][:l] + (buf,) + state[key][l + 1:]
            for key, buf in layer.items()})

    # -- for a format whose buffers lie behind a ``groups`` axis or none

    def _group(self, layer: dict, group):
        """``layer``'s buffers behind a group axis (added where the
        format has no ``groups``), and the group as kernels and slices
        take it: [1] int32."""
        if self.groups is None:
            return {key: buf[None] for key, buf in layer.items()}, \
                jnp.zeros(1, jnp.int32)
        return layer, jnp.asarray(group, jnp.int32).reshape(1)

    def _ungroup(self, layer: dict) -> dict:
        return layer if self.groups is not None else {
            key: buf[0] for key, buf in layer.items()}

    def state_bytes(self, batch: int, layers: int) -> int:
        """Bytes of ``layers`` layers' buffers for ``batch`` sequences."""
        return layers * sum(map(nbytes, self.buffers(batch).values()))

    # -- what a holder posts of this layer, by gauge name

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """What one layer of this format holds for ``batch`` sequences
        a group on each of ``stages`` stages, beyond its bytes (the
        holder's ``decode.<kind>.state_bytes``): the parts and measures
        its kind of memory has names for.  None, unless a format says."""
        del batch, stages
        return {}

    def rows_read(self, rows: int, positions: int) -> dict[str, int]:
        """What the newest step read of this layer for ``rows``
        sequences at ``positions`` positions each, where its kind
        counts that: host integers, from shapes."""
        del rows, positions
        return {}


@dataclasses.dataclass(frozen=True)
class NoMemory(LayeredState):
    """The format of a layer that keeps **nothing** a sequence
    (``models/decoder.py::MemorylessBlock``): no key, no buffer, no
    byte, no gauge.  ``layer`` hands out an empty dict and
    ``with_layer`` hands the state back as it was, so a holder walks
    such a layer as it walks the others and allocates, aliases, idles
    and posts nothing for it; a bubble has nothing to leave alone, so
    both slots are None."""

    #: the ring's round-robin groups, as the other formats name them;
    #: nothing here depends on it
    groups: int | None = None

    keys = ()

    def buffers(self, batch: int) -> dict:
        del batch
        return {}

    @staticmethod
    def decode_slot(valid, pos):
        del valid, pos
        return None

    @staticmethod
    def prefill_slot(valid, group, row=None):
        del valid, group, row
        return None


def totals(formats, ask) -> dict[str, int]:
    """What the formats of a holder's layers (``formats``, one a layer)
    answer to ``ask(fmt)`` — :meth:`LayeredState.gauges` or
    ``.rows_read`` — added up by name; of a name among a format's
    ``largest`` the largest."""
    out: dict[str, int] = {}
    for fmt in formats:
        for name, value in ask(fmt).items():
            out[name] = max(out.get(name, 0), value) \
                if name in fmt.largest else out.get(name, 0) + value
    return out


def shapes_by_layer(formats, batch: int) -> dict:
    """What a holder's state looks like whose layers each have a format
    of their own (``formats[l]`` layer ``l``'s, of any kinds): under
    each key any of them names, a tuple with a layer's
    ``ShapeDtypeStruct``, or None where the layer keeps nothing under
    that key."""
    shapes = [fmt.buffers(batch) for fmt in formats]
    keys = dict.fromkeys(key for s in shapes for key in s)
    return {key: tuple(s.get(key) for s in shapes) for key in keys}


def zeros_by_layer(formats, batch: int, lead: tuple = ()) -> dict:
    """The empty state of :func:`shapes_by_layer`'s shape, each buffer
    behind the holder's own axes ``lead``: what
    :meth:`LayeredState.zeros` gives where the layers are all alike."""
    return jax.tree.map(lambda s: jnp.zeros(lead + s.shape, s.dtype),
                        shapes_by_layer(formats, batch))
