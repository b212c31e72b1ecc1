"""The convolution window's format: how the last inputs of a short
depthwise causal convolution are kept on the device, moved on and read —
a per-sequence memory of its own (:class:`ConvWindowFormat`), and the
half of a state-space layer's memory that ``ops/ssm.py``'s two formats
share (:class:`Window`; they add the recurrent state ``h``).

A causal convolution of ``d_conv`` taps over ``W`` columns

    c(t) = sum_j w[j] * u(t - d_conv + 1 + j)

needs of a sequence's past its last ``d_conv - 1`` inputs and nothing
else, whatever the text's length: a gated short-convolution mixer
(``models/decoder.py::ConvWindowBlock``; LFM2's keeps 2 rows of 2048
values, 8 KB in bfloat16, a sequence a layer) has **no other memory**.

**The format.**  One buffer a layer, behind a leading ``groups`` axis for
the ring: ``conv [d_conv - 1, batch, W]`` in the compute type — the taps
lead, so a tap is ``[batch, W]`` of whole tiles (``[batch, 2, W]`` would
pad 2 sublanes to 16).  ``conv[j]`` is the input ``d_conv - 1 - j``
positions back; before a sequence's start it is zero.

Like a retention state and unlike a KV cache it has **no scratch group
and no scratch row**: a pipeline's bubble keeps the window as it is,
which :meth:`Window.shift` and :meth:`Window.prefill_shift` make of a
call whose ``valid`` is false.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from .layered import LayeredState, nbytes


class Window(LayeredState):
    """The window's buffer and its three calls (a step's shift, a
    prompt's, the bubble as an identity update), for a format that
    names ``d_conv``, ``conv_width``, ``dtype`` and ``groups`` and keeps
    the window under the key ``conv`` — alone
    (:class:`ConvWindowFormat`) or beside a state (``ops/ssm.py``)."""

    def window_buffer(self, batch: int) -> jax.ShapeDtypeStruct:
        """One layer's window for ``batch`` sequences (a group)."""
        lead = () if self.groups is None else (self.groups,)
        return jax.ShapeDtypeStruct(
            lead + (self.d_conv - 1, batch, self.conv_width), self.dtype)

    def window_bytes(self, batch: int, stages: int) -> int:
        """The windows' bytes of one layer on each of ``stages`` stages."""
        return stages * nbytes(self.window_buffer(batch))

    # -- where a ring step's memory goes: a bubble is an identity update

    @staticmethod
    def decode_slot(valid, pos):
        """What :meth:`shift` (and a state's step) take as ``valid``;
        the position is not part of a window's address."""
        del pos
        return valid

    @staticmethod
    def prefill_slot(valid, group, row=None):
        """What the prefill calls take as ``slot``: the group and
        whether the call is real; with ``row``, also the sequence of
        the group from which a piece's prompts lie."""
        return (group, valid) if row is None else (group, valid, row)

    # -- one token a sequence ------------------------------------------------

    def shift(self, u, layer: dict, group=None, valid=True):
        """The convolution's taps for one token of every sequence (of
        group ``group``), and the window moved on by it: ``u`` [b, W]
        the position's input -> ``(taps, layer)``, ``taps`` the
        ``d_conv`` inputs ``[b, W]`` the convolution reads, oldest
        first, ``u`` itself the last.  With ``valid`` false the window
        is kept as it is."""
        bufs, group = self._group(layer, group)
        at = (group[0], 0, 0, 0)
        win = lax.dynamic_slice(bufs["conv"], at,
                                (1,) + bufs["conv"].shape[1:])[0]
        u = u.astype(win.dtype)
        new = jnp.concatenate([win[1:], u[None]], axis=0)
        new = jnp.where(valid, new, win)
        conv = lax.dynamic_update_slice(bufs["conv"], new[None], at)
        return [win[j] for j in range(self.d_conv - 1)] + [u], \
            self._ungroup(dict(bufs, conv=conv))

    # -- a whole prompt ---------------------------------------------------------

    def prefill_shift(self, u, layer: dict, slot=(None, True)):
        """The taps of a whole prompt ``u`` [b, t, W] from an empty
        window, and the window after its last position left where
        ``slot`` says: ``(taps, layer)``, ``taps`` the ``d_conv`` arrays
        ``[b, t, W]``, the input ``d_conv - 1 - j`` positions back under
        ``j`` (zero before the prompt's start)."""
        group, valid, row = slot if len(slot) == 3 else (*slot, 0)
        k = self.d_conv - 1
        b, t, e = u.shape
        bufs, group = self._group(layer, group)
        u = u.astype(bufs["conv"].dtype)
        padded = jnp.pad(u, ((0, 0), (k, 0), (0, 0)))
        taps = [lax.slice_in_dim(padded, j, j + t, axis=1)
                for j in range(k + 1)]
        at = (group[0], 0, row, 0)
        old = lax.dynamic_slice(bufs["conv"], at, (1, k, b, e))
        # taps-major as the buffer holds them: left to itself the
        # compiler keeps the prompt's last inputs sequence-major (what
        # the slice before them liked) and, the write needing one layout
        # on both sides, converts the *buffer* there and back
        last = with_layout_constraint(padded[:, t:].swapaxes(0, 1),
                                      Layout(major_to_minor=(0, 1, 2)))
        new = jnp.where(valid, last[None], old)
        conv = lax.dynamic_update_slice(bufs["conv"], new, at)
        return taps, self._ungroup(dict(bufs, conv=conv))


@dataclasses.dataclass(frozen=True)
class ConvWindowFormat(Window):
    """One layer's memory of a gated short-convolution mixer, described:
    the window and nothing else (``zeros``, ``layer`` and ``with_layer``
    are ``ops/layered.py``'s)."""

    #: columns the convolution runs over
    conv_width: int
    d_conv: int
    #: the window's type, the block's compute type
    dtype: Any
    #: the ring's round-robin groups (a leading axis); None for one batch
    groups: int | None = None

    keys = ("conv",)

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffer for ``batch`` sequences (a group), by key."""
        return {"conv": self.window_buffer(batch)}

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The layer's bytes under the kind's own name: the window is
        all of them."""
        return {"decode.conv.window_bytes": self.window_bytes(batch, stages)}


def dense_window(conv):
    """A layer's window of one group on the host in the form that knows
    no layout: ``conv`` [d_conv - 1, b, W] -> ``[b, d_conv - 1, W]``,
    oldest input first."""
    return np.swapaxes(np.asarray(conv), 0, 1)
