"""The latent cache's format: how a layer of latent attention (MLA)
keeps what a sequence has seen, and how a step attends over it.

Such a layer keeps **one row a position**: the normalised latent ``c``
(``latent`` columns) and behind it the one rotated key every head shares
(``rope`` columns) — 512 + 64 where expanded heads would keep 64 x 320.
The row is a position's key *whole* and its value *by its first*
``latent`` *columns*: a step's queries arrive already absorbed into the
latent space (``models/decoder.py::LatentBlock``), every head reads the
same row, and the output leaves in the latent space too.

**The layout.**  One buffer a layer under the key ``latent``,
``[batch, positions, padded]`` (behind ``groups + 1`` with the ring's
scratch group, and one more position, its scratch row: the bookkeeping
is ``ops/kv_cache.py::RingRows``'s, shared with the KV cache), the
positions rounded up to whole sublane tiles.  ``padded`` is the row's
width rounded up to whole lane tiles: 576 columns are 4.5 lane tiles,
and XLA:TPU lays a ``[.., positions, 576]`` buffer out in tiles of 128
lanes, so the half tile is padded to 640 in memory whatever the shape
says; two buffers of 512 and 64 would pad the second to 128, the same
640.  The format says 640 itself: the bytes a step reads are then what
the gauge ``decode.cache.latent_bytes`` counts (1280 B a row, 1.11 of
the 1152 B the row needs), every product contracts over whole tiles and
the padding columns hold zeros, which add nothing to a score and are
never read as values.

**Sublayers.**  A block that holds several latent-attention sublayers
(``models/longcat_flash.py``: two a block, around one shortcut-connected
MoE) keeps one such buffer *a sublayer*: the format's ``sublayers``, the
buffers under ``latent``, ``latent_1``, ...  They are written,
bulk-written and attended separately, each call naming its ``sublayer``;
the slots, the scratch row and the scratch group are the layer's, one
for all of them.

**The two writes** the ring makes: :meth:`LatentCacheFormat.write_position`
(one position for every sequence: one ``lax.dynamic_update_slice``) and
:meth:`LatentCacheFormat.write_prefix` (a whole prompt for a group or a
piece of one: one bulk write).  No serving engine holds this format, so
it has no write of a position a sequence.

**The attention**, :meth:`LatentCacheFormat.attend`: the Pallas kernel
:func:`latent_attend`.  A row block is fetched once and multiplied
twice — ``[heads, padded] x [padded, rows]`` for the scores, then
``[heads, rows] x [rows, latent]`` on the same block's first columns —
where two buffers, or ``kv_attend_joined`` on a copy of the latent,
would read 1088 values a row for the 576 needed.  :func:`attend_einsum`
is the oracle the tests hold it to.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from .kv_cache import (_BLOCK_BYTES, _JOINED_ROWS, _LANES, RingRows,
                       _group_slice)


def _attend_kernel(group_ref, pos_ref, q_ref, rows_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, tl, latent, scale):
    """One row block of one sequence, every head at once: ``q_ref``
    ``[1, heads, padded]``, ``rows_ref`` ``[1, 1, tl, padded]`` as the
    buffer lies, ``o_ref`` ``[1, heads, latent]``.  The scores are
    ``[heads, padded] x [padded, tl]`` and the output ``[heads, tl] x
    [tl, latent]`` on the matrix unit, operands in the rows' type,
    accumulated in f32; the online softmax between them runs on
    ``[heads, tl]``.  m_ref / l_ref ``[heads, 128]`` (a row's scalar on
    every lane), acc_ref ``[heads, latent]``."""
    del group_ref                       # the index maps read it
    t = pl.program_id(1)
    pos = pos_ref[pl.program_id(0)]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(ragged):
        rows = rows_ref[0, 0]
        v = rows[:, :latent]
        s = lax.dot_general(q_ref[0], rows, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if ragged:
            # a dead position's row may hold anything (the scratch row,
            # a block's overhang): 0 x NaN must not reach a sum
            s = jnp.where(t * tl + lax.broadcasted_iota(
                jnp.int32, s.shape, 1) <= pos, s, -jnp.inf)
            v = jnp.where(t * tl + lax.broadcasted_iota(
                jnp.int32, v.shape, 0) <= pos, v, jnp.zeros_like(v))
        # block 0 always holds a live position: from the first block on
        # the running max is finite
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # a block wholly past ``pos`` is not computed (nor fetched: its
    # index map names a block that is wanted next); only the block that
    # holds ``pos`` pays for masks
    pl.when((t + 1) * tl - 1 <= pos)(lambda: accumulate(False))
    pl.when(jnp.logical_and(t * tl <= pos, (t + 1) * tl - 1 > pos))(
        lambda: accumulate(True))

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def block_rows(padded: int, length: int, itemsize: int) -> int:
    """Positions of one block of :func:`latent_attend`: as many lane
    rows of them as ``ops/kv_cache.py``'s block bytes hold."""
    return min(length, max(_LANES, _BLOCK_BYTES // (padded * itemsize)
                           // _LANES * _LANES))


@functools.partial(jax.jit, static_argnames=("latent", "scale"))
def latent_attend(q, buf, pos, group, *, latent: int, scale: float):
    """Every head's absorbed query over one sequence's live rows: ``q``
    ``[b, heads, padded]`` against ``buf`` ``[groups, b, L, padded]`` as
    it is stored, sequence ``i`` of group ``group`` [1] over its rows
    ``<= pos[i]`` ([b], int32, in ``[0, L)``).  Scores are ``q . row *
    scale``, the softmax exact and in f32, online over row blocks; the
    output ``[b, heads, latent]`` is the weighted sum of the rows' first
    ``latent`` columns, in ``q``'s type.

    The grid runs (sequence, row block); a block is whole rows as they
    lie, so its DMA is one contiguous run; a block past ``pos[i]`` is
    neither fetched nor computed (its index names the first block of
    the grid's next sequence, which is then fetched behind the last
    live one's products).  Each row is read once and serves both
    products.  Jitted for the reason ``write_kv_rows`` is."""
    b, heads, padded = q.shape
    groups, _, length, _ = buf.shape
    tl = block_rows(padded, length, buf.dtype.itemsize)
    pos = jnp.clip(pos.astype(jnp.int32), 0, length - 1)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)

    def head_block(i, t, group_ref, pos_ref):
        return (i, 0, 0)

    def row_block(i, t, group_ref, pos_ref):
        more = jnp.logical_and(t > pos_ref[i] // tl, i + 1 < b)
        i = jnp.where(more, i + 1, i)
        t = jnp.where(more, 0, jnp.minimum(t, pos_ref[i] // tl))
        return (group_ref[0], i, t, 0)

    return pl.pallas_call(
        functools.partial(_attend_kernel, tl=tl, latent=latent, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, pl.cdiv(length, tl)),
            in_specs=[pl.BlockSpec((1, heads, padded), head_block),
                      pl.BlockSpec((1, 1, tl, padded), row_block)],
            out_specs=pl.BlockSpec((1, heads, latent), head_block),
            scratch_shapes=[pltpu.VMEM((heads, _LANES), jnp.float32),
                            pltpu.VMEM((heads, _LANES), jnp.float32),
                            pltpu.VMEM((heads, latent), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, latent), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="latent_attend",
    )(group, pos, q, buf)


def attend_einsum(q, item, pos, *, latent: int, scale: float):
    """:func:`latent_attend` as two plain einsums over one group's
    buffer ``item`` ``[b, L, padded]``: the oracle the tests hold the
    kernel to.  ``pos`` a scalar or one a sequence."""
    rows = item.astype(jnp.float32)
    att = jnp.einsum("bhw,blw->bhl", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(item.shape[1])[None, None, :] \
        <= jnp.reshape(pos, (-1, 1, 1))
    att = jax.nn.softmax(jnp.where(live, att, -jnp.inf), axis=-1)
    # a dead row may hold anything: it is not a value
    rows = jnp.where(live[:, 0, :, None], rows[..., :latent], 0.0)
    return jnp.einsum("bhl,blc->bhc", att, rows).astype(q.dtype)


@dataclasses.dataclass(frozen=True)
class LatentCacheFormat(RingRows):
    """One layer's latent cache, described (the module docstring);
    ``zeros``, ``layer`` and ``with_layer`` are ``ops/layered.py``'s,
    the slots ``ops/kv_cache.py::RingRows``'s."""

    #: columns of the normalised latent: what a row is as a value
    latent: int
    #: columns of the shared rotated key behind it
    rope: int
    #: positions a sequence may hold
    positions: int
    #: the rows' float type
    dtype: Any
    #: what a score is multiplied by (the block's: it is not one over
    #: the root of any width the format knows)
    scale: float
    #: the ring's round-robin groups (a leading axis, with the scratch
    #: group and the scratch row); None for slots alone
    groups: int | None = None
    #: row buffers a layer keeps, one a latent-attention sublayer of the
    #: block (the module docstring)
    sublayers: int = 1

    @property
    def keys(self) -> tuple:
        """A buffer a sublayer: ``latent``, ``latent_1``, ..."""
        return ("latent",) + tuple(
            f"latent_{i}" for i in range(1, self.sublayers))

    @property
    def width(self) -> int:
        """Columns of a row as the block hands it over."""
        return self.latent + self.rope

    @property
    def padded(self) -> int:
        """Columns of a row as the buffer holds it: whole lane tiles."""
        return -(-self.width // _LANES) * _LANES

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group): one a
        sublayer, all alike."""
        lead, length = self._buffer_rows()
        buf = jax.ShapeDtypeStruct(
            lead + (batch, -(-length // _JOINED_ROWS) * _JOINED_ROWS,
                    self.padded), self.dtype)
        return {key: buf for key in self.keys}

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The layer's buffers as they are laid out, the rows (of a
        sequence, a group, a sublayer) those bytes are — their quotient
        is what a live row costs a step to read — and the buffers."""
        rows = sum(math.prod(buf.shape[:-1])
                   for buf in self.buffers(batch).values())
        return {"decode.cache.latent_bytes":
                stages * self.state_bytes(batch, 1),
                "decode.cache.latent_positions": stages * rows,
                "decode.cache.latent_sublayers": stages * self.sublayers}

    def _pad(self, a):
        """``a [..., width]`` with zeros up to the buffer's columns."""
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                       + [(0, self.padded - self.width)])

    def rows(self, row) -> dict:
        """A block's new rows ``[b, width]``, one position a sequence,
        as :meth:`write_position` takes them."""
        return {"latent": self._pad(row)[:, None]}

    def write_position(self, layer: dict, rows: dict, pos, group=None,
                       sublayer: int = 0):
        """``rows`` written in place at the one position ``pos`` of
        every sequence (of group ``group``, where the format has
        groups) of sublayer ``sublayer``'s buffer: the layer."""
        key = self.keys[sublayer]
        buf = layer[key]
        lead = () if group is None else (group,)
        row = lax.expand_dims(rows["latent"], range(len(lead)))
        return dict(layer, **{key: lax.dynamic_update_slice(
            buf, row.astype(buf.dtype), lead + (0, pos, 0))})

    def write_prefix(self, layer: dict, rows, slot,
                     sublayer: int = 0) -> dict:
        """A whole prompt's rows ``[b, t, width]`` written at positions
        ``0..t-1`` of sublayer ``sublayer``'s buffer where ``slot``
        (``prefill_slot``'s) says — a group, or a group and the
        sequence of it the ``b`` prompts start at; in a format without
        groups the sequence alone: one bulk write."""
        if self.groups is None:
            at = (slot,)
        else:
            at = slot if isinstance(slot, tuple) else (slot, 0)
        key = self.keys[sublayer]
        buf = layer[key]
        # rows as the buffer holds them: left to itself the compiler may
        # produce them positions-minor and convert the *buffer* around
        # the write (``KVCacheFormat.write_prefix``'s joined rows)
        rows = with_layout_constraint(self._pad(rows),
                                      Layout(major_to_minor=(0, 1, 2)))
        if self.groups is not None:
            rows = rows[None]
        return dict(layer, **{key: lax.dynamic_update_slice(
            buf, rows.astype(buf.dtype), at + (0,) * (buf.ndim - len(at)))})

    def item(self, layer: dict, group=None, sublayer: int = 0):
        """One group's buffer ``[b, L, padded]`` of one sublayer: what
        :func:`attend_einsum` reads."""
        buf = layer[self.keys[sublayer]]
        return buf if group is None else _group_slice(buf, group)[0]

    def step(self, q, layer: dict, rows: dict, pos, group=None,
             sublayer: int = 0):
        """``RingRows.step`` on one sublayer's buffer: the row written
        at ``pos``, then ``q`` over that buffer's rows ``<= pos``."""
        layer = self.write_position(layer, rows, pos, group=group,
                                    sublayer=sublayer)
        return self.attend(q, layer, pos, group=group,
                           sublayer=sublayer), layer

    def attend(self, q, layer: dict, pos, group=None, sublayer: int = 0):
        """Every head's absorbed query ``q`` ``[b, heads * width]`` over
        the rows of ``layer``'s sublayer ``sublayer`` (group ``group``'s
        sequences, where the format has groups), positions ``<= pos``
        live — ``pos`` a scalar or [b]; returns the heads' outputs in
        the latent space, ``[b, heads * latent]``.
        :func:`latent_attend`."""
        b = q.shape[0]
        buf = layer[self.keys[sublayer]]
        if group is None:
            buf, group = buf[None], 0
        q = self._pad(q.reshape(b, -1, self.width)).astype(buf.dtype)
        out = latent_attend(
            q, buf, jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)),
            jnp.asarray(group, jnp.int32).reshape(1),
            latent=self.latent, scale=self.scale)
        return out.reshape(b, -1)
