"""The KV cache's format: the one module that knows how a layer's keys
and values are kept on the device, written and attended over.

Both decode engines hold a cache through :class:`KVCacheFormat` and
touch a buffer only through its methods: the ring
(``runtime/decode.py``; every sequence of a group at one position) and
the serving engine (``serve/engine.py``; every slot at its own).  The
blocks (``models/decoder.py``) hand over key and value *columns* and
take back the attention's output; they know nothing of what follows.

**The format.**  One layer is a dict of buffers: ``k`` and ``v``,
head-major ``[batch, kv_heads, positions, head_dim]`` — KV heads lead
so the attention contractions are plain batched dots; a position-major
``[batch, positions, d]`` layout would make XLA materialize a transpose
of the whole cache every step, and under GQA each cache head serves its
whole query group without materializing repeats.  Quantized, the rows
are int8 with one f32 scale a (head, position) under ``ks`` / ``vs``
``[batch, kv_heads, positions]``: the scale is constant over the
contracted head dim, so it folds exactly into the attention's dots, the
int8 rows are read raw and no dequantized copy is ever made.

A pipeline's bubbles (warm-up skew, chunk overshoot, a prefill's fill
and drain) need somewhere to write that nothing reads, so that no step
needs a masked read-modify-write: with ``groups`` the buffers carry a
leading axis of ``groups + 1`` (the ring's round-robin groups and one
scratch group) and one more position, the scratch row.

The *state* of several layers is a dict of tuples, one buffer a layer
under each key: the layers are never stacked into one array, because
XLA:TPU wraps a write into a value that large in copies of all of it
(docs/DECODE_CLIFF.md).  A holder may keep entries of its own beside
the format's in the same dict; the format passes them through.

**The three writes**, each the operation its caller's positions make
cheapest (docs/DECODE_CLIFF.md):

* :meth:`KVCacheFormat.write_position` — one position for every
  sequence: one ``lax.dynamic_update_slice`` a buffer;
* :meth:`KVCacheFormat.write_slots` — a position a sequence: the
  aliased Pallas call :func:`write_kv_rows`;
* :meth:`KVCacheFormat.write_prefix` — a whole prompt for one group:
  one relayout to head-major a prompt, then one bulk write.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a window of the row-writer holds: one lane row
_WINDOW = 128


def _write_kernel(pos_ref, rows_ref, win_ref, out_ref):
    # rows_ref [1, hd, kv]; win_ref / out_ref [1, kv, hd, window]
    kv, hd, window = win_ref.shape[1:]
    at = pos_ref[pl.program_id(0)] % window
    hit = lax.broadcasted_iota(jnp.int32, (hd, window), 1) == at
    for k in range(kv):
        out_ref[0, k] = jnp.where(hit, rows_ref[0, :, k:k + 1], win_ref[0, k])


@jax.jit
def write_kv_rows(cache, rows, pos):
    """``cache`` [b, kv, L, hd] with ``rows[i]`` ([b, kv, 1, hd], cast
    to the cache's type) written at position ``pos[i]`` of sequence
    ``i``; nothing else of the buffer is touched.  ``pos`` [b] int32
    in ``[0, L)``.  The result aliases ``cache``: donate it.

    XLA:TPU keeps such an f32 array with the positions on the lanes
    when ``hd`` is under a lane row (128): ``hd`` 64 would otherwise be
    padded to twice its size.  What the obvious forms of the write cost
    in that layout, at gpt2-xl, 16 sequences, 192 positions
    (docs/DECODE_CLIFF.md, "The engine"):

    * ``jax.vmap`` of a ``dynamic_update_slice`` over the positions is
      a batched scatter: the compiler copies the whole buffer into the
      scatter's layout and back.
    * one scalar-indexed ``dynamic_update_slice`` a sequence touches
      one lane of every tile, which XLA runs as a read-modify-write of
      the sequence's item: 5.6 us a row, 8.4 ms a step for 1,536 rows.

    Here the buffer is viewed as ``[b, kv, hd, L]`` — the same bytes,
    so both ``swapaxes`` compile to bitcasts — and aliased to the
    output.  Each grid step moves the one 128-position window that
    holds its sequence's position through VMEM and replaces one lane of
    it.  Off-TPU the identical kernel runs in interpreter mode, as the
    other kernels of this package do.

    Jitted so that a step program that calls it once a buffer traces
    and lowers the kernel once: 96 separate ``pallas_call`` sites added
    6 s to the serving cell's set-up."""
    b, kv, cache_len, hd = cache.shape
    window = min(_WINDOW, cache_len)

    def at_window(i, pos_ref):
        return (i, 0, 0, pos_ref[i] // window)

    # the rows go in as [b, hd, kv]: a head's row is then a column the
    # kernel spreads over the lanes, and the array is 0.4 MB where
    # [b, kv, hd, 1] would be padded to 128 lanes, 13 MB
    rows = jnp.swapaxes(rows[:, :, 0, :], 1, 2).astype(cache.dtype)
    out = pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b,),
            in_specs=[pl.BlockSpec((1, hd, kv),
                                   lambda i, pos_ref: (i, 0, 0)),
                      pl.BlockSpec((1, kv, hd, window), at_window)],
            out_specs=pl.BlockSpec((1, kv, hd, window), at_window)),
        out_shape=jax.ShapeDtypeStruct((b, kv, hd, cache_len), cache.dtype),
        input_output_aliases={2: 0},
        interpret=jax.default_backend() != "tpu",
        name="kv_write_rows",
    )(pos.astype(jnp.int32), rows, jnp.swapaxes(cache, 2, 3))
    return jnp.swapaxes(out, 2, 3)


def quantize_rows(rows):
    """Symmetric int8 a (head, position) row: [..., hd] float ->
    ([..., hd] int8, [...] f32 scale)."""
    rowf = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rowf), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(rowf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _group_slice(buf, g):
    """Group ``g``'s part ``[1, ...]`` of a ``[groups + 1, ...]`` buffer."""
    return lax.dynamic_slice(buf, (g,) + (0,) * (buf.ndim - 1),
                             (1,) + buf.shape[1:])


@dataclasses.dataclass(frozen=True)
class KVCacheFormat:
    """One layer's cache, described: what both decode engines build
    their buffers from and write and read them through."""

    kv_heads: int
    head_dim: int
    #: positions a sequence may hold
    positions: int
    #: the rows' float type (not read when quantized: the rows are int8)
    dtype: Any
    quantized: bool = False
    #: the ring's round-robin groups (a leading axis, with the scratch
    #: group and the scratch row); None for slots alone
    groups: int | None = None

    @property
    def scratch_position(self) -> int:
        """Where a bubble step writes: a row nothing reads (with
        ``groups`` only)."""
        return self.positions

    @property
    def scratch_group(self) -> int:
        """Where a prefill's bubble writes: a group nothing reads."""
        return self.groups

    # -- buffers ---------------------------------------------------------

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group), by key."""
        lead, length = (), self.positions
        if self.groups is not None:
            lead, length = (self.groups + 1,), self.positions + 1
        scales = lead + (batch, self.kv_heads, length)
        rows = jax.ShapeDtypeStruct(
            scales + (self.head_dim,),
            jnp.int8 if self.quantized else self.dtype)
        out = {"k": rows, "v": rows}
        if self.quantized:
            out["ks"] = out["vs"] = jax.ShapeDtypeStruct(scales, jnp.float32)
        return out

    def zeros(self, batch: int, layers: int, lead: tuple = ()) -> dict:
        """The empty state of ``layers`` layers: a tuple of buffers under
        each key, each behind the holder's own axes ``lead``."""
        return {key: tuple(jnp.zeros(lead + s.shape, s.dtype)
                           for _ in range(layers))
                for key, s in self.buffers(batch).items()}

    @property
    def keys(self) -> tuple:
        """The format's entries of a state (any other is its holder's)."""
        return ("k", "v", "ks", "vs") if self.quantized else ("k", "v")

    def layer(self, state: dict, l: int) -> dict:
        """Layer ``l``'s buffers out of a state."""
        return {key: state[key][l] for key in self.keys}

    @staticmethod
    def with_layer(state: dict, l: int, layer: dict) -> dict:
        """``state`` with layer ``l``'s buffers replaced."""
        return dict(state, **{
            key: state[key][:l] + (buf,) + state[key][l + 1:]
            for key, buf in layer.items()})

    # -- rows ------------------------------------------------------------

    def rows(self, k_new, v_new) -> dict:
        """A block's new key and value columns [b, kv_heads * head_dim],
        one position a sequence, as the writes take them."""
        b = k_new.shape[0]
        rows = {"k": k_new.reshape(b, self.kv_heads, 1, -1),
                "v": v_new.reshape(b, self.kv_heads, 1, -1)}
        if self.quantized:
            rows["k"], rows["ks"] = quantize_rows(rows["k"])
            rows["v"], rows["vs"] = quantize_rows(rows["v"])
        return rows

    # -- the three writes ------------------------------------------------

    def write_position(self, layer: dict, rows: dict, pos, group=None):
        """``rows`` written in place at the one position ``pos`` of
        every sequence (of group ``group``, where the format has
        groups).  Returns the layer and the read-only item
        :meth:`attend` reads: nothing the size of an item is written
        back."""
        lead = () if group is None else (group,)
        out, item = dict(layer), {}
        for key, row in rows.items():
            buf = layer[key]
            row = lax.expand_dims(row, range(len(lead))).astype(buf.dtype)
            at = lead + (0, 0, pos) + (0,) * (row.ndim - len(lead) - 3)
            out[key] = buf = lax.dynamic_update_slice(buf, row, at)
            item[key] = buf if group is None else _group_slice(buf, group)[0]
        return out, item

    def write_slots(self, layer: dict, rows: dict, pos) -> dict:
        """``rows`` written in place, sequence ``i``'s at its own
        position ``pos[i]``: the layer, which is its own item."""
        if self.quantized or self.groups is not None:
            raise NotImplementedError(
                "a position a sequence is written into unquantized "
                "slots only (ROADMAP.md A5)")
        return {key: write_kv_rows(layer[key], rows[key], pos)
                for key in layer}

    def write_prefix(self, layer: dict, k, v, group) -> dict:
        """A whole prompt's key and value columns [b, t, kv_heads *
        head_dim] written at positions ``0..t-1`` of group ``group``:
        one head-major relayout a prompt (amortized), one bulk write a
        buffer."""
        b, t = k.shape[:2]
        shape = (b, t, self.kv_heads, self.head_dim)
        k = k.reshape(shape).transpose(0, 2, 1, 3)
        v = v.reshape(shape).transpose(0, 2, 1, 3)
        new = {"k": k, "v": v}
        if self.quantized:
            new["k"], new["ks"] = quantize_rows(k)
            new["v"], new["vs"] = quantize_rows(v)
        out = dict(layer)
        for key, rows in new.items():
            buf = layer[key]
            out[key] = lax.dynamic_update_slice(
                buf, rows[None].astype(buf.dtype),
                (group,) + (0,) * (buf.ndim - 1))
        return out

    def reparent(self, state: dict, group, parents) -> dict:
        """Beam search: sequence ``i`` of group ``group`` takes over
        sequence ``parents[i]``'s rows, in every layer."""
        def one(buf):
            grp = jnp.take(_group_slice(buf, group), parents, axis=1)
            return lax.dynamic_update_slice(
                buf, grp, (group,) + (0,) * (buf.ndim - 1))

        return {key: tuple(one(b) for b in bufs) if key in self.keys else bufs
                for key, bufs in state.items()}

    # -- attention -------------------------------------------------------

    @staticmethod
    def live_to(pos):
        """Per-sequence positions ``pos`` [b] as :meth:`attend` takes
        them: computed once a step, not once a layer."""
        return pos[:, None, None, None]

    @staticmethod
    def attend(q, item: dict, pos):
        """One query a sequence over its item: ``q`` [b, heads *
        head_dim], positions ``<= pos`` live — ``pos`` a scalar (every
        sequence at one position) or :meth:`live_to` of each sequence's
        own; returns [b, heads * head_dim]."""
        k_cache, v_cache = item["k"], item["v"]
        k_scale, v_scale = item.get("ks"), item.get("vs")
        b, d = q.shape
        kv, cache_len, hd = k_cache.shape[1:]
        quant = k_scale is not None

        qh = q.reshape(b, kv, d // (kv * hd), hd)
        kh = k_cache.astype(q.dtype)
        vh = v_cache.astype(q.dtype)
        att = jnp.einsum("bkgd,bkld->bkgl", qh, kh) / math.sqrt(hd)
        if quant:
            att = att * k_scale[:, :, None, :].astype(att.dtype)
        live = jnp.arange(cache_len)[None, None, None, :] <= pos
        att = jnp.where(live, att, jnp.asarray(-jnp.inf, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        if quant:
            att = att * v_scale[:, :, None, :].astype(att.dtype)
        return jnp.einsum("bkgl,bkld->bkgd", att, vh).reshape(b, d)
