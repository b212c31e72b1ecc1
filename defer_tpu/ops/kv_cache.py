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

**A ring buffer** (``window=W``): a layer whose attention reaches back
``W`` positions and no further keeps ``W`` rows a sequence, not one a
position.  Row ``p`` lives at ``p % W``; until the first wrap rows
``0..p`` are live, from then on every row is, and their order does not
matter to a softmax (a family with rotary positions rotates its keys
before they are cached).  So attention over the buffer is attention
over rows ``<= min(p, W - 1)``: the kernels need no second rule.  A
format is a layer's, and a holder of several layers holds one a layer
(a window layer's beside a full layer's).

The *state* of several layers is a dict of tuples, one buffer a layer
under each key, never stacked (``ops/layered.py``, which the three
other kinds of per-sequence memory share: the retention state's format,
``ops/retention.py``, the state-space state's, ``ops/ssm.py``, and the
latent cache's, ``ops/latent_cache.py`` — the fourth kind, one row a
position that every head shares, which keeps rows as this format does
and inherits the bubbles' bookkeeping from it, :class:`RingRows`).

**The three writes**, each the operation its caller's positions make
cheapest (docs/DECODE_CLIFF.md):

* :meth:`KVCacheFormat.write_position` — one position for every
  sequence: one ``lax.dynamic_update_slice`` a buffer (the row-writer
  where the positions lie on the lanes);
* :meth:`KVCacheFormat.write_slots` — a position a sequence: the
  aliased Pallas call :func:`write_kv_rows`, which with a list of live
  sequences (:func:`live_slots`) moves their windows and no other;
* :meth:`KVCacheFormat.write_prefix` — a whole prompt for one group:
  one relayout to head-major a prompt, then one bulk write.

**The step that writes while it attends**, :meth:`KVCacheFormat.step`
(``RingRows.step``: what a block's decode step calls, ``write_position``
and then ``attend`` by default).  Where the positions lie on the lanes —
float rows under a lane row, a row a position, plain buffers:
:attr:`KVCacheFormat.writes_in_attention`, the format's geometry and
nothing else — the least a write can move is the lane row of 128
positions that holds its position, and the attention fetches that same
lane row an instant later inside its block.  There a layer's step is one
kernel, :func:`kv_step`: the attention's grid and blocks, the new rows
put into the block that holds ``pos`` in fast memory, the lane row stored
back through an aliased output.  Every other format keeps the two calls:
a position's rows lie together there, and their write is a slice.  (The
lane row stored for one position is the layout's price, 410 KB a
sequence a buffer for GPT-2's 3.2 KB of new rows: since PR 68 the ring
holds heads of 64 joined, below, and ``kv_step`` is narrower heads'.)

**The attention**, :meth:`KVCacheFormat.attend`: one query a sequence
over a layer's buffers *where they lie* — the Pallas kernel
:func:`kv_attend`, which takes the group as an index and reads the
position blocks that hold live rows and no other.  Only the int8 rows
stay on the plain einsum (:func:`attend_einsum`), which is also the
oracle the tests hold the kernel to.  Which of two kernels a float
format takes is its geometry's: :func:`kv_attend` walks a block once a
query on the vector unit, which one query a KV head of a whole lane row
does at the memory's pace and two or more do not, so rows of whole lane
rows that :data:`_JOINED_GROUP` or more query rows read hold their
buffers *joined* (:attr:`KVCacheFormat.joined`: a position's rows of all
heads side by side, ``[batch, positions, heads * head_dim]``) and attend
on the matrix unit, :func:`kv_attend_joined`.  A lane row is a head's
own, or the one two heads of 64 pair into (:func:`_lane_heads`; an odd
count pairs off with a phantom head of zeros:
:attr:`KVCacheFormat.held_heads`); its query rows are a KV head's group
(LFM2's 8 KV heads of 64 in groups of 4: to the kernel a pair is one
head of 128 and its two groups that head's group, each query in its own
head's columns beside zeros) and, where the holder writes every sequence
of a group at one position — the ring —, one query a head of the lane
row's two (GPT-2's 25 heads: 13 lane rows; there all the row's heads
side by side are one head to the kernel, 26 query rows over 1664
columns).  What is left to :func:`kv_attend`'s pass a query is one query
a head of 128 (OLMoE), heads that pair into no lane row (the tests' 8,
16 and 32), and the serving engine's slots, whose writes and live list
(below) plain rows take and joined rows do not.  A block is fetched
whole, so what it holds past ``pos`` is read for nothing: over joined
rows (:func:`kv_attend_joined`) a block has two extents, sequences and
positions, and where a position's rows are thin (one KV head of 128:
256 B) it stops at :data:`_BLOCK_POSITIONS` and holds several sequences
instead of one sequence's 4096 rows (:func:`joined_block_rows`, from the
operands' shapes alone).

**A list of live sequences** (:func:`live_slots`), for a holder most of
whose sequences are idle — the serving engine, 2 of 16 slots live under
its knee: ``write_slots`` and ``attend`` take it as data, a scalar row
their kernels read, and visit the listed sequences only.  The grid
keeps its extent (a program a *count* of live sequences would be a
compile a count); a grid step past the list names what is already in
fast memory and does nothing.  Without a list both calls are what they
were, operand for operand: the ring passes none.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from ..obs.registry import REGISTRY
from .layered import LayeredState

#: what a lane row holds: the positions of a window of the row-writer,
#: and the step by which a block of the attention grows
_LANES = 128
#: the most one of the attention's blocks (keys or values) may hold
_BLOCK_BYTES = 1 << 20
#: the positions of a block of thin rows (:func:`joined_block_rows`):
#: what a block reads past ``pos`` is at most these, a sequence
_BLOCK_POSITIONS = 512
#: joined buffers hold a multiple of this many positions (a sublane
#: tile of 16-bit rows)
_JOINED_ROWS = 16
#: from this many query rows a lane row of a joined row on, they are the
#: rows of a matrix product a position block (:func:`kv_attend_joined`).
#: One query's multiply-and-reduce on the vector unit keeps up with the
#: DMA (OLMoE's call, one query a head of 128, reads 82% of its bytes'
#: time); a pass a query does not from the second on — granite's group
#: of 4 took 1.70 ms where its rows take 0.36, 21% — while the products'
#: cost a block is the tiles of keys and values they load, whatever the
#: rows pushed through them: the same call over joined rows takes 0.60
#: ms, at 4 queries as at 2 or 16 (docs/DECODE_CLIFF.md, "The
#: attention"; PERF.md §6, PR 64).  The width of a head is no part of
#: the rule where heads pair into lane rows (:func:`_lane_heads`):
#: LFM2's group of 4 over heads of 64 took 0.81 ms in ``kv_step`` where
#: its rows take 0.24, and two heads a lane row as one head of 128 —
#: eight query rows, half of each zeros — take 0.47 (PERF.md §6, PR 66).
#: Nor is the group: a lane row's two heads with one query each are two
#: query rows, and where a holder writes every sequence at one position
#: (the ring) GPT-2's 25 heads hold 13 lane rows and a position's write
#: is a slice, not ``kv_step``'s lane row of 128 positions stored for
#: one (6.5 MB a layer a step): 81 us a call there, 59 us here and two
#: slices of 2.8 — with all the row's heads side by side as one head;
#: lane row by lane row the kernel's 13 passes a block took 93 (PERF.md
#: §6, PR 68)
_JOINED_GROUP = 2


def live_slots(slots, width: int) -> np.ndarray:
    """The list the slot-wise calls take (:meth:`KVCacheFormat.write_slots`,
    :meth:`KVCacheFormat.attend`): ``[width + 1]`` int32 — the ``n``
    slots that hold a row this step, ascending, the last of them
    repeated up to ``width``, and ``n`` (at least 1) behind them.  One
    host row: a step's kernels read it from scalar memory, so who is
    live is data and a step stays one program."""
    out = np.empty(width + 1, np.int32)
    n = len(slots)
    out[:n] = slots
    out[n:width] = slots[-1]
    out[width] = n
    return out


def _visited(j, live_ref):
    """Grid step ``j`` of a call that walks a list (:func:`live_slots`):
    ``(slot, wanted)`` — the slot it serves, and whether the step is one
    of the list's ``n``.  A step past them names the last live slot
    again: its blocks are the blocks already in fast memory, and under
    ``pl.when(wanted)`` it neither fetches, computes nor writes back."""
    n = live_ref[live_ref.shape[0] - 1]
    return live_ref[jnp.minimum(j, n - 1)], j < n


def _write_kernel(group_ref, pos_ref, *refs):
    # rows_ref [1, hd, kv] f32; win_ref / out_ref [1, 1, kv, hd, window];
    # with a list, live_ref [b + 1] leads them
    del group_ref                       # the index maps read it
    *live, rows_ref, win_ref, out_ref = refs
    kv, hd, window = win_ref.shape[2:]
    i, wanted = pl.program_id(0), None
    if live:
        i, wanted = _visited(i, live[0])

    def write():
        at = pos_ref[i] % window
        hit = lax.broadcasted_iota(jnp.int32, (hd, window), 1) == at
        for k in range(kv):
            out_ref[0, 0, k] = jnp.where(
                hit, rows_ref[0, :, k:k + 1],
                win_ref[0, 0, k].astype(jnp.float32)).astype(out_ref.dtype)

    if wanted is None:
        write()
    else:
        pl.when(wanted)(write)


@jax.jit
def write_kv_rows(cache, rows, pos, group=None, live=None):
    """``cache`` [b, kv, L, hd] with ``rows[i]`` ([b, kv, 1, hd], cast
    to the cache's type) written at position ``pos[i]`` of sequence
    ``i``; nothing else of the buffer is touched.  ``pos`` [b] int32
    in ``[0, L)``.  With ``group`` ([1] int32) the cache is a ring's
    ``[groups, b, kv, L, hd]`` and the rows are that group's.  The
    result aliases ``cache``: donate it.

    With ``live`` (:func:`live_slots`, [b + 1] int32) only the listed
    sequences' rows are written, and only their windows move: the grid
    keeps its ``b`` steps, step ``j`` serves sequence ``live[j]``, and a
    step past the list's ``n`` names the window already in fast memory
    and does nothing (:func:`_visited`) — an unlisted sequence's window
    stays as it lies, through the alias.  The serving engine's call: 2.5
    of its 16 slots hold a request, and a window is 0.8 MB each way.
    The slot axis is revisited on those steps, so it is ``"arbitrary"``
    (the default; the v5e has one TensorCore to run it on).

    XLA:TPU keeps such an array with the positions on the lanes when
    ``hd`` is under a lane row (128): ``hd`` 64 would otherwise be
    padded to twice its size.  What the obvious forms of the write cost
    in that layout (docs/DECODE_CLIFF.md, "The engine" and "The
    attention"):

    * ``jax.vmap`` of a ``dynamic_update_slice`` over the positions is
      a batched scatter: the compiler copies the whole buffer into the
      scatter's layout and back.
    * a scalar-indexed ``dynamic_update_slice`` touches one lane of
      every tile, which XLA runs as a read-modify-write of the whole
      item: 5.6 us a row in the engine (8.4 ms a step for 1,536 rows),
      75 us a buffer in the ring (3.6 ms a step at gpt2-xl, 24 layers,
      8 sequences).

    Here the buffer is viewed as ``[.., kv, hd, L]`` — the same bytes,
    so both ``swapaxes`` compile to bitcasts — and aliased to the
    output.  Each grid step moves the one 128-position window that
    holds its sequence's position through VMEM and replaces one lane of
    it.  Off-TPU the identical kernel runs in interpreter mode, as the
    other kernels of this package do.

    Jitted so that a step program that calls it once a buffer traces
    and lowers the kernel once: 96 separate ``pallas_call`` sites added
    6 s to the serving cell's set-up."""
    if group is None:
        return write_kv_rows(cache.reshape((1,) + cache.shape), rows, pos,
                             jnp.zeros(1, jnp.int32),
                             live).reshape(cache.shape)
    groups, b, kv, cache_len, hd = cache.shape
    window = min(_LANES, cache_len)
    pos = jnp.clip(pos.astype(jnp.int32), 0, cache_len - 1)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)
    listed = () if live is None else (live.astype(jnp.int32),)

    def sequence(i, live):
        return _visited(i, live[0])[0] if live else i

    def at_row(i, group_ref, pos_ref, *live):
        return (sequence(i, live), 0, 0)

    def at_window(i, group_ref, pos_ref, *live):
        i = sequence(i, live)
        return (group_ref[0], i, 0, 0, pos_ref[i] // window)

    # the rows go in as [b, hd, kv]: a head's row is then a column the
    # kernel spreads over the lanes, and the array is 0.4 MB where
    # [b, kv, hd, 1] would be padded to 128 lanes, 13 MB
    rows = jnp.swapaxes(rows[:, :, 0, :], 1, 2).astype(jnp.float32)
    out = pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(listed), grid=(b,),
            in_specs=[pl.BlockSpec((1, hd, kv), at_row),
                      pl.BlockSpec((1, 1, kv, hd, window), at_window)],
            out_specs=pl.BlockSpec((1, 1, kv, hd, window), at_window)),
        out_shape=jax.ShapeDtypeStruct((groups, b, kv, hd, cache_len),
                                       cache.dtype),
        input_output_aliases={3 + len(listed): 0},
        interpret=jax.default_backend() != "tpu",
        name="kv_write_rows",
    )(group, pos, *listed, rows, jnp.swapaxes(cache, 3, 4))
    return jnp.swapaxes(out, 3, 4)


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


def _on_lanes(hd: int) -> bool:
    """Whether XLA:TPU holds a ``[.., kv, L, hd]`` float buffer with the
    positions on the lanes: it does when ``hd`` is under a lane row
    (``hd`` 64 would otherwise be padded to twice its size); from 128
    on, a position's ``kv x hd`` rows lie together."""
    return hd < _LANES


def _lane_heads(hd: int) -> int:
    """KV heads that lie side by side in one lane row of a joined row —
    1 for heads of whole lane rows, 2 for heads of half a lane row (64)
    — or 0: joined, such heads would be no lane-aligned slices of a
    row.  An odd count of halves pairs off with one *phantom* head of
    zeros in the row's last half lane row
    (:attr:`KVCacheFormat.held_heads`: GPT-2's
    25 heads are 13 lane rows; the device tiles 1600 columns as 1664
    anyway, so the 26th head costs no byte that is not already there).
    (Narrower heads would tile a lane row too, four of 32; no family has
    them and no chip has timed three quarters of the query rows as
    zeros.)"""
    if hd % _LANES == 0:
        return 1
    return 2 if 2 * hd == _LANES else 0


def _side_by_side_heads(hd: int, kv: int, g: int) -> int:
    """How many of a joined row's ``kv`` heads of ``hd`` go side by side
    as one head to :func:`kv_attend_joined`'s kernel: a lane row's
    (:func:`_lane_heads`; 1: every head its own), and with one query a
    head the whole row's.  Two query rows a lane row leave a pass of the
    kernel's loop over heads 131 KB of a block of 256 positions, less
    than the memory moves in the pass's own latency, and the passes run
    one behind the other (GPT-2's 13 lane rows: 93.7 us a call, 51% of
    the fetched bytes' time); all the row's heads as one head of the
    row's width are one pass a block: 59.1 us, 79%.  A group's passes
    hold enough to hide theirs and the whole row gains nothing — LFM2's
    kernel takes 443.5 us either way and the two fusions around it 12 us
    more for queries eight heads wide, 480.3 us a call against the
    pairs' 469.0; granite's and command-a-plus's heads of 128 side by
    side read 790.2 against 789.7 and 1114.9 against 1133.6 — so a group
    keeps the lane row's (``scripts/joined_attend_bench.py
    --side-by-side=lane|row`` times either form of any case; PERF.md §6,
    PR 68)."""
    per = _lane_heads(hd)
    return kv if per > 1 and g == 1 else per


@functools.lru_cache(maxsize=None)
def attend_blocks(kv: int, hd: int, length: int, itemsize: int):
    """``(kv heads, positions)`` of one block of :func:`kv_attend` over
    buffers of ``length`` positions: as many heads and then as many
    lane rows of positions as :data:`_BLOCK_BYTES` hold (a block under
    ~0.5 MB leaves the chip waiting on each grid step, a larger one
    reads further past the last live position)."""
    tl = min(_LANES, length)
    kvb = max(d for d in range(1, kv + 1)
              if kv % d == 0 and (d == 1 or d * hd * tl * itemsize
                                  <= _BLOCK_BYTES))
    while kvb == kv and tl + _LANES <= length \
            and kv * hd * (tl + _LANES) * itemsize <= _BLOCK_BYTES:
        tl += _LANES
    return kvb, tl


def _attend_kernel(group_ref, pos_ref, *refs, tl, on_lanes, scale,
                   writes=False):
    """One position block of one sequence's KV heads: online softmax on
    the vector unit, in f32.  A block is ``[kvb, hd, tl]`` with the
    positions on the lanes (``on_lanes``) or ``[tl, kvb, hd]``; one body
    serves both, told which axis holds the positions (``pa``) and which
    the head's dimension (``da``).  With one query a KV head (or a few:
    the group) there is no matrix for the matrix unit: a score is a
    multiply and a reduce over ``da``, the output a multiply and a
    reduce over ``pa``.

    q_ref / o_ref ``[1, kvb, g, hd]`` (``[1, g, kvb, hd]`` off the
    lanes); qs_ref / acc_ref ``[g, kvb, hd, 1]`` (``[g, 1, kvb, hd]``):
    a query in the shape that multiplies a block; m_ref / l_ref the
    same with ``hd`` reduced away.  With a list two operands lead them:
    ``live_ref`` [b + 1], which the grid's first axis walks
    (:func:`_visited`), and the output's zeros, aliased to it and never
    read.

    ``writes`` (:func:`kv_step`; on the lanes, no list): the step's new
    rows ``krow_ref`` / ``vrow_ref`` ``[1, kvb, 1, hd]`` follow
    ``q_ref``, and behind ``o_ref`` come ``kwin_ref`` / ``vwin_ref``
    ``[1, 1, kvb, hd, window]``, the lane row of positions that holds
    ``pos`` as the buffers' aliases take it back.  The block that holds
    ``pos`` gets the rows at lane ``pos % tl`` before anything reads
    it."""
    del group_ref                       # the index maps read it
    if writes:
        (q_ref, krow_ref, vrow_ref, k_ref, v_ref, o_ref, kwin_ref, vwin_ref,
         qs_ref, m_ref, l_ref, acc_ref), live = refs, ()
    else:
        *live, q_ref, k_ref, v_ref, o_ref, qs_ref, m_ref, l_ref, acc_ref = \
            refs
    pa, da = (2, 1) if on_lanes else (0, 2)
    g, hd = qs_ref.shape[0], qs_ref.shape[2 if on_lanes else 3]
    t = pl.program_id(2)
    i, wanted = pl.program_id(0), None
    if live:
        i, wanted = _visited(i, live[0])
    pos = pos_ref[i]

    def when(condition):
        # a step past the list's last slot does nothing at all
        return pl.when(condition if wanted is None
                       else jnp.logical_and(wanted, condition))

    if on_lanes:
        # a head's row [1, hd] and its column [hd, 1] are each the
        # other spread over this diagonal and reduced
        eye = (lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
               == lax.broadcasted_iota(jnp.int32, (hd, hd), 1))[None]

    @when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        for j in range(g):
            if on_lanes:
                row = q_ref[0, :, j:j + 1, :].astype(jnp.float32) * scale
                qs_ref[j] = jnp.sum(jnp.where(eye, row, 0.0), axis=2,
                                    keepdims=True)
            else:
                qs_ref[j, 0] = q_ref[0, j].astype(jnp.float32) * scale

    def column(row_ref):
        # a new row [kvb, 1, hd] as the column [kvb, hd, 1] that stands
        # at one lane of a block; the largest of one value and -inf is
        # that value, bit for bit
        row = row_ref[0].astype(jnp.float32)
        return jnp.max(jnp.where(eye, row, -jnp.inf), axis=2, keepdims=True)

    def written(col, ref, win_ref, at):
        """``ref``'s block as f32 with ``col`` at lane ``at``; the lane
        row that holds ``at`` goes out through ``win_ref``."""
        window = win_ref.shape[4]
        # a block of one lane row (a buffer under 128 positions) is its
        # own window: Mosaic wants a lane index it can see is aligned
        first = 0 if window == tl else pl.multiple_of(
            at // window * window, window)

        def put(block, at):
            lane = lax.broadcasted_iota(jnp.int32, (1, 1, block.shape[2]), 2)
            return jnp.where(lane == at, col, block.astype(jnp.float32))

        win_ref[0, 0] = put(ref[0, 0, :, :, pl.ds(first, window)],
                            at - first).astype(win_ref.dtype)
        return put(ref[0, 0], at)

    def accumulate(ragged):
        if writes and ragged:
            k = written(column(krow_ref), k_ref, kwin_ref, pos - t * tl)
            v = written(column(vrow_ref), v_ref, vwin_ref, pos - t * tl)
        else:
            k = k_ref[0, 0].astype(jnp.float32)
            v = v_ref[0, 0].astype(jnp.float32)

        def live(shape):
            return t * tl + lax.broadcasted_iota(jnp.int32, shape, pa) <= pos

        if ragged:
            # a dead position's row may hold anything (the scratch row,
            # a block's overhang): 0 x NaN must not reach a sum
            v = jnp.where(live(v.shape), v, 0.0)
        for j in range(g):
            s = jnp.sum(k * qs_ref[j], axis=da, keepdims=True)
            if ragged:
                s = jnp.where(live(s.shape), s, -jnp.inf)
            # block 0 always holds a live position: from the first
            # block on the running max is finite
            m_new = jnp.maximum(m_ref[j], jnp.max(s, axis=pa, keepdims=True))
            alpha = jnp.exp(m_ref[j] - m_new)
            p = jnp.exp(s - m_new)
            l_ref[j] = l_ref[j] * alpha + jnp.sum(p, axis=pa, keepdims=True)
            acc_ref[j] = acc_ref[j] * alpha + jnp.sum(
                p * v, axis=pa, keepdims=True)
            m_ref[j] = m_new

    # a block wholly past ``pos`` is not computed (nor fetched: its
    # index map names a block that is wanted next); only the block that
    # holds ``pos`` pays for masks — and is the block written, so with
    # ``writes`` a block whose last row is ``pos`` goes that way too
    # (every position live: the masks change nothing)
    edge = 0 if writes else 1
    when((t + 1) * tl - edge <= pos)(lambda: accumulate(False))
    when(jnp.logical_and(t * tl <= pos, (t + 1) * tl - edge > pos))(
        lambda: accumulate(True))

    @when(t == pl.num_programs(2) - 1)
    def _finish():
        for j in range(g):
            out = acc_ref[j] / l_ref[j]
            if on_lanes:
                o_ref[0, :, j:j + 1, :] = jnp.sum(
                    jnp.where(eye, out, 0.0), axis=1,
                    keepdims=True).astype(o_ref.dtype)
            else:
                o_ref[0, j] = out[0].astype(o_ref.dtype)


def _block_positions(pos_ref, i, sb: int) -> list:
    """``pos`` of each of the ``sb`` sequences of block ``i``."""
    if sb == 1:
        return [pos_ref[i]]
    return [pos_ref[i * sb + j] for j in range(sb)]


def _attend_joined_kernel(group_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                          m_ref, l_ref, acc_ref, *, tl, kv, scale):
    """One position block of a block's sequences, every KV head's whole
    query group at once: ``q_ref`` / ``o_ref`` ``[sb, kv * g, hd]``,
    ``k_ref`` / ``v_ref`` ``[1, sb, tl, kv * hd]`` as a joined buffer
    lies.  A head's keys are a lane-aligned slice ``[tl, hd]`` of a
    sequence's rows, its scores ``[g, hd] x [hd, tl]`` and its output
    ``[g, tl] x [tl, hd]`` on the matrix unit, accumulated in f32 (16
    queries over 512 bytes a position are 8192 f32 operations for every
    512 bytes: more than the vector unit has at the memory's pace — and
    so are 4, 2048 of them, which it walks a pass a query); the online
    softmax between them runs on ``[g, tl]``.  The keys and values are
    the products' stationary operand: a block costs the tiles it loads,
    not the ``g`` rows pushed through them, so a group of 2 takes what a
    group of 16 does, and ``g`` need fill no tile — a head's rows are a
    slice at ``h * g`` of the queries and of the state, whatever ``g``
    (2 to 7 lower under Mosaic as 8 and 16 do).  Each sequence masks by
    its own ``pos``.  m_ref / l_ref ``[sb * stride, 128]`` (a row's
    scalar on every lane), acc_ref ``[sb * stride, hd]``: a sequence's
    heads from row ``j * stride`` on, whole sublane tiles apart."""
    del group_ref                       # the index maps read it
    t = pl.program_id(1)
    sb, heads, hd = q_ref.shape
    g, stride = heads // kv, m_ref.shape[0] // sb
    at = _block_positions(pos_ref, pl.program_id(0), sb)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(ragged):
        for j, h in itertools.product(range(sb), range(kv)):
            pos = at[j]
            rows, cols = pl.ds(j * stride + h * g, g), pl.ds(h * hd, hd)
            q, k, v = q_ref[j, pl.ds(h * g, g), :], k_ref[0, j, :, cols], \
                v_ref[0, j, :, cols]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if ragged:
                # a dead position's row may hold anything (the scratch
                # row, a block's overhang): 0 x NaN must not reach a sum
                s = jnp.where(t * tl + lax.broadcasted_iota(
                    jnp.int32, s.shape, 1) <= pos, s, -jnp.inf)
                v = jnp.where(t * tl + lax.broadcasted_iota(
                    jnp.int32, v.shape, 0) <= pos, v, jnp.zeros_like(v))
            # block 0 always holds a live position: from the first
            # block on the running max is finite, and a sequence whose
            # ``pos`` lies before a block its neighbours still read
            # adds exp(-inf) = 0 to its sums
            m_prev = m_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[rows, :] = jnp.broadcast_to(
                l_ref[rows, :1] * alpha + jnp.sum(p, axis=1, keepdims=True),
                (g, l_ref.shape[1]))
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[rows, :] = jnp.broadcast_to(m_new, (g, m_ref.shape[1]))

    # a block every sequence fills goes unmasked; one that holds or
    # lies past some sequence's ``pos`` — and before the furthest —
    # pays for masks; one past them all is not computed
    least, most = functools.reduce(jnp.minimum, at), \
        functools.reduce(jnp.maximum, at)
    pl.when((t + 1) * tl - 1 <= least)(lambda: accumulate(False))
    pl.when(jnp.logical_and(t * tl <= most, (t + 1) * tl - 1 > least))(
        lambda: accumulate(True))

    @pl.when(t == pl.num_programs(1) - 1)
    def _finish():
        for j in range(sb):
            rows = pl.ds(j * stride, heads)
            o_ref[j] = (acc_ref[rows, :] / l_ref[rows, :1]).astype(o_ref.dtype)


def joined_block_rows(kv: int, hd: int, length: int, itemsize: int,
                      b: int) -> tuple[int, int]:
    """``(sequences, positions)`` of one block of
    :func:`kv_attend_joined` over ``b`` sequences' buffers of ``length``
    positions: for one sequence as many lane rows of positions as
    :data:`_BLOCK_BYTES` hold of every KV head's keys.  A block is
    fetched whole, so its positions past ``pos`` are bytes nothing
    reads (4096 positions of Jamba's 256 B took 0.91 ms a call whatever
    the position, 512 take 0.23 at 384: PERF.md §6, PR 63): where the
    rows are thin — :data:`_BLOCK_POSITIONS` of a sequence under half of
    :data:`_BLOCK_BYTES` — the block stops at those positions and takes,
    of ``b``'s divisors, as many sequences as :data:`_BLOCK_BYTES` hold
    (their DMAs in flight together: at full length 78% of the memory
    peak where a sequence a grid step read 63%).  From the operands'
    shapes alone: a position of 1 KB and more keeps one sequence a
    block."""
    row = kv * hd * itemsize
    tl = min(length, max(_LANES, _BLOCK_BYTES // row // _LANES * _LANES))
    if 2 * _BLOCK_POSITIONS * row >= _BLOCK_BYTES:
        return 1, tl
    tl = min(tl, _BLOCK_POSITIONS)
    return max(d for d in range(1, b + 1)
               if b % d == 0 and d * tl * row <= _BLOCK_BYTES), tl


@functools.partial(jax.jit, static_argnames=("kv", "name"))
def kv_attend_joined(q, k_buf, v_buf, pos, group, *, kv: int,
                     name: str = "kv_attend"):
    """:func:`kv_attend` over *joined* buffers ``[groups, b, L, kv *
    hd]`` (:attr:`KVCacheFormat.joined`: a position's rows of all KV
    heads side by side on the lanes), for a query group of two or more
    (:data:`_JOINED_GROUP`): ``q`` [b, heads * hd], sequence ``i`` over
    its rows ``<= pos[i]``.  The grid runs (block of sequences, position
    block), a block ``[sequences, positions, kv * hd]`` of whole rows as
    they lie (:func:`joined_block_rows`: one sequence's run of positions
    where a position's rows are 1 KB and more, several sequences' capped
    runs where they are thin), so its DMA is a contiguous run a
    sequence, a block past every ``pos`` of its sequences is neither
    fetched nor computed, and each KV head's ``[positions, hd]`` is a
    lane-aligned slice of it — the operand the products want.

    A block of several sequences is fetched as far as the *furthest* of
    them has come.  The ring's sequences of a group stand at one
    position, so nothing is fetched twice or in vain; a holder whose
    sequences stand at unlike positions (the serving engine could hold
    such a format; no list is walked here) fetches every sequence of a
    block to the longest one's ``pos``, and masks the others.

    Heads of half a lane row (``hd`` 64: :func:`_lane_heads`, two
    side by side in one) are no lane-aligned slices, and a product over
    64 columns of a tile costs the tile (LFM2's call by such slices:
    0.49 ms at 740 positions, 1.27 at 2559; as below 0.47 and 1.00:
    PERF.md §6, PR 66).  The kernel sees a lane row's heads as one head
    of 128 and their groups as its group, each query in its own head's
    columns beside zeros (:func:`_side_by_side`); the scale stays the
    head's own, and of the output a head's group keeps its own columns
    (:func:`_own_columns`).  Two small fusions around the call.

    With one query a head (GPT-2: ``kv`` 26, the 25 and a phantom) a
    lane row has two query rows and a pass of the kernel's loop over
    heads little to do: there all the row's heads go side by side, one
    head of ``kv * hd`` columns whose group is every head's query, each
    in its own columns of the row as they lie — a mask, no element
    moved — and the output's own columns come back under the same mask
    (:func:`_side_by_side_heads` has the rule and its timings).

    The same name in a device trace as :func:`kv_attend`, and like it
    ``name`` where a format names its kernels
    (:attr:`KVCacheFormat.kernel_suffix`)."""
    b, d = q.shape
    groups, _, length, width = k_buf.shape
    hd, g = width // kv, d // width
    scale = 1.0 / math.sqrt(hd)
    per = _side_by_side_heads(hd, kv, g)
    if per > 1:
        # heads under a lane row: to the kernel the ``per`` side by side
        # are one head of their joint width and their queries its group
        q = _side_by_side(q.reshape(b, kv // per, per, g, hd))
        kv, hd = kv // per, per * hd
    heads = kv * per * g
    sb, tl = joined_block_rows(kv, hd, length, k_buf.dtype.itemsize, b)
    blocks = b // sb
    # a sequence's rows of the softmax's state start on a sublane tile
    stride = -(-heads // 8) * 8
    pos = jnp.clip(pos.astype(jnp.int32), 0, length - 1)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)

    def head_block(i, t, group_ref, pos_ref):
        return (i, 0, 0)

    def last_block(i, pos_ref):
        """The last position block any sequence of block ``i`` reads."""
        return functools.reduce(
            jnp.maximum, _block_positions(pos_ref, i, sb)) // tl

    def cache_block(i, t, group_ref, pos_ref):
        # as in kv_attend: past the last live block, name the first
        # block of the grid's next block of sequences
        more = jnp.logical_and(t > last_block(i, pos_ref), i + 1 < blocks)
        i = jnp.where(more, i + 1, i)
        t = jnp.where(more, 0, jnp.minimum(t, last_block(i, pos_ref)))
        return (group_ref[0], i, t, 0)

    out = pl.pallas_call(
        functools.partial(_attend_joined_kernel, tl=tl, kv=kv, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(blocks, pl.cdiv(length, tl)),
            in_specs=[pl.BlockSpec((sb, heads, hd), head_block),
                      pl.BlockSpec((1, sb, tl, width), cache_block),
                      pl.BlockSpec((1, sb, tl, width), cache_block)],
            out_specs=pl.BlockSpec((sb, heads, hd), head_block),
            scratch_shapes=[pltpu.VMEM((sb * stride, _LANES), jnp.float32),
                            pltpu.VMEM((sb * stride, _LANES), jnp.float32),
                            pltpu.VMEM((sb * stride, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, heads, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name=name,
    )(group, pos, q.reshape(b, heads, hd), k_buf, v_buf)
    if per > 1:
        out = _own_columns(out, kv, per)
    return out.reshape(b, d)


def _own(per: int, hd: int):
    """``[per, per * hd]``: whether a column of ``per`` heads side by
    side is row ``i``'s own head's."""
    return (jnp.arange(per)[:, None]
            == jnp.arange(per * hd)[None, :] // hd)


def _side_by_side(q):
    """The queries ``[b, rows, per, g, hd]`` of KV heads that lie side
    by side in a joined row (a lane row's two, or with one query a head
    the whole row's), as one head's group ``[b, rows, per * g, per *
    hd]``: head ``i``'s queries in its own ``hd`` columns and zeros in
    its neighbours', so a row's score against the joint keys is that
    query's against its own head's, and of its output (every head's
    values, weighted its way) its own columns are its own:
    :func:`_own_columns`.  The zeros ride through the matrix unit
    beside the queries: a block costs the tiles of keys and values it
    loads.  One query a head is its columns of the row as they lie, so
    there a row of the group is the whole row under a mask and no
    element moves."""
    b, rows, per, g, hd = q.shape
    if g == 1:
        return jnp.where(_own(per, hd), q.reshape(b, rows, 1, per * hd), 0)
    own = jnp.eye(per, dtype=bool)[:, None, :, None]
    return jnp.where(own, q[:, :, :, :, None, :], 0).reshape(
        b, rows, per * g, per * hd)


def _own_columns(out, rows: int, per: int):
    """Of the output ``[b, rows * per * g, per * hd]`` over ``per``
    heads side by side, head ``i``'s group's columns of head ``i``:
    ``[b, rows, per, g, hd]`` (with one query a head ``[b, rows, per *
    hd]``, the same elements: a column's one row under the mask, summed
    out of zeros)."""
    b, heads, width = out.shape
    g = heads // (rows * per)
    if g == 1:
        return jnp.sum(jnp.where(_own(per, width // per),
                                 out.reshape(b, rows, per, width), 0), axis=2)
    out = out.reshape(b, rows, per, g, per, width // per)
    return jnp.stack([out[:, :, i, :, i] for i in range(per)], 2)


@functools.partial(jax.jit, static_argnames=("name",))
def kv_attend(q, k_buf, v_buf, pos, group, live=None, *,
              name: str = "kv_attend"):
    """One query a sequence over its live rows: ``q`` [b, heads * hd]
    against ``k_buf`` / ``v_buf`` [groups, b, kv, L, hd] as they are
    stored, sequence ``i`` of group ``group`` [1] over its positions
    ``<= pos[i]`` ([b], int32, in ``[0, L)``).  Exact softmax in f32,
    online over position blocks as ``ops/flash_attention.py`` does;
    returns [b, heads * hd] in ``q``'s type.

    What the two einsums this replaces cost on the chip
    (docs/DECODE_CLIFF.md, "The attention"): XLA:TPU lowers them to an
    f32 multiply-and-reduce over *every* position of an item it first
    slices out of the buffer, in a layout of its own that pads the rows
    and that the compiled loop converts every buffer to and from, every
    dispatch.  Here the group is an index, the grid runs (sequence,
    block of KV heads, position block), a block past ``pos[i]`` is
    neither fetched nor computed, and — a Pallas call's operands having
    one fixed layout — the buffer a loop carries is the buffer its
    caller holds.

    Two block shapes, by ``hd`` alone, each a view of the bytes as
    XLA:TPU holds such a buffer (it tiles the two dimensions that pad
    least).  Under a lane row (gpt2's 64) that is the positions on the
    lanes (:func:`write_kv_rows`): ``[.., kv, hd, L]``, a block ``[kvb,
    hd, positions]``.  From 128 on (OLMoE) it is a position's ``kv x
    hd`` rows together: ``[.., L, kv, hd]``, a block ``[positions, kvb,
    hd]``.  Where the bytes lie otherwise, the view is a transpose and
    the compile checks (``scripts/*_tpu_compile_check.py``) say so.
    The query group of a KV head rides along in the block — a few
    queries, a pass over the block each; a group of
    :data:`_JOINED_GROUP` or more over heads of whole lane rows is a
    matrix's rows, and a format for such a group holds its buffers
    joined for :func:`kv_attend_joined` (the same name in a device
    trace), and so does one for a group over heads of half a lane row
    that pair off (two of 64): here that leaves one query a KV head, and
    the groups over heads that pair into no lane row (an odd number of
    heads of 64; the tests' heads of 8, 16 and 32).

    With ``live`` (:func:`live_slots`, [b + 1] int32) the grid's first
    axis walks the list as :func:`write_kv_rows`'s does: step ``j``
    serves sequence ``live[j]``, a step past the list's ``n`` names the
    blocks of the last live step again and does nothing, and the
    look-ahead of a dead position block names the first block of
    ``live[j + 1]`` (behind the last listed sequence there is none).
    An unlisted sequence's keys and values are not fetched and its
    output row is zeros: the output aliases a zero operand, so what no
    step writes is defined.  Revisited on the steps past ``n``, the
    first axis is then ``"arbitrary"``, and the heads' with it (one
    TensorCore on the v5e; on two, a split of the heads' axis would
    hand a core steps whose blocks another core holds).
    Jitted for the reason :func:`write_kv_rows` is.  ``name`` is the
    call's in a device trace."""
    return _attend_call(q, k_buf, v_buf, pos, group, live, name=name)


@functools.partial(jax.jit, static_argnames=("name",))
def kv_step(q, k_row, v_row, k_buf, v_buf, pos, group, *,
            name: str = "kv_step"):
    """A ring step's write and attention in one call, where the
    positions lie on the lanes (``hd`` under a lane row):
    :func:`write_kv_rows` of ``k_row`` / ``v_row`` ([b, kv, 1, hd]) at
    ``pos`` ([b]) of group ``group`` ([1]) into ``k_buf`` / ``v_buf``
    [groups, b, kv, L, hd], then :func:`kv_attend` of ``q`` over the
    rows ``<= pos``, bit for bit: ``(out, k_buf, v_buf)``.  The buffers
    alias their results: donate them.

    On the lanes one position of a sequence is one lane of ``kv x hd``
    sublane rows, and the least a DMA moves is the lane row of 128
    positions that holds it (410 KB at gpt2-xl).  The writer fetches
    that window, replaces a lane and stores it; the attention, the
    layer's very next call, fetches the same window again inside the
    block that holds ``pos``.  Here the attention's own grid and blocks
    do both: the rows ride in beside the query (cast to the buffers'
    type first, so the attention sees the row it would have read back),
    the block that holds ``pos`` gets them at lane ``pos % tl`` in fast
    memory, and its lane row of 128 positions goes back through a
    second and third output, aliased to the buffers, whose block index
    ``pos // 128`` is constant over a sequence's position blocks:
    stored once a (sequence, head block), after that sequence's last
    read.  The writer's fetch and two of a layer's three launches go
    (docs/DECODE_CLIFF.md, "The attention").  ``kv_step`` (``name``) in
    a device trace."""
    return _attend_call(q, k_buf, v_buf, pos, group, rows=(k_row, v_row),
                        name=name)


def _attend_call(q, k_buf, v_buf, pos, group, live=None, rows=None, *,
                 name: str):
    """:func:`kv_attend`, or with ``rows`` :func:`kv_step`."""
    b, d = q.shape
    groups, _, kv, length, hd = k_buf.shape
    g = d // (kv * hd)
    on_lanes = _on_lanes(hd)
    kvb, tl = attend_blocks(kv, hd, length, k_buf.dtype.itemsize)
    pos = jnp.clip(pos.astype(jnp.int32), 0, length - 1)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)
    q = q.reshape(b, kv, g, hd)
    steps = (b, kv // kvb, pl.cdiv(length, tl))

    def walk(i, h, t, live):
        """``(i, h, t, sequence of, count)``: without a list the grid's
        own step, the identity and ``b``; with one, a step past the
        list's ``n`` is the last live step again, a step serves
        ``live[i]``, and there are ``n`` to serve."""
        if not live:
            return i, h, t, lambda i: i, b
        n = live[0][b]
        over = i >= n
        return (jnp.where(over, n - 1, i), jnp.where(over, steps[1] - 1, h),
                jnp.where(over, steps[2] - 1, t), lambda i: live[0][i], n)

    def head_block(i, h, t, group_ref, pos_ref, *live):
        i, h, t, sequence, _ = walk(i, h, t, live)
        return (sequence(i), h, 0, 0) if on_lanes else (sequence(i), 0, h, 0)

    def cache_block(i, h, t, group_ref, pos_ref, *live):
        # past the last live block, name the first block of the grid's
        # next (sequence, heads): it is fetched while the last live one
        # is computed, and being named again until its turn comes it is
        # fetched once.  Naming the last live block again would leave
        # the next sequence's first fetch with nothing to hide behind.
        i, h, t, sequence, count = walk(i, h, t, live)
        dead = t > pos_ref[sequence(i)] // tl
        wrap = h + 1 == kv // kvb
        more = jnp.logical_and(dead, jnp.logical_or(i + 1 < count,
                                                    jnp.logical_not(wrap)))
        i = jnp.where(jnp.logical_and(more, wrap), i + 1, i)
        h = jnp.where(more, jnp.where(wrap, 0, h + 1), h)
        t = jnp.where(more, 0, jnp.minimum(t, pos_ref[sequence(i)] // tl))
        return (group_ref[0], sequence(i), h, 0, t) if on_lanes \
            else (group_ref[0], sequence(i), t, h, 0)

    if on_lanes:
        k_buf, v_buf = jnp.swapaxes(k_buf, 3, 4), jnp.swapaxes(v_buf, 3, 4)
        heads, block = (1, kvb, g, hd), (1, 1, kvb, hd, tl)
        state, reduced = (g, kvb, hd, 1), (g, kvb, 1, 1)
    else:
        q = jnp.swapaxes(q, 1, 2)
        k_buf, v_buf = jnp.swapaxes(k_buf, 2, 3), jnp.swapaxes(v_buf, 2, 3)
        heads, block = (1, g, kvb, hd), (1, 1, tl, kvb, hd)
        state, reduced = (g, 1, kvb, hd), (g, 1, kvb, 1)
    # with a list: the list, and the zeros an unvisited row keeps (the
    # output's alias, left where it lies)
    listed = () if live is None else (live.astype(jnp.int32),
                                      jnp.zeros_like(q))
    out_specs = pl.BlockSpec(heads, head_block)
    out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    aliases = {3: 0} if listed else {}
    if rows is None:
        rows = ()
    else:
        # the rows [b, kv, 1, hd] ride as the query does, in the
        # buffers' type; the lane row of positions that holds ``pos``
        # goes back into each buffer, one a (sequence, head block)
        window = min(_LANES, length)

        def window_block(i, h, t, group_ref, pos_ref):
            return (group_ref[0], i, h, 0, pos_ref[i] // window)

        rows = tuple(row.astype(k_buf.dtype) for row in rows)
        out_specs = [out_specs] + [pl.BlockSpec(
            (1, 1, kvb, hd, window), window_block)] * 2
        out_shape = [out_shape] + [jax.ShapeDtypeStruct(
            buf.shape, buf.dtype) for buf in (k_buf, v_buf)]
        aliases = {5: 1, 6: 2}
    out = pl.pallas_call(
        functools.partial(_attend_kernel, tl=tl, on_lanes=on_lanes,
                          scale=1.0 / math.sqrt(hd), writes=bool(rows)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(listed[:1]), grid=steps,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY) for _ in listed[1:]]
            + [pl.BlockSpec(heads, head_block)]
            + [pl.BlockSpec((1, kvb, 1, hd), head_block) for _ in rows]
            + [pl.BlockSpec(block, cache_block),
               pl.BlockSpec(block, cache_block)],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM(state, jnp.float32),
                            pltpu.VMEM(reduced, jnp.float32),
                            pltpu.VMEM(reduced, jnp.float32),
                            pltpu.VMEM(state, jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3 if listed
            else ("parallel", "parallel", "arbitrary")),
        input_output_aliases=aliases,
        interpret=jax.default_backend() != "tpu",
        name=name,
    )(group, pos, *listed, q, *rows, k_buf, v_buf)
    if rows:
        out, k_buf, v_buf = out
        return (out.reshape(b, d), jnp.swapaxes(k_buf, 3, 4),
                jnp.swapaxes(v_buf, 3, 4))
    if not on_lanes:
        out = jnp.swapaxes(out, 1, 2)
    return out.reshape(b, d)


def attend_einsum(q, item: dict, pos, window: int | None = None):
    """:meth:`KVCacheFormat.attend` as two plain einsums over one item
    (buffers ``[b, kv, L, hd]``, no group axis): what the int8 rows run
    on — their scales fold into the dots — and the oracle the tests
    hold :func:`kv_attend` to.  ``pos`` a scalar or one a sequence.
    With ``window`` the item is a ring buffer of that many rows and
    ``pos`` the sequence's true position: rows ``<= min(pos, window -
    1)`` are live."""
    if window is not None:
        pos = jnp.minimum(pos, window - 1)
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
    live = jnp.arange(cache_len)[None, None, None, :] \
        <= jnp.reshape(pos, (-1, 1, 1, 1))
    att = jnp.where(live, att, jnp.asarray(-jnp.inf, att.dtype))
    att = jax.nn.softmax(att, axis=-1)
    if quant:
        att = att * v_scale[:, :, None, :].astype(att.dtype)
    return jnp.einsum("bkgl,bkld->bkgd", att, vh).reshape(b, d)


class RingRows(LayeredState):
    """The ring's bookkeeping of a memory that keeps rows: where a step's
    row and a prompt's rows go, and where a bubble's go instead — the
    scratch row, the scratch group.  What :class:`KVCacheFormat` and the
    latent cache's format (``ops/latent_cache.py``) share; a format says
    ``positions``, ``groups`` and, for a ring buffer, ``window``."""

    #: a ring buffer's rows (a format that has them names its own)
    window = None

    @property
    def rows_held(self) -> int:
        """Rows a sequence keeps (the scratch row not counted)."""
        return self.positions if self.window is None else self.window

    @property
    def scratch_position(self) -> int:
        """Where a bubble step writes: a row nothing reads (with
        ``groups`` only)."""
        return self.rows_held

    @property
    def scratch_group(self) -> int:
        """Where a prefill's bubble writes: a group nothing reads."""
        return self.groups

    @property
    def bubble_slot(self) -> int:
        """What :meth:`decode_slot` says for a bubble: the scratch row
        itself, or in a ring buffer — where a position names its row
        only through ``% window`` — no position at all."""
        return self.scratch_position if self.window is None else -1

    def decode_slot(self, valid, pos):
        """Where a ring step's row goes: position ``pos``, or for a
        bubble (``valid`` false) the scratch row."""
        return jnp.where(valid, pos, self.bubble_slot)

    def prefill_slot(self, valid, group, row=None):
        """Where a ring prefill's rows go: group ``group``, or for a
        bubble the scratch group; with ``row``, a piece of the group
        from that sequence on."""
        group = jnp.where(valid, group, self.scratch_group)
        return group if row is None else (group, row)

    def _buffer_rows(self) -> tuple:
        """``(leading axes, rows)`` of a buffer: with ``groups`` the
        scratch group and the scratch row are counted."""
        if self.groups is None:
            return (), self.rows_held
        return (self.groups + 1,), self.rows_held + 1

    def step(self, q, layer: dict, rows: dict, pos, group=None):
        """A decode step's half of a layer that touches the memory:
        ``rows`` written at ``pos`` (:meth:`write_position`), then ``q``
        over the rows ``<= pos`` (:meth:`attend`): ``(out, layer)``.
        Two calls, unless a format has one that does both."""
        layer = self.write_position(layer, rows, pos, group=group)
        return self.attend(q, layer, pos, group=group), layer


@dataclasses.dataclass(frozen=True)
class KVCacheFormat(RingRows):
    """One layer's cache, described: what both decode engines build
    their buffers from and write and read them through (``zeros``,
    ``layer`` and ``with_layer`` are ``ops/layered.py``'s)."""

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
    #: a ring buffer: the rows a sequence keeps, fewer than
    #: ``positions`` (the module docstring); None: a row a position
    window: int | None = None
    #: queries that read one KV head (what decides :attr:`joined`)
    query_group: int = 1
    #: appended to the names of this layer's attention kernels
    #: (``kv_attend`` / ``kv_step``) in a device trace: a family whose
    #: layers are of several kinds tells them apart by it
    kernel_suffix: str = ""

    @property
    def joined(self) -> bool:
        """Whether the buffers are ``[batch, positions, held_heads *
        head_dim]`` (the module docstring): for float rows of whole
        lane rows — a head's own, or those that heads of half a lane
        row pair into (:func:`_lane_heads`: two KV heads of 64) — that
        :data:`_JOINED_GROUP` or more query rows read.  A KV head's
        group are such rows whoever holds the format; the queries of a
        lane row's *two* heads are too, one a head (GPT-2), where the
        holder writes every sequence of a group at one position — the
        ring's formats, ``groups`` set.  A holder of slots (the serving
        engine) writes a position a sequence and walks a list of live
        sequences, which plain rows take and joined rows do not
        (:meth:`write_slots`, :meth:`attend`): one query a head keeps
        plain rows there, as it does over heads of a whole lane row
        wherever they are held (OLMoE: the vector unit at the memory's
        pace)."""
        per = _lane_heads(self.head_dim)
        if self.quantized or not per:
            return False
        rows = self.query_group * (1 if self.groups is None else per)
        return rows >= _JOINED_GROUP

    @property
    def held_heads(self) -> int:
        """The heads a joined row holds: :attr:`kv_heads`, and where an
        odd count of halves leaves the row's last lane row half empty,
        one phantom head of zeros behind them (GPT-2's 25 heads of 64:
        26, 13 lane rows).  :meth:`zeros` makes the phantom's columns
        and no write puts anything else there (:meth:`rows`,
        :meth:`write_prefix` pad with zeros), so its scores are 0, its
        output 0, and :meth:`attend` drops it."""
        per = _lane_heads(self.head_dim) or 1
        return -(-self.kv_heads // per) * per

    def _held(self, a):
        """``a`` ``[..., kv_heads * n]`` (``n`` columns a KV head: its
        row, or its group's queries) with the phantom head's behind
        them, zeros."""
        phantom = a.shape[-1] // self.kv_heads \
            * (self.held_heads - self.kv_heads)
        return jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, phantom)]) \
            if phantom else a

    def __post_init__(self):
        if self.window is not None and not 0 < self.window < self.positions:
            raise ValueError(
                f"a ring buffer of {self.window} rows for {self.positions} "
                "positions: a window that never wraps is no window "
                "(window=None holds a row a position)")

    def _row(self, slot):
        """The ring buffer's row for :meth:`decode_slot`'s ``slot``."""
        return jnp.where(slot < 0, self.scratch_position,
                         slot % self.window)

    def _last_live(self, slot):
        """The ring buffer's last live row at ``slot``: rows ``0..slot``
        until the first wrap, every row from then on."""
        return jnp.clip(slot, 0, self.window - 1)

    # -- buffers ---------------------------------------------------------

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group), by key."""
        lead, length = self._buffer_rows()
        if self.joined:
            # whole sublane tiles of positions: at 4097 rows XLA:TPU
            # would tile another dimension with the lanes (the sequences:
            # 16 fit a tile exactly) and convert every buffer to the
            # kernel's layout and back around each dispatch
            rows = jax.ShapeDtypeStruct(
                lead + (batch, -(-length // _JOINED_ROWS) * _JOINED_ROWS,
                        self.held_heads * self.head_dim), self.dtype)
            return {"k": rows, "v": rows}
        scales = lead + (batch, self.kv_heads, length)
        rows = jax.ShapeDtypeStruct(
            scales + (self.head_dim,),
            jnp.int8 if self.quantized else self.dtype)
        out = {"k": rows, "v": rows}
        if self.quantized:
            out["ks"] = out["vs"] = jax.ShapeDtypeStruct(scales, jnp.float32)
        return out

    @property
    def keys(self) -> tuple:
        """The format's entries of a state (any other is its holder's)."""
        return ("k", "v", "ks", "vs") if self.quantized else ("k", "v")

    # -- what a holder posts ---------------------------------------------

    #: a window's rows and a block's extents are a layer's measures:
    #: over layers the largest
    largest = frozenset({"decode.cache.window_positions",
                         "decode.cache.block_sequences",
                         "decode.cache.block_positions"})

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The layer's bytes under its kind — a ring buffer of a
        window's rows, or a row a position —, the window's rows, and
        for joined rows the two extents of the attention's block
        (:func:`joined_block_rows`; 0 and 0 for any other rows)."""
        held = stages * self.state_bytes(batch, 1)
        ring = self.window is not None
        sequences = positions = 0
        if self.joined:
            rows = self.buffers(batch)["k"]
            sequences, positions = joined_block_rows(
                self.held_heads, self.head_dim, rows.shape[-2],
                jnp.dtype(rows.dtype).itemsize, batch)
        return {"decode.cache.window_bytes": held if ring else 0,
                "decode.cache.full_bytes": 0 if ring else held,
                "decode.cache.window_positions": self.window or 0,
                "decode.cache.block_sequences": sequences,
                "decode.cache.block_positions": positions}

    def rows_read(self, rows: int, positions: int) -> dict[str, int]:
        """The cached rows the step's attention read, under its kind:
        ``positions`` of a sequence where every position is kept, the
        window's at most in a ring buffer."""
        if self.window is None:
            return {"decode.cache.full_rows_read": rows * positions}
        return {"decode.cache.window_rows_read":
                rows * min(positions, self.window)}

    # -- rows ------------------------------------------------------------

    def rows(self, k_new, v_new) -> dict:
        """A block's new key and value columns [b, kv_heads * head_dim],
        one position a sequence, as the writes take them."""
        b = k_new.shape[0]
        if self.joined:
            return {"k": self._held(k_new)[:, None],
                    "v": self._held(v_new)[:, None]}
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
        groups).  Returns the layer: :meth:`attend` reads it where it
        lies, so nothing the size of an item is cut out or written
        back.  One ``lax.dynamic_update_slice`` a buffer where a
        position's rows lie together; where the positions lie on the
        lanes (float rows under a lane row) that would rewrite the
        group's whole item, and the row-writer does it.  In a ring
        buffer ``pos`` lands at ``pos % window``."""
        if self.window is not None:
            pos = self._row(pos)
        if not (self.quantized or self.joined) and _on_lanes(self.head_dim):
            b = rows["k"].shape[0]
            pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
            if group is not None:
                group = jnp.asarray(group, jnp.int32).reshape(1)
            return {key: write_kv_rows(layer[key], rows[key], pos, group)
                    for key in layer}
        lead = () if group is None else (group,)
        out = dict(layer)
        for key, row in rows.items():
            buf = layer[key]
            row = lax.expand_dims(row, range(len(lead))).astype(buf.dtype)
            at = lead + (0, pos, 0) if self.joined else \
                lead + (0, 0, pos) + (0,) * (row.ndim - len(lead) - 3)
            out[key] = lax.dynamic_update_slice(buf, row, at)
        return out

    def write_slots(self, layer: dict, rows: dict, pos, live=None) -> dict:
        """``rows`` written in place, sequence ``i``'s at its own
        position ``pos[i]``: the layer.  With ``live``
        (:func:`live_slots`) the listed sequences' rows only: an
        unlisted sequence's rows are neither written nor moved."""
        if self.quantized or self.groups is not None \
                or self.window is not None or self.joined:
            raise NotImplementedError(
                "a position a sequence is written into unquantized "
                "slots of a row a position only (ROADMAP.md A5, B2)")
        return {key: write_kv_rows(layer[key], rows[key], pos, live=live)
                for key in layer}

    def write_prefix(self, layer: dict, k, v, slot) -> dict:
        """A whole prompt's key and value columns [b, t, kv_heads *
        head_dim] written at positions ``0..t-1`` where ``slot``
        (:meth:`prefill_slot`'s) says — a group, or a group and the
        sequence of it the ``b`` prompts start at; in a format without
        groups, the sequence alone: one head-major relayout a prompt
        (amortized), one bulk write a buffer.  A ring buffer shorter
        than the prompt keeps the prompt's newest rows, each where a
        decode step will look for it (``p % window``)."""
        if self.groups is None:
            at = (slot,)
        else:
            at = slot if isinstance(slot, tuple) else (slot, 0)
        b, t = k.shape[:2]
        # plain rows of a piece of a group (the joined rows' constraint
        # below serves whole groups and pieces alike)
        pieces = not self.joined and isinstance(slot, tuple)
        w, shift = self.window, 0
        if w is not None and t > w:
            # position t - w + i lies at row (t - w + i) % w
            k, v, shift, t = k[:, t - w:], v[:, t - w:], (t - w) % w, w
        if self.joined:
            # rows as the buffer holds them: left to itself the
            # compiler may produce a prompt's keys positions-minor (what
            # the product before them liked) and, the write needing one
            # layout on both sides, convert the *buffer* there and back
            rows_major = Layout(major_to_minor=(0, 1, 2))
            k, v = (with_layout_constraint(self._held(a), rows_major)
                    for a in (k, v))
        else:
            shape = (b, t, self.kv_heads, self.head_dim)
            k = k.reshape(shape).transpose(0, 2, 1, 3)
            v = v.reshape(shape).transpose(0, 2, 1, 3)
            if pieces:
                # a piece of a group, written inside the prefill's loop
                # over pieces, in the order the chip keeps the *buffer*
                # (else the loop holds it otherwise and converts all of
                # it in and out, two copies a buffer a prefill): heads
                # under a lane row keep the positions on the lanes; 8
                # heads of 128 are one tile, the rows outside the heads
                order = (0, 1, 3, 2) if _on_lanes(self.head_dim) \
                    else (0, 2, 1, 3)
                as_made = Layout(major_to_minor=order)
                k, v = (with_layout_constraint(a, as_made) for a in (k, v))
        if shift:
            k, v = (jnp.roll(a, shift, axis=1 if self.joined else 2)
                    for a in (k, v))
        new = {"k": k, "v": v}
        if self.quantized:
            new["k"], new["ks"] = quantize_rows(k)
            new["v"], new["vs"] = quantize_rows(v)
        out = dict(layer)
        for key, rows in new.items():
            buf = layer[key]
            if self.groups is not None:
                rows = rows[None]
            out[key] = lax.dynamic_update_slice(
                buf, rows.astype(buf.dtype), at + (0,) * (buf.ndim - len(at)))
        return out

    def head_major(self, item: dict) -> dict:
        """One group's buffers (no group axis) as ``[b, kv, L, hd]``,
        whichever way the format holds them: what
        :func:`attend_einsum` reads."""
        if not self.joined:
            return item
        return {key: buf.reshape(buf.shape[:2] + (self.held_heads, -1))
                [:, :, :self.kv_heads].swapaxes(1, 2)
                for key, buf in item.items()}

    def reparent(self, state: dict, group, parents) -> dict:
        """Beam search: sequence ``i`` of group ``group`` takes over
        sequence ``parents[i]``'s rows, in every layer that keeps rows
        (a layer without memory has None there)."""
        def one(buf):
            if buf is None:
                return None
            grp = jnp.take(_group_slice(buf, group), parents, axis=1)
            return lax.dynamic_update_slice(
                buf, grp, (group,) + (0,) * (buf.ndim - 1))

        return {key: tuple(one(b) for b in bufs) if key in self.keys else bufs
                for key, bufs in state.items()}

    # -- attention -------------------------------------------------------

    @property
    def writes_in_attention(self) -> bool:
        """Whether :meth:`step` is one call, :func:`kv_step`: float rows
        with the positions on the lanes, a row a position.  Only there
        does a row's write move a whole lane row of positions, which the
        attention's block holds anyway; everywhere else a position's
        rows lie together and their write is a slice.  (A ring buffer
        on the lanes writes at ``pos % window`` and attends up to
        ``min(pos, window - 1)``: two blocks after the first wrap.)"""
        return (not self.quantized and not self.joined
                and self.window is None and _on_lanes(self.head_dim))

    def step(self, q, layer: dict, rows: dict, pos, group=None):
        """:meth:`write_position` then :meth:`attend`, as one call where
        the format :attr:`writes_in_attention`; the result is the two
        calls' bit for bit.  A subclass that writes or attends its own
        way is stepped through its own two calls."""
        # ``__class__``: the class this method is defined in
        own = (type(self).write_position is __class__.write_position
               and type(self).attend is __class__.attend)
        if not (own and self.writes_in_attention):
            return super().step(q, layer, rows, pos, group)
        REGISTRY.gauge("decode.kv.fused_layers").inc()
        out, k_buf, v_buf = kv_step(
            q, rows["k"], rows["v"],
            *self._kernel_operands(layer, pos, group, q.shape[0]),
            name="kv_step" + self.kernel_suffix)
        return out, {"k": k_buf.reshape(layer["k"].shape),
                     "v": v_buf.reshape(layer["v"].shape)}

    @staticmethod
    def _kernel_operands(layer: dict, pos, group, b: int) -> tuple:
        """``(k_buf, v_buf, pos, group)`` as the attention's kernels take
        them: the buffers behind a group axis, a position a sequence,
        the group a row of one."""
        k_buf, v_buf = layer["k"], layer["v"]
        if group is None:
            k_buf, v_buf, group = k_buf[None], v_buf[None], 0
        return (k_buf, v_buf,
                jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,)),
                jnp.asarray(group, jnp.int32).reshape(1))

    def attend(self, q, layer: dict, pos, group=None, live=None):
        """One query a sequence over ``layer``'s buffers (group
        ``group``'s sequences, where the format has groups): ``q`` [b,
        heads * head_dim], positions ``<= pos`` live — ``pos`` a scalar
        (every sequence at one position) or [b], one a sequence;
        returns [b, heads * head_dim].  :func:`kv_attend`, or the
        einsums for int8 rows.  Over a ring buffer ``pos`` is
        :meth:`decode_slot`'s, the sequences' true position.  With
        ``live`` (:func:`live_slots`) the listed sequences only: an
        unlisted sequence's rows are not read and its output is zeros
        (plain rows only: neither the einsums nor
        :func:`kv_attend_joined` walk a list)."""
        if live is not None and (self.quantized or self.joined):
            raise NotImplementedError(
                "a list of live sequences is walked over plain rows only "
                "(kv_attend; ROADMAP.md A5)")
        if self.window is not None:
            pos = self._last_live(pos)
        if self.quantized:
            item = layer if group is None else {
                key: _group_slice(buf, group)[0]
                for key, buf in layer.items()}
            return attend_einsum(q, item, pos)
        k_buf, v_buf, pos, group = self._kernel_operands(
            layer, pos, group, q.shape[0])
        name = "kv_attend" + self.kernel_suffix
        if self.joined:
            REGISTRY.gauge("decode.kv.joined_layers").inc()
            out = kv_attend_joined(
                self._held(q), k_buf, v_buf, pos, group, kv=self.held_heads,
                name=name)
            # a phantom head's queries ride as zeros behind the others
            # and its output is dropped: inside the two fusions that put
            # a lane row's heads side by side and take each head's own
            # columns back
            return out[:, :q.shape[1]]
        return kv_attend(q, k_buf, v_buf, pos, group, live, name=name)
