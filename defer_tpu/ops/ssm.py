"""The state-space state's format: the one module that knows how a
state-space layer's per-sequence memory is kept on the device, updated
and read — of two shapes, Mamba-1's (:class:`SsmFormat`) and Mamba-2's
(:class:`SsdFormat`, below), which share the window and the layout.

A selective state-space mixer (Gu & Dao, arXiv:2312.00752) of ``E``
channels and ``N`` states keeps two things a sequence, whatever its
length: the last ``d_conv - 1`` inputs of its depthwise causal
convolution, and the recurrent state ``H [E, N]``:

    c(t) = silu(b + sum_j w[j] * u(t - d_conv + 1 + j))          (the window)
    H(t) = exp(dt(t) (x) A) * H(t-1) + (dt(t) * c(t)) (x) B(t)
    y(t) = H(t) C(t)

``dt [E]``, ``B [N]``, ``C [N]`` are functions of ``c(t)`` (the
*selection*), ``A [E, N]`` a parameter.  The blocks
(``models/decoder.py::StateSpaceBlock``) hand over ``u``, then ``dt``,
``c``, ``B``, ``C`` and ``A``, and take the window's taps and ``y``
back; they know nothing of what follows.

**The format.**  One layer is a dict of two buffers, behind a leading
``groups`` axis for the ring:

* ``h [batch, N, E]`` float32 — the states on the sublanes, the
  channels on the lanes: 16 x 5120 are whole (8, 128) tiles, where ``[E,
  N]`` would pad 16 values to 128 lanes (8x).  Float32 because the sum
  runs over hundreds of positions under a decay near 1, as the
  retention state's does;
* ``conv [d_conv - 1, batch, E]`` in the compute type — the taps lead,
  so a tap is ``[batch, E]`` of whole tiles (``[batch, 3, E]`` would pad
  3 sublanes to 16).  ``conv[j]`` is the input ``d_conv - 1 - j``
  positions back; before a sequence's start it is zero.

Like a retention state and unlike a KV cache it has **no scratch group
and no scratch row**: a pipeline's bubble is the identity update (``dt =
0`` leaves ``H`` bit for bit, and the window is kept), which
:meth:`SsmFormat.shift`, :meth:`SsmFormat.step` and the two prefill
calls make of a call whose ``valid`` is false.  The state of several
layers is a tuple of buffers a key, never stacked (``ops/layered.py``).

* :meth:`SsmFormat.step` — one token a sequence: the aliased Pallas
  kernel :func:`ssm_step` streams a block of sequences' ``H`` through
  VMEM once (decay, add, read out, write back in place).
* :meth:`SsmFormat.prefill` — a whole prompt from an empty memory: the
  Pallas kernel :func:`ssm_scan` runs the recurrence position by
  position over a block of channels whose ``H`` stays in registers,
  carried between blocks of positions in VMEM; the ``[t, E, N]`` tensor
  of a prompt is never made.
* :func:`step_reference` / :func:`prefill_reference` — the same in plain
  ``jnp``, the tests' oracle.

**The second shape: a state of heads** (Mamba-2, Dao & Gu,
arXiv:2405.21060; :class:`SsdFormat`).  The ``E`` channels are ``heads``
heads of ``head_dim``; the decay is **one scalar a head** (``dt [heads]``
a position, ``A [heads]``), ``B`` and ``C [N]`` are shared by the heads
of a **group** — ``G`` groups (``bc_groups``) of ``heads / G``
consecutive heads, head ``h`` in group ``h // (heads / G)``; one group
in granite-4.0-h, eight in Nemotron-3 — and the convolution runs over
the channels, every group's ``B`` and every group's ``C`` together, so
its window is ``E + 2 G N`` wide, wider than the state:

    [x(t), B(t)[0..G-1], C(t)[0..G-1]] = c(t)       (the window's output)
    H(t)[h] = exp(dt(t)[h] A[h]) * H(t-1)[h]
              + dt(t)[h] * x(t)[h] (x) B(t)[g(h)]
    y(t)[h] = H(t)[h] C(t)[g(h)]

The buffers are the first shape's — ``h [batch, N, E]`` float32, the
states on the sublanes and every head's channels side by side on the
lanes (128 x 8192: whole tiles; ``B`` and ``C`` being shared within a
group, a product over the states serves a group's heads at once),
``conv [d_conv - 1, batch, E + 2 G N]`` — and so are the window's three
calls, the scratch-free bubble and the layered state.  ``B`` and ``C``
pass as ``[..., G N]``, a group after the other as the window holds
them, and **a kernel's block of channels lies inside one group** (a
block of 1024 channels is a group's 16 heads of 64 at the published
sizes): its ``B`` and ``C`` are that group's ``N`` columns, named by
the block's index map — nothing is gathered or repeated, and at one
group the calls are what they were before groups, operand for operand.
What differs from the first shape is the recurrence:

* :meth:`SsdFormat.step` — the aliased Pallas kernel :func:`ssd_step`:
  a grid over blocks of sequences *and* blocks of channels (a
  sequence's 4.2 MB does not cross VMEM whole), the decay a row a
  sequence (one exponential a head, taken before the call), ``B`` and
  ``C`` the block's group's, ``y`` a sum over the sublanes.
* :meth:`SsdFormat.prefill` — the Pallas kernel :func:`ssd_scan`: the
  chunked matrix form.  Within a chunk of ``chunk`` positions ``Y =
  (L o C B^T) (dt x)`` with ``L[i, j] = exp(sum_{j<k<=i} dt_k A)`` a
  head and ``C B^T`` a group, on the matrix unit; between chunks the
  ``[N, E]`` state is
  carried in VMEM; the ``[t, E, N]`` tensor is never made.
* :func:`ssd_step_reference` / :func:`ssd_prefill_reference` — plain
  ``jnp``, position by position.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
# (the window, and its layout constraint, live in ops/conv_window.py)
from jax.experimental.pallas import tpu as pltpu

from .conv_window import Window, dense_window

#: channels a kernel works on at a time: 16 states x 512 channels of
#: float32 are 8 vector registers, so a block's ``H`` stays in them
_CHANNELS = 512
#: sequences of one grid step of :func:`ssm_step` (a sublane tile)
_SEQUENCES = 8
#: the most positions of one grid step of :func:`ssm_scan`
_POSITIONS = 256
#: positions the scan unrolls: one sublane tile of ``dt`` and ``y``
_TILE = 8


def _channel_block(e: int) -> int:
    """Channels of a kernel's inner block: the largest of 512, 256, 128
    that divides ``e``, else all of them."""
    return next((c for c in (_CHANNELS, 256, 128) if e % c == 0), e)


# -- the step kernel ----------------------------------------------------------

def _step_kernel(group_ref, dt_ref, dx_ref, b_ref, c_ref, a_ref, h_ref,
                 y_ref, out_ref, *, block: int):
    """A block of sequences: ``h_ref`` / ``out_ref`` ``[1, bs, N, E]``,
    ``dt_ref`` / ``dx_ref`` / ``y_ref`` ``[bs, E]``, ``b_ref`` /
    ``c_ref`` ``[bs, N, 1]`` (a column a sequence, spread over the lanes
    here), ``a_ref`` ``[N, E]``.  A block of channels at a time, each
    sequence's ``[N, block]`` tile once through the registers."""
    del group_ref                       # the index map reads it
    bs, e = dt_ref.shape
    for lo in range(0, e, block):
        cols = slice(lo, lo + block)
        a = a_ref[:, cols]
        ys = []
        for i in range(bs):
            h = jnp.exp(dt_ref[i:i + 1, cols] * a) * h_ref[0, i, :, cols] \
                + dx_ref[i:i + 1, cols] * b_ref[i]
            out_ref[0, i, :, cols] = h
            ys.append(jnp.sum(h * c_ref[i], axis=0, keepdims=True))
        y_ref[:, cols] = jnp.concatenate(ys, axis=0)


@jax.jit
def ssm_step(dt, dx, b, c, a, state, group):
    """``H <- exp(dt (x) A) * H + dx (x) B`` in place and ``y = H C`` of
    the new state.  ``state`` [groups, batch, N, E] f32, of which group
    ``group`` [1] int32; ``dt`` / ``dx`` [batch, E] f32 (the step and
    the step times the input); ``b`` / ``c`` [batch, N] f32; ``a`` [N,
    E] f32.  Returns ``(y [batch, E] f32, state)``; the state aliases
    its argument: donate it.

    One grid step a block of sequences, whose states cross VMEM once
    (327 KB a sequence in and as much out at 16 x 5120).  Every
    operation is on the vector unit in float32: there is no matrix in a
    diagonal recurrence.  Off-TPU the identical kernel runs in
    interpreter mode, as the package's others do.  Jitted so that a
    step program that calls it once a layer traces and lowers it
    once."""
    groups, batch, n, e = state.shape
    bs = _SEQUENCES if batch % _SEQUENCES == 0 else batch
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)
    rows = pl.BlockSpec((bs, e), lambda i, group_ref: (i, 0))
    cols = pl.BlockSpec((bs, n, 1), lambda i, group_ref: (i, 0, 0))
    big = pl.BlockSpec((1, bs, n, e),
                       lambda i, group_ref: (group_ref[0], i, 0, 0))
    y, out = pl.pallas_call(
        functools.partial(_step_kernel, block=_channel_block(e)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch // bs,),
            in_specs=[rows, rows, cols, cols,
                      pl.BlockSpec((n, e), lambda i, group_ref: (0, 0)),
                      big],
            out_specs=[rows, big]),
        out_shape=[jax.ShapeDtypeStruct((batch, e), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the states' block in and out, each double-buffered, the
            # columns (a lane row a value) and the rows
            vmem_limit_bytes=4 * bs * n * e * 4 + 4 * bs * n * 512
            + 8 * bs * e * 4 + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="ssm_step",
    )(group, dt, dx, b[..., None], c[..., None], a, state)
    return y, out


# -- the scan kernel ----------------------------------------------------------

def _scan_kernel(dt_ref, dx_ref, b_ref, c_ref, a_ref, y_ref, last_ref,
                 h_ref):
    """One sequence, one block of channels, one block of positions:
    ``dt_ref`` / ``dx_ref`` / ``y_ref`` ``[1, tb, block]``, ``b_ref`` /
    ``c_ref`` ``[1, tb, N, 1]``, ``a_ref`` ``[N, block]``; ``h_ref``
    ``[N, block]`` carries the state from one block of positions to the
    next, ``last_ref`` ``[1, N, block]`` takes it after the last."""
    at_t = pl.program_id(2)
    tb = dt_ref.shape[1]

    @pl.when(at_t == 0)
    def _empty():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]

    def tile(k, h):
        lo = pl.multiple_of(k * _TILE, _TILE)
        dts = dt_ref[0, pl.ds(lo, _TILE), :]
        dxs = dx_ref[0, pl.ds(lo, _TILE), :]
        ys = []
        for j in range(_TILE):
            h = jnp.exp(dts[j:j + 1] * a) * h + dxs[j:j + 1] * b_ref[0, lo + j]
            ys.append(jnp.sum(h * c_ref[0, lo + j], axis=0, keepdims=True))
        y_ref[0, pl.ds(lo, _TILE), :] = jnp.concatenate(ys, axis=0)
        return h

    h = lax.fori_loop(0, tb // _TILE, tile, h_ref[...])
    h_ref[...] = h

    @pl.when(at_t == pl.num_programs(2) - 1)
    def _last():
        last_ref[0] = h


@jax.jit
def ssm_scan(dt, dx, b, c, a):
    """The recurrence of :func:`ssm_step` over whole prompts from an
    empty memory: ``dt`` / ``dx`` [batch, t, E] f32, ``b`` / ``c``
    [batch, t, N] f32, ``a`` [N, E] f32 -> ``(y [batch, t, E] f32, H
    [batch, N, E] f32 after the last position)``.

    The grid is (sequence, block of channels, block of positions), the
    positions innermost and in order: a block of channels' ``[N, 512]``
    state is 8 vector registers, updated position by position and kept
    in VMEM between two blocks of positions; a position's ``[E, N]``
    outer products exist a block of channels at a time, in registers.
    A prompt whose length is no multiple of 8 is padded at its end with
    identity steps (``dt = 0``)."""
    batch, t, e = dt.shape
    n = a.shape[0]
    pad = -t % _TILE
    if pad:
        dt, dx, b, c = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (dt, dx, b, c))
    tp = t + pad
    tb = next(k for k in range(min(tp, _POSITIONS), 0, -_TILE)
              if tp % k == 0)
    block = _channel_block(e)
    rows = pl.BlockSpec((1, tb, block), lambda i, j, k: (i, k, j))
    cols = pl.BlockSpec((1, tb, n, 1), lambda i, j, k: (i, k, 0, 0))
    y, last = pl.pallas_call(
        _scan_kernel,
        grid=(batch, e // block, tp // tb),
        in_specs=[rows, rows, cols, cols,
                  pl.BlockSpec((n, block), lambda i, j, k: (0, j))],
        out_specs=[rows, pl.BlockSpec((1, n, block),
                                      lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((batch, tp, e), jnp.float32),
                   jax.ShapeDtypeStruct((batch, n, e), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, block), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # the columns (a lane row a value), double-buffered, are most
            # of it: 2 x 2 x tb x N x 512 B
            vmem_limit_bytes=4 * tb * n * 512 + 6 * tb * block * 4
            + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="ssm_scan",
    )(dt, dx, b[..., None], c[..., None], a)
    return (y[:, :t] if pad else y), last


# -- the second shape's step kernel -------------------------------------------

def _group_of_block(e: int, block: int, bc_groups: int):
    """Which ``B`` / ``C`` group a kernel's ``j``-th block of ``block``
    channels reads: a function of ``j`` for an index map.  With one
    group the constant 0 (the call is then what it was before groups);
    else ``j`` over the blocks a group's ``e / bc_groups`` channels
    make."""
    if bc_groups == 1:
        return lambda j: 0
    per = e // bc_groups // block
    return lambda j: j // per


def _block_in_group(e: int, bc_groups: int, sizes) -> int:
    """The first of ``sizes`` (candidate channel blocks, widest first)
    that divides a group's ``e / bc_groups`` channels; with one group
    and none, all ``e``."""
    span = e // bc_groups
    block = next((k for k in sizes if span % k == 0), None)
    if block is None:
        if bc_groups > 1:
            raise ValueError(
                f"{bc_groups} B/C groups of {span} channels: a kernel's "
                "block of channels lies inside one group, and no block of "
                "whole lane tiles divides a group")
        block = e
    return block


#: the most bytes of ``H`` one grid step of :func:`ssd_step` takes in
#: (and as many out): 8 sequences x 128 states x 1024 channels
_SSD_STEP_BYTES = 4 << 20
#: channels whose ``[N, .]`` tile a sequence's update holds at a time
_SSD_LANES = 256


def _ssd_step_kernel(group_ref, decay_ref, dx_ref, b_ref, c_ref, h_ref,
                     y_ref, out_ref, *, block: int):
    """A block of sequences and of channels: ``h_ref`` / ``out_ref``
    ``[1, bs, N, cb]``, ``decay_ref`` / ``dx_ref`` / ``y_ref`` ``[bs,
    cb]`` (a head's decay on each of its channels), ``b_ref`` /
    ``c_ref`` ``[bs, N, 1]`` (a column a sequence, spread over the
    lanes here).  ``block`` channels at a time, each sequence's ``[N,
    block]`` tile once through the registers."""
    del group_ref                       # the index map reads it
    bs, cb = decay_ref.shape
    for lo in range(0, cb, block):
        cols = slice(lo, lo + block)
        ys = []
        for i in range(bs):
            h = decay_ref[i:i + 1, cols] * h_ref[0, i, :, cols] \
                + dx_ref[i:i + 1, cols] * b_ref[i]
            out_ref[0, i, :, cols] = h
            ys.append(jnp.sum(h * c_ref[i], axis=0, keepdims=True))
        y_ref[:, cols] = jnp.concatenate(ys, axis=0)


@jax.jit
def ssd_step(decay, dx, b, c, state, group):
    """``H <- decay * H + dx (x) B`` in place and ``y = H C`` of the new
    state, for a state of heads laid ``[N, E]``.  ``state`` [groups,
    batch, N, E] f32, of which group ``group`` [1] int32; ``decay`` /
    ``dx`` [batch, E] f32 (a head's ``exp(dt A)`` on each of its
    channels, and the step times the input); ``b`` / ``c`` [batch, G N]
    f32, a group after the other (``G`` from the shapes: a block of
    channels reads its own group's ``N`` columns).  Returns ``(y [batch,
    E] f32, state)``; the state aliases its argument: donate it.

    The grid runs over blocks of sequences and, inside, blocks of
    channels: at 128 states x 8192 channels a sequence is 4.2 MB, so a
    grid step takes 8 sequences' ``[128, 1024]`` (4 MB in, 4 MB out)
    and every value of ``H`` crosses VMEM once.  There is no
    exponential in here: a head has one, taken before the call."""
    groups, batch, n, e = state.shape
    bs = _SEQUENCES if batch % _SEQUENCES == 0 else batch
    bc_groups = b.shape[-1] // n
    fit = max(128, _SSD_STEP_BYTES // (4 * bs * n))
    cb = _block_in_group(e, bc_groups, [
        k for k in range(min(e, fit), 0, -128) if k % 128 == 0 and e % k == 0])
    block = next((k for k in (_SSD_LANES, 128) if cb % k == 0), cb)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)
    of = _group_of_block(e, cb, bc_groups)
    rows = pl.BlockSpec((bs, cb), lambda i, j, group_ref: (i, j))
    cols = pl.BlockSpec((bs, n, 1), lambda i, j, group_ref: (i, of(j), 0))
    big = pl.BlockSpec((1, bs, n, cb),
                       lambda i, j, group_ref: (group_ref[0], i, 0, j))
    y, out = pl.pallas_call(
        functools.partial(_ssd_step_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch // bs, e // cb),
            in_specs=[rows, rows, cols, cols, big],
            out_specs=[rows, big]),
        out_shape=[jax.ShapeDtypeStruct((batch, e), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the states' block in and out, each double-buffered, the
            # columns (a lane row a value) and the rows
            vmem_limit_bytes=4 * bs * n * cb * 4 + 4 * bs * n * 512
            + 6 * bs * cb * 4 + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="ssd_step",
    )(group, decay, dx, b[..., None], c[..., None], state)
    return y, out


# -- the second shape's scan kernel ---------------------------------------------

#: channels of one grid step of :func:`ssd_scan` (16 heads of 64)
_SSD_SCAN_CHANNELS = 1024


def _ssd_scan_kernel(cum_ref, row_ref, col_ref, dx_ref, b_ref, bt_ref,
                     c_ref, y_ref, last_ref, h_ref, *, head_dim: int,
                     tile: int):
    """One sequence, one block of channels, one chunk of ``L``
    positions.  ``cum_ref`` ``[1, L, heads]`` is the running sum of ``dt
    A`` inside the chunk, every head's; ``row_ref`` ``[1, 1, hb, L]``
    and ``col_ref`` ``[1, 1, hb, L, 1]`` the same for this block's heads
    as rows and as columns; ``dx_ref`` / ``y_ref`` ``[1, L, eb]``;
    ``b_ref`` / ``c_ref`` ``[1, L, N]``, ``bt_ref`` ``[1, 1, N, L]``;
    ``h_ref`` ``[N, eb]`` carries the state from chunk to chunk,
    ``last_ref`` ``[1, N, eb]`` takes it after the last."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    at_e, at_t = pl.program_id(1), pl.program_id(2)
    length, eb = dx_ref.shape[1:]
    heads = cum_ref.shape[2]

    @pl.when(at_t == 0)
    def _empty():
        h_ref[...] = jnp.zeros_like(h_ref)

    def dot(a, b):
        return jnp.dot(a, b, precision=hi, preferred_element_type=f32)

    cum = cum_ref[0]                                   # [L, heads]
    # a head's value on each of its channels: a product with 0 / 1
    # (exact at this precision) spreads [L, heads] over [L, eb]
    spread = (lax.broadcasted_iota(jnp.int32, (heads, eb), 0)
              == (lax.broadcasted_iota(jnp.int32, (heads, eb), 1)
                  + at_e * eb) // head_dim).astype(f32)
    since = dot(jnp.exp(cum), spread)          # decay since the chunk began
    until = dot(jnp.exp(cum[length - 1:length] - cum), spread)  # to its end
    x, b, c = dx_ref[0], b_ref[0], c_ref[0]
    h = h_ref[...]
    off = dot(c, h) * since                            # [L, eb]
    scores = dot(c, bt_ref[0, 0])                      # C B^T [L, L]
    causal = lax.broadcasted_iota(jnp.int32, (length, length), 0) \
        >= lax.broadcasted_iota(jnp.int32, (length, length), 1)
    lane = lax.broadcasted_iota(jnp.int32, (length, tile), 1)
    per_tile = tile // head_dim
    for k in range(eb // tile):
        cols = slice(k * tile, (k + 1) * tile)
        y = None
        for j in range(per_tile):
            head = k * per_tile + j
            decay = jnp.exp(jnp.where(
                causal, col_ref[0, 0, head] - row_ref[0, 0, head:head + 1],
                -jnp.inf))
            part = dot(scores * decay, x[:, cols])
            y = part if y is None else jnp.where(
                lane < j * head_dim, y, part)
        y_ref[0, :, cols] = y + off[:, cols]
    h = since[length - 1:length] * h + dot(bt_ref[0, 0], until * x)
    h_ref[...] = h

    @pl.when(at_t == pl.num_programs(2) - 1)
    def _last():
        last_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "bc_groups"))
def ssd_scan(dt, dx, b, c, a, *, chunk: int, bc_groups: int = 1):
    """The recurrence of :func:`ssd_step` over whole prompts from an
    empty memory, in the chunked matrix form: ``dt`` [batch, t, heads]
    f32, ``dx`` [batch, t, E] f32 (the step times the input, a head's
    step on each of its channels), ``b`` / ``c`` [batch, t, G N] f32 (``G =
    bc_groups``, a group after the other), ``a`` [heads] f32 -> ``(y
    [batch, t, E] f32, H [batch, N, E] f32 after the last position)``.

    The grid is (sequence, block of channels, chunk), the chunks
    innermost and in order.  Inside a chunk of ``L`` positions a head's
    outputs are ``(decay o C B^T) dx`` with ``decay[i, j] = exp(sum_{j <
    k <= i} dt_k A)`` for ``j <= i`` — ``[L, L]`` products on the matrix
    unit, ``C B^T`` shared by a group's heads (a block of channels lies
    inside one group and reads that group's columns) — plus what the
    state carried
    in gives, ``(C H) * decay since the chunk began``; the state moves
    on by ``B^T (decay to the chunk's end * dx)``.  Every product takes
    float32 operands whole (``Precision.HIGHEST``).  The running sums
    of ``dt A`` are taken here, outside the kernel, a chunk at a time.
    A prompt whose length is no multiple of ``chunk`` is padded at its
    end with identity steps (``dt = 0``).  Heads narrower than a lane
    tile share one: each takes the whole tile's product and keeps its
    own lanes."""
    batch, t, heads = dt.shape
    e, n = dx.shape[-1], b.shape[-1] // bc_groups
    head_dim = e // heads
    length = min(chunk, -(-t // _TILE) * _TILE)
    pad = -t % length
    if pad:
        dt, dx, b, c = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                        for x in (dt, dx, b, c))
    tp = t + pad
    chunks = tp // length
    cum = jnp.cumsum((dt * a).reshape(batch, chunks, length, heads), axis=2)
    rows = cum.swapaxes(2, 3)                   # [batch, chunks, heads, L]
    eb = _block_in_group(e, bc_groups, [
        k for k in (_SSD_SCAN_CHANNELS, 512, 256, 128)
        if e % k == 0 and k % head_dim == 0])
    of = _group_of_block(e, eb, bc_groups)
    tile = next((k for k in (128, head_dim) if eb % k == 0
                 and k % head_dim == 0), eb)
    hb = eb // head_dim
    wide = pl.BlockSpec((1, length, eb), lambda i, j, k: (i, k, j))
    thin = pl.BlockSpec((1, length, n), lambda i, j, k: (i, k, of(j)))
    y, last = pl.pallas_call(
        functools.partial(_ssd_scan_kernel, head_dim=head_dim, tile=tile),
        grid=(batch, e // eb, chunks),
        in_specs=[
            pl.BlockSpec((1, length, heads), lambda i, j, k: (i, k, 0)),
            pl.BlockSpec((1, 1, hb, length), lambda i, j, k: (i, k, j, 0)),
            pl.BlockSpec((1, 1, hb, length, 1),
                         lambda i, j, k: (i, k, j, 0, 0)),
            wide, thin,
            pl.BlockSpec((1, 1, n, length),
                         lambda i, j, k: (i, k, of(j), 0)),
            thin],
        out_specs=[wide, pl.BlockSpec((1, n, eb), lambda i, j, k: (i, 0, j))],
        out_shape=[jax.ShapeDtypeStruct((batch, tp, e), jnp.float32),
                   jax.ShapeDtypeStruct((batch, n, e), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, eb), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            # the inputs' and the output's blocks double-buffered (the
            # columns a lane row a value), a handful of [L, eb] and
            # [L, L] temporaries
            vmem_limit_bytes=4 * (4 * length * eb + 2 * hb * length * 128
                                  + 8 * length * max(n, heads, 128)
                                  + 6 * length * eb + 8 * length * length)
            + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="ssd_scan",
    )(cum.reshape(batch, tp, heads), rows, rows[..., None], dx, b,
      b.reshape(batch, chunks, length, bc_groups * n).swapaxes(2, 3), c)
    return (y[:, :t] if pad else y), last


# -- the convolution ----------------------------------------------------------

def causal_conv(taps, w, bias=None, *, activation=jax.nn.silu):
    """``activation(bias + sum_j w[j] * taps[j])`` in float32, rounded
    to the taps' type: ``taps`` a sequence of ``d_conv`` arrays [..., E],
    oldest first, the newest the position's own input; ``w`` [d_conv,
    E], ``bias`` [E] or None (a convolution without one).  The
    state-space mixers' is a ``silu``; ``activation`` None leaves the
    sum as it is (a gated short convolution's,
    ``models/decoder.py::ConvWindowBlock``)."""
    acc = None if bias is None else bias.astype(jnp.float32)
    for j, tap in enumerate(taps):
        term = w[j].astype(jnp.float32) * tap.astype(jnp.float32)
        acc = term if acc is None else acc + term
    if activation is not None:
        acc = activation(acc)
    return acc.astype(taps[-1].dtype)


# -- the format --------------------------------------------------------------------

class _WindowedState(Window):
    """What both shapes of state-space memory share: the convolution's
    window (``ops/conv_window.py``'s, with its three calls and the
    bubble as an identity update; only its width differs) and, beside
    it, the state ``h``, and where a prefill leaves its last state."""

    keys = ("conv", "h")

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group), by key."""
        lead = () if self.groups is None else (self.groups,)
        return {
            "conv": self.window_buffer(batch),
            "h": jax.ShapeDtypeStruct(
                lead + (batch, self.states, self.channels), jnp.float32)}

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The convolution's window, of the layer's bytes."""
        return {"decode.ssm.conv_bytes": self.window_bytes(batch, stages)}

    def _leave(self, last, layer: dict, slot) -> dict:
        """``layer`` with ``last`` [b, N, E], the state after a prompt's
        last position, left where ``slot`` says (kept where the call is
        a bubble)."""
        group, valid, row = slot if len(slot) == 3 else (*slot, 0)
        bufs, group = self._group(layer, group)
        at = (group[0], row, 0, 0)
        old = lax.dynamic_slice(bufs["h"], at, (1,) + last.shape)
        h = lax.dynamic_update_slice(
            bufs["h"], jnp.where(valid, last[None], old), at)
        return self._ungroup(dict(bufs, h=h))


@dataclasses.dataclass(frozen=True)
class SsmFormat(_WindowedState):
    """One layer's Mamba-1 memory, described: what the ring builds its
    buffers from and updates and reads them through (``zeros``,
    ``layer`` and ``with_layer`` are ``ops/layered.py``'s)."""

    channels: int           #: ``E``
    states: int             #: ``N``
    d_conv: int
    #: the window's type, the block's compute type (``h`` is float32)
    dtype: Any
    #: the ring's round-robin groups (a leading axis); None for one batch
    groups: int | None = None

    @property
    def conv_width(self) -> int:
        return self.channels

    def step(self, dt, x, b, c, a, layer: dict, group=None, valid=True):
        """One token of every sequence (of group ``group``): ``dt`` [b,
        E] the step (float32), ``x`` [b, E] the convolution's output,
        ``b`` / ``c`` [b, N], ``a`` [N, E].  The state is decayed, ``(dt
        x) (x) b`` is added and ``c`` reads the *new* state: returns
        ``(y [b, E] float32, the layer)``.  With ``valid`` false (a
        pipeline's bubble) the update is the identity (``dt = 0``) and
        ``y`` means nothing."""
        f32 = jnp.float32
        dt = jnp.where(valid, dt.astype(f32), 0.0)
        bufs, group = self._group(layer, group)
        y, h = ssm_step(dt, dt * x.astype(f32), b.astype(f32),
                        c.astype(f32), a.astype(f32), bufs["h"], group)
        return y, self._ungroup(dict(bufs, h=h))

    def prefill(self, dt, x, b, c, a, layer: dict, slot=(None, True)):
        """A whole prompt of every sequence (of the group ``slot``
        names) into an *empty* memory: ``dt`` / ``x`` [b, t, E], ``b`` /
        ``c`` [b, t, N], ``a`` [N, E] -> ``(y [b, t, E] float32, the
        layer)``, the layer holding the state after the last position.
        Where ``slot`` says the call is a bubble, the state is kept."""
        f32 = jnp.float32
        dt = dt.astype(f32)
        y, last = ssm_scan(dt, dt * x.astype(f32), b.astype(f32),
                           c.astype(f32), a.astype(f32))
        return y, self._leave(last, layer, slot)


@dataclasses.dataclass(frozen=True)
class SsdFormat(_WindowedState):
    """One layer's Mamba-2 memory, described: a state of ``heads`` heads
    of ``head_dim`` channels under one decay a head, ``B`` and ``C``
    shared by the heads of each of ``bc_groups`` groups and convolved
    with the channels (the module docstring's second shape)."""

    heads: int
    head_dim: int
    states: int             #: ``N``
    d_conv: int
    #: positions of one chunk of the prefill's matrix form
    chunk: int
    #: the window's type, the block's compute type (``h`` is float32)
    dtype: Any
    #: the ring's round-robin groups (a leading axis); None for one batch
    groups: int | None = None
    #: ``G``: groups of consecutive heads that share a ``B`` and a ``C``
    bc_groups: int = 1

    largest = frozenset({"decode.ssm.bc_groups"})

    def __post_init__(self):
        if self.heads % self.bc_groups:
            raise ValueError(f"{self.heads} heads do not form "
                             f"{self.bc_groups} B/C groups")

    @property
    def channels(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """The channels, every group's ``B`` and every group's ``C``."""
        return self.channels + 2 * self.bc_groups * self.states

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The window's bytes, and the ``B`` / ``C`` groups (the most
        any layer has)."""
        return dict(super().gauges(batch, stages),
                    **{"decode.ssm.bc_groups": self.bc_groups})

    def step(self, dt, x, b, c, a, layer: dict, group=None, valid=True):
        """One token of every sequence (of group ``group``): ``dt`` [b,
        heads] the step (float32), ``x`` [b, E] the channels of the
        convolution's output, ``b`` / ``c`` [b, G N] (a group after the
        other), ``a`` [heads].  Every head's state is decayed by its
        ``exp(dt a)``, ``(dt x) (x) b`` is added and ``c`` reads the
        *new* state: returns ``(y [b, E] float32, the layer)``.  With
        ``valid`` false (a pipeline's bubble) the update is the identity
        (``dt = 0``) and ``y`` means nothing."""
        f32 = jnp.float32
        dt = jnp.where(valid, dt.astype(f32), 0.0)
        bufs, group = self._group(layer, group)
        y, h = ssd_step(
            jnp.repeat(jnp.exp(dt * a.astype(f32)), self.head_dim, axis=-1),
            jnp.repeat(dt, self.head_dim, axis=-1) * x.astype(f32),
            b.astype(f32), c.astype(f32), bufs["h"], group)
        return y, self._ungroup(dict(bufs, h=h))

    def prefill(self, dt, x, b, c, a, layer: dict, slot=(None, True)):
        """A whole prompt of every sequence (of the group ``slot``
        names) into an *empty* memory: ``dt`` [b, t, heads], ``x`` [b,
        t, E], ``b`` / ``c`` [b, t, G N], ``a`` [heads] -> ``(y [b, t, E]
        float32, the layer)``, the layer holding the state after the
        last position.  Where ``slot`` says the call is a bubble, the
        state is kept."""
        f32 = jnp.float32
        dt = dt.astype(f32)
        y, last = ssd_scan(
            dt, jnp.repeat(dt, self.head_dim, axis=-1) * x.astype(f32),
            b.astype(f32), c.astype(f32), a.astype(f32), chunk=self.chunk,
            bc_groups=self.bc_groups)
        return y, self._leave(last, layer, slot)


def dense(h, conv, heads: int | None = None
          ) -> tuple[np.ndarray, np.ndarray]:
    """A layer's buffers of one group on the host in the form that
    knows no layout: ``h`` [b, N, E] -> ``H [b, E, N]``, or with
    ``heads`` ``[b, heads, head_dim, N]``; ``conv`` [d_conv - 1, b, W]
    -> the window ``[b, d_conv - 1, W]``, oldest input first."""
    h = np.swapaxes(np.asarray(h), -1, -2)
    if heads is not None:
        h = h.reshape(h.shape[:-2] + (heads, -1, h.shape[-1]))
    return h, dense_window(conv)


# -- the oracle -----------------------------------------------------------------

def step_reference(dt, x, b, c, a, h):
    """:func:`ssm_step` in plain ``jnp`` over one item (``h`` [batch,
    N, E], no group axis), all float32: ``(y [batch, E], h)``."""
    h = jnp.exp(dt[:, None, :] * a[None]) * h \
        + (dt * x)[:, None, :] * b[:, :, None]
    return jnp.sum(h * c[:, :, None], axis=1), h


def prefill_reference(dt, x, b, c, a):
    """The recurrence position by position from an empty memory: ``dt``
    / ``x`` [batch, t, E], ``b`` / ``c`` [batch, t, N], ``a`` [N, E],
    all float32 -> ``(y [batch, t, E], h [batch, N, E])``."""
    def step(h, xs):
        y, h = step_reference(*xs, a, h)
        return h, y

    start = jnp.zeros((dt.shape[0], a.shape[0], a.shape[1]), jnp.float32)
    h, ys = lax.scan(step, start, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), h


def ssd_step_reference(dt, x, b, c, a, h):
    """:func:`ssd_step` (behind :meth:`SsdFormat.step`'s spreading of a
    head's step) in plain ``jnp`` over one item: ``dt`` [batch, heads],
    ``x`` [batch, E], ``b`` / ``c`` [batch, G N] (a group after the
    other; ``G`` from ``h``'s ``N``), ``a`` [heads], ``h`` [batch, N,
    E], all float32: ``(y [batch, E], h)``."""
    e, n = x.shape[-1], h.shape[-2]
    p = e // dt.shape[-1]

    def spread(v):
        """A group's ``[N]`` on each of its channels: ``[batch, N, E]``."""
        g = v.shape[-1] // n
        return jnp.repeat(v.reshape(-1, g, n), e // g, axis=1).swapaxes(1, 2)

    h = jnp.repeat(jnp.exp(dt * a), p, axis=-1)[:, None, :] * h \
        + (jnp.repeat(dt, p, axis=-1) * x)[:, None, :] * spread(b)
    return jnp.sum(h * spread(c), axis=1), h


def ssd_prefill_reference(dt, x, b, c, a, *, bc_groups: int = 1):
    """The second shape's recurrence position by position from an empty
    memory: ``dt`` [batch, t, heads], ``x`` [batch, t, E], ``b`` / ``c``
    [batch, t, G N] (``G = bc_groups``), ``a`` [heads], all float32 ->
    ``(y [batch, t, E], h [batch, N, E])``."""
    def step(h, xs):
        y, h = ssd_step_reference(*xs, a, h)
        return h, y

    start = jnp.zeros((dt.shape[0], b.shape[-1] // bc_groups, x.shape[-1]),
                      jnp.float32)
    h, ys = lax.scan(step, start, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), h
