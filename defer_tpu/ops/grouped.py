"""The routed experts' grouped product: the one module that knows how
rows sorted by expert meet that expert's matrix.

``xs [rows, k]`` holds rows sorted by group, ``w [groups, k, n]`` one
matrix a group, ``sizes [groups]`` how many consecutive rows are each
group's — the contract of ``lax.ragged_dot``:

    out[r] = xs[r] @ w[g]      for  sum(sizes[:g]) <= r < sum(sizes[:g+1])

``sizes`` may sum to *less* than ``rows`` (``ops/routed.py::
expert_dispatch_held``: the tail is no held expert's); those rows come
back zero and the caller masks them.
Operands in one type, bfloat16 or float32; the sum in float32; the
output in the operands' type.

**Two paths, both Pallas kernels of this module, chosen by the
product's static shape alone** (:func:`takes_kernel`).  A decode step's
product has a handful of rows a group (2 in OLMoE's step, ~9 in
granite-4.0-h's, ~1 in command-a-plus's, ~6 in Mellum2's, ~5.5 in
Nemotron-3-Super's, whose rows are latent rows a quarter as wide as the
stream and whose expert is two such products with a squared relu
between them, ``ops/routed.py::grouped_mlp``): far under the
~240 rows at which a bfloat16 matrix's operations cost what its bytes do
on a v5e (197 TFLOP/s over 819 GB/s), so it is bound by the *touched*
matrices' bytes and takes :func:`grouped_experts`.  A prompt's product
has hundreds to thousands of rows a group (or more rows than VMEM
holds), is bound by the matrix unit and takes the row-tiled
:func:`grouped_rows`.  Nothing else decides: no setting of the process,
no configuration field, no model's name.  The choice is made while a
program is traced and counted there (``moe.grouped.kernel_products`` /
``moe.grouped.tiled_products``, docs/OBSERVABILITY.md).  The boundary —
mean rows a group at a quarter of the ridge, ~60 — is PR 43's, put
there by the step kernel's bench; PR 56's bench (``scripts/
grouped_product_bench.py prefill``, PERF.md section 6) found the tiled
kernel ahead of ``lax.ragged_dot`` at every prompt shape a cell runs —
80-86% of the matrix peak against 22-25% at Mellum2's ``196608 x 2304 x
896`` (the lowering halves at a width of 896, seven lane tiles), 76-82%
against 50-55% at OLMoE's ``131072 x 2048 x 1024``, 58-66% against
40-44% on a held run of 4096 pairs — so ``lax.ragged_dot`` left this
module and is the tests' oracle only.

**The step kernel.**  The sorted rows stay in VMEM for the whole call (at
most 640 x 4096 bfloat16 = 5.2 MB in the cells).  The grid runs over
(column tile, group); ``sizes``' running sum and, a group, *which
group's tile the step wants* are scalar-prefetch operands.  An
untouched group names the tile of the next touched one, so nothing is
fetched for it (that tile is on its way while the touched group before
it is multiplied: named behind instead, the transfer would start only
once the untouched step is reached, with nothing beside it), and its
step is skipped; a touched group's ``[k, tn]`` tile is fetched once
and multiplied with the aligned blocks of
:data:`_ROW_BLOCK` rows that cover its rows (one block at these sizes;
a dynamic loop when a group is longer), the neighbours' rows masked
out of the store, while the pipeline fetches the next touched tile.
The tile's width comes from ``(k, n)`` and :data:`_TILE_BYTES`.  With
two matrices the same pass emits ``silu(x g) * (x u)``
(:func:`grouped_gate_up`): the rows and their blocks once, half the
group-steps a layer, and the gate and the up product meet in float32
(apart, each is rounded to the stream's type first).

**The tiled kernel.**  The grid runs over (column tile, visit): a visit
is a (group, row tile) pair, a row tile :data:`_ROW_TILE` sorted rows
with ``k`` whole, and the list of visits (:func:`_visits`) is made from
``sizes`` beside the call and handed over as scalar-prefetch operands
with the groups' first rows.  A group's consecutive row tiles name the
same ``[k, tn]`` matrix tile, which is therefore fetched once a group a
column tile; a row tile that straddles a boundary is visited once a
group that has rows in it, consecutively, so its output block stays in
VMEM between the visits and each stores its own group's rows only; the
rows behind the last group are a group of their own that multiplies
nothing and stores zeros; empty groups have no visit.  Nothing is
padded or copied: the last row tile may be partial.  With two matrices
the visit emits ``silu(x g) * (x u)`` from float32, as the step kernel
does: a prompt's rows are read once and no ``[rows, n]`` pair of
products is written and read back.

* :func:`grouped_product` / :func:`grouped_gate_up` — what the blocks
  call (through ``ops/routed.py::grouped_swiglu``): the shape rule, then
  one of the two kernels.
* :func:`grouped_experts` / :func:`grouped_rows` — the Pallas calls,
  whatever the shape.
* :func:`grouped_reference` — the same in plain ``jnp``, the tests'
  oracle beside ``lax.ragged_dot``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.registry import REGISTRY

#: rows a group at which a bfloat16 matrix's operations take what its
#: bytes take on a v5e: 197 TFLOP/s over 819 GB/s
_RIDGE_ROWS = 240
#: a product whose mean rows a group stay under this share of the ridge
#: is bound by its matrices' bytes, and takes the kernel
_RIDGE_SHARE = 4
#: the most bytes of sorted rows (or of output rows) a call keeps in
#: VMEM whole
_ROWS_BYTES = 16 << 20
#: a block of rows starts on a multiple of this (bfloat16's sublane tile)
_ROW_ALIGN = 16
#: rows of one product inside the kernel: a group of up to 17 rows lies
#: in one block wherever it starts
_ROW_BLOCK = 32
#: the most bytes of one matrix tile ``[k, tn]``
_TILE_BYTES = 8 << 20
#: rows of one visit of the tiled kernel.  PR 56's bench (PERF.md
#: section 6): 256 rows are 2% faster at Mellum2's and OLMoE's prompts
#: and no faster on a held run, 512 and 1024 slower; the kernel's body
#: is unrolled over its tile and the chip holds a program's text, so
#: 128 rows are also 0.7 MB less of Mellum2's prefill program
_ROW_TILE = 128


def takes_kernel(rows: int, groups: int, k: int, n: int, itemsize: int
                 ) -> bool:
    """The shape rule: does the grouped product of ``rows`` sorted rows
    with ``groups`` matrices ``[k, n]`` take the step kernel
    (:func:`grouped_experts`) — yes where the mean rows a group stay
    under a quarter of the ridge and the rows fit VMEM whole — or the
    tiled one (:func:`grouped_rows`)?  Counts each answer (it is asked
    while a program is traced: once a product a layer, and once more
    where the held dispatcher probes its ``expert_fn``'s output
    shape)."""
    kernel = (_RIDGE_SHARE * rows <= _RIDGE_ROWS * groups
              and rows * max(k, n) * itemsize <= _ROWS_BYTES)
    REGISTRY.counter("moe.grouped.kernel_products" if kernel
                     else "moe.grouped.tiled_products").inc()
    return kernel


def _call(xs, w):
    """The Pallas call :func:`takes_kernel` gives ``xs`` with matrices
    shaped as ``w``."""
    return grouped_experts if takes_kernel(
        xs.shape[0], *w.shape, xs.dtype.itemsize) else grouped_rows


def grouped_product(xs, w, sizes):
    """``xs [rows, k]`` sorted by group times ``w [groups, k, n]``,
    ``sizes [groups]`` rows each: ``[rows, n]`` in ``xs``'s type."""
    return _call(xs, w)(xs, (w,), sizes)


def grouped_gate_up(xs, gate, up, sizes):
    """``silu(xs g) * (xs u)`` a group, ``gate`` / ``up [groups, k,
    n]``: ``[rows, n]`` in ``xs``'s type, in one pass of either
    kernel."""
    return _call(xs, gate)(xs, (gate, up), sizes)


def _tile_width(k: int, n: int, itemsize: int) -> int:
    """Columns of a matrix tile: the widest divisor of ``n`` in whole
    lane tiles whose ``[k, tn]`` stays under :data:`_TILE_BYTES`; all
    of ``n`` where it is no multiple of 128."""
    if n % 128:
        return n
    fit = max(128, _TILE_BYTES // (k * itemsize) // 128 * 128)
    return next(t for t in range(min(n, fit), 0, -128) if n % t == 0)


def _kernel(off_ref, fetch_ref, x_ref, *refs):
    """One (column tile, group) step.  ``off_ref [groups + 1]`` the
    groups' first rows, ``x_ref [rows, k]`` every sorted row, ``refs``
    one or two ``[k, tn]`` tiles of this step's group (gate and up where
    two) and the output's ``[rows, tn]`` column tile, which stays in
    VMEM over the groups."""
    del fetch_ref                       # the index map reads it
    *w_refs, o_ref = refs
    f32 = jnp.float32
    g = pl.program_id(1)
    rows = o_ref.shape[0]

    @pl.when(g == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    start, end = off_ref[g], off_ref[g + 1]

    @pl.when(end > start)
    def _():
        first = jnp.minimum(start // _ROW_ALIGN * _ROW_ALIGN,
                            rows - _ROW_BLOCK)

        def block(b, carry):
            r0 = pl.multiple_of(
                jnp.minimum(first + b * _ROW_BLOCK, rows - _ROW_BLOCK),
                _ROW_ALIGN)
            at = pl.ds(r0, _ROW_BLOCK)
            x = x_ref[at, :]
            y = jnp.dot(x, w_refs[0][...], preferred_element_type=f32)
            if len(w_refs) == 2:
                y = jax.nn.silu(y) * jnp.dot(x, w_refs[1][...],
                                             preferred_element_type=f32)
            row = r0 + lax.broadcasted_iota(jnp.int32, (_ROW_BLOCK, 1), 0)
            mine = jnp.logical_and(row >= start, row < end)
            o_ref[at, :] = jnp.where(mine, y, o_ref[at, :].astype(f32)
                                     ).astype(o_ref.dtype)
            return carry

        lax.fori_loop(0, pl.cdiv(end - first, _ROW_BLOCK), block, 0)


@jax.jit
def grouped_experts(xs, mats, sizes):
    """The kernel, whatever the shape: ``mats`` a tuple of one matrix
    stack ``[groups, k, n]`` (``xs w``) or two (``silu(xs g) * (xs
    u)``).  ``[rows, n]`` in ``xs``'s type; rows behind the last group
    are zero.  In interpreter mode off the TPU, as the package's other
    kernels are."""
    rows, k, groups, n = _check_operands("grouped_experts", xs, mats)
    item = xs.dtype.itemsize
    padded = max(-(-rows // _ROW_ALIGN) * _ROW_ALIGN, _ROW_BLOCK)
    if padded != rows:
        xs = jnp.pad(xs, ((0, padded - rows), (0, 0)))
    tn = _tile_width(k, n, item * len(mats))
    sizes = sizes.astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes)])
    # the group whose tile a step wants: its own where it has rows, else
    # the next touched one's (behind the last touched one, that one's):
    # an untouched group fetches nothing of its own, and the tile that
    # follows it is on its way while the one before it is multiplied
    index = jnp.arange(groups, dtype=jnp.int32)
    touched = sizes > 0
    ahead = lax.cummin(jnp.where(touched, index, groups), reverse=True)
    fetch = jnp.where(ahead < groups, ahead,
                      jnp.max(jnp.where(touched, index, 0)))
    tile = pl.BlockSpec((None, k, tn),
                        lambda j, g, off, fetch: (fetch[g], 0, j))
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n // tn, groups),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [tile] * len(mats),
            out_specs=pl.BlockSpec((padded, tn),
                                   lambda j, g, off, fetch: (0, j))),
        out_shape=jax.ShapeDtypeStruct((padded, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the rows once, each matrix's tile and the output's column
            # tile double-buffered, a block's float32 products
            vmem_limit_bytes=padded * k * item
            + 2 * len(mats) * k * tn * item + 2 * padded * tn * item
            + 4 * _ROW_BLOCK * tn * 4 + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="grouped_experts",
    )(offsets, fetch, xs, *mats)
    return out[:rows] if padded != rows else out


def _check_operands(name, xs, mats):
    rows, k = xs.shape
    groups, _, n = mats[0].shape
    if any(m.shape != (groups, k, n) or m.dtype != xs.dtype for m in mats):
        raise ValueError(
            f"{name}: rows {xs.shape} {xs.dtype} need matrices "
            f"[groups, {k}, n] of their type, got "
            f"{[(m.shape, str(m.dtype)) for m in mats]}")
    return rows, k, groups, n


def _visits(sizes, rows: int, tm: int):
    """The (group, row tile) pairs the tiled kernel visits, in the
    order of the rows: ``offsets [groups + 3]`` the first rows of the
    groups, of the tail behind the last group (a group of its own, no
    matrix's) and, twice, ``rows``; ``group [visits]`` and ``tile
    [visits]`` each visit's pair; ``fetch [visits]`` the group whose
    matrix tile the visit wants.  A row tile is listed once a group
    that has rows in it; there are at most ``tiles + groups`` such
    pairs, and the visits behind the last pair repeat its tile and
    its matrix (nothing is fetched) under group ``groups + 1``, which
    is empty.  The tail's visits want the matrix of the last group
    with rows, which is there already."""
    groups = sizes.shape[0]
    tiles = -(-rows // tm)
    total = jnp.sum(sizes)
    ext = jnp.concatenate([sizes, (rows - total)[None]])
    ends = jnp.cumsum(ext)
    starts = ends - ext
    offsets = jnp.concatenate([starts, jnp.full((2,), rows, jnp.int32)])
    first = starts // tm
    count = jnp.where(ext > 0, (ends - 1) // tm - first + 1, 0)
    behind = jnp.cumsum(count)                  # visits up to each group's end
    v = jnp.arange(tiles + groups, dtype=jnp.int32)
    at = jnp.minimum(v, behind[-1] - 1)
    group = jnp.searchsorted(behind, at, side="right").astype(jnp.int32)
    tile = first[group] + at - (behind - count)[group]
    index = jnp.arange(groups, dtype=jnp.int32)
    last = jnp.max(jnp.where(sizes > 0, index, 0))
    fetch = jnp.minimum(group, last)
    group = jnp.where(v < behind[-1], group, groups + 1)
    return offsets, group, tile, fetch


def _rows_kernel(off_ref, group_ref, tile_ref, fetch_ref, x_ref, *refs):
    """One (column tile, visit) step: the ``[tm, k]`` rows of the
    visit's row tile times the ``[k, tn]`` tile of the visit's group
    (gate and up where two), stored to the rows that are the group's.
    A row tile's visits are consecutive, so its output block stays in
    VMEM between them; the first writes zeros to the rows that are not
    its group's, the later ones keep what is there."""
    del fetch_ref                       # the index map reads it
    *w_refs, o_ref = refs
    f32 = jnp.float32
    v = pl.program_id(1)
    g, t = group_ref[v], tile_ref[v]
    tm = o_ref.shape[0]
    groups = off_ref.shape[0] - 3
    start, end = off_ref[g], off_ref[g + 1]
    first = jnp.logical_or(v == 0, tile_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(g < groups)
    def _():
        x = x_ref[...]
        y = jnp.dot(x, w_refs[0][...], preferred_element_type=f32)
        if len(w_refs) == 2:
            y = jax.nn.silu(y) * jnp.dot(x, w_refs[1][...],
                                         preferred_element_type=f32)
        row = t * tm + lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = jnp.logical_and(row >= start, row < end)
        kept = jnp.where(first, 0.0, o_ref[...].astype(f32))
        o_ref[...] = jnp.where(mine, y, kept).astype(o_ref.dtype)

    # the tail behind the last group: no product, zeros where the tile
    # is met for the first time
    @pl.when(jnp.logical_and(g == groups, first))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@jax.jit
def grouped_rows(xs, mats, sizes):
    """The tiled kernel, whatever the shape: :func:`grouped_experts`'s
    contract for many rows a group.  The sorted rows go through the
    matrix unit a tile of :data:`_ROW_TILE` rows at a time, ``k``
    whole; a group's ``[k, tn]`` matrix tile is fetched once for the
    consecutive row tiles of the group; a row tile that straddles a
    boundary is visited once a group (:func:`_visits`).  Nothing is
    padded: where ``rows`` is no multiple of the tile the last block is
    partial.  In interpreter mode off the TPU."""
    rows, k, groups, n = _check_operands("grouped_rows", xs, mats)
    item = xs.dtype.itemsize
    # (no more than the rows there are, in whole sublane tiles)
    tm = min(_ROW_TILE, -(-rows // _ROW_ALIGN) * _ROW_ALIGN)
    tn = _tile_width(k, n, item * len(mats))
    offsets, group, tile, fetch = _visits(sizes.astype(jnp.int32), rows, tm)
    matrix = pl.BlockSpec(
        (None, k, tn), lambda j, v, off, group, tile, fetch: (fetch[v], 0, j))
    return pl.pallas_call(
        _rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n // tn, group.shape[0]),
            in_specs=[pl.BlockSpec(
                (tm, k), lambda j, v, off, group, tile, fetch: (tile[v], 0))]
            + [matrix] * len(mats),
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, off, group, tile, fetch: (tile[v], j))),
        out_shape=jax.ShapeDtypeStruct((rows, n), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a row tile, each matrix's tile and the output's block
            # double-buffered, the float32 products and their meeting
            vmem_limit_bytes=2 * tm * k * item
            + 2 * len(mats) * k * tn * item + 2 * tm * tn * item
            + (len(mats) + 2) * tm * tn * 4 + (8 << 20)),
        cost_estimate=pl.CostEstimate(
            flops=2 * rows * k * n * len(mats),
            transcendentals=rows * n * (len(mats) - 1),
            bytes_accessed=(n // tn * rows * k + rows * n
                            + len(mats) * groups * k * n) * item),
        interpret=jax.default_backend() != "tpu",
        name="grouped_rows",
    )(offsets, group, tile, fetch, xs, *mats)


def grouped_reference(xs, mats, sizes):
    """:func:`grouped_experts` in plain ``jnp``: a masked loop over the
    groups, every product in float32."""
    f32 = jnp.float32
    ends = jnp.cumsum(sizes)
    row = jnp.arange(xs.shape[0])
    out = jnp.zeros((xs.shape[0], mats[0].shape[-1]), f32)
    for g in range(mats[0].shape[0]):
        y = jnp.dot(xs, mats[0][g], preferred_element_type=f32)
        if len(mats) == 2:
            y = jax.nn.silu(y) * jnp.dot(xs, mats[1][g],
                                         preferred_element_type=f32)
        mine = jnp.logical_and(row >= ends[g] - sizes[g], row < ends[g])
        out = jnp.where(mine[:, None], y, out)
    return out.astype(xs.dtype)
