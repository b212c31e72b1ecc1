"""The routed experts' grouped product: the one module that knows how
rows sorted by expert meet that expert's matrix.

``xs [rows, k]`` holds rows sorted by group, ``w [groups, k, n]`` one
matrix a group, ``sizes [groups]`` how many consecutive rows are each
group's — the contract of ``lax.ragged_dot``:

    out[r] = xs[r] @ w[g]      for  sum(sizes[:g]) <= r < sum(sizes[:g+1])

``sizes`` may sum to *less* than ``rows`` (``graph/ops.py::
expert_dispatch_held``: the tail is no held expert's); those rows come
back finite (zero from the kernel) and the caller masks them.
Operands in one type, bfloat16 or float32; the sum in float32; the
output in the operands' type.

**Two paths, chosen by the product's static shape alone**
(:func:`takes_kernel`).  A decode step's product has a handful of rows
a group (2 in OLMoE's step, ~9 in granite-4.0-h's, ~1 in
command-a-plus's): far under the ~240 rows at which a bfloat16 matrix's
operations cost what its bytes do on a v5e (197 TFLOP/s over 819 GB/s),
so it is bound by the *touched* matrices' bytes and takes the Pallas
kernel :func:`grouped_experts`.  A prompt's product has hundreds to
thousands of rows a group, is bound by the matrix unit and keeps
``lax.ragged_dot``, whose lowering is good there.  Nothing else
decides: no setting of the process, no configuration field, no model's
name.  The choice is made
while a program is traced and counted there (``moe.grouped.kernel_products``
/ ``moe.grouped.ragged_products``, docs/OBSERVABILITY.md).

**The kernel.**  The sorted rows stay in VMEM for the whole call (at
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

* :func:`grouped_product` / :func:`grouped_gate_up` — what the blocks
  call (through ``graph/ops.py::grouped_swiglu``): the shape rule, then
  the kernel or ``lax.ragged_dot``.
* :func:`grouped_experts` — the Pallas call, whatever the shape.
* :func:`grouped_reference` — the same in plain ``jnp``, the tests'
  oracle.
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


def takes_kernel(rows: int, groups: int, k: int, n: int, itemsize: int
                 ) -> bool:
    """The shape rule: does the grouped product of ``rows`` sorted rows
    with ``groups`` matrices ``[k, n]`` take the kernel?  Yes where the
    mean rows a group stay under a quarter of the ridge and the rows
    fit VMEM whole.  Counts each answer (it is asked while a program is
    traced: once a product a layer, and once more where the held
    dispatcher probes its ``expert_fn``'s output shape)."""
    kernel = (_RIDGE_SHARE * rows <= _RIDGE_ROWS * groups
              and rows * max(k, n) * itemsize <= _ROWS_BYTES)
    REGISTRY.counter("moe.grouped.kernel_products" if kernel
                     else "moe.grouped.ragged_products").inc()
    return kernel


def grouped_product(xs, w, sizes):
    """``xs [rows, k]`` sorted by group times ``w [groups, k, n]``,
    ``sizes [groups]`` rows each: ``[rows, n]`` in ``xs``'s type."""
    if takes_kernel(xs.shape[0], *w.shape, xs.dtype.itemsize):
        return grouped_experts(xs, (w,), sizes)
    return lax.ragged_dot(xs, w, sizes)


def grouped_gate_up(xs, gate, up, sizes):
    """``silu(xs g) * (xs u)`` a group, ``gate`` / ``up [groups, k,
    n]``: ``[rows, n]`` in ``xs``'s type.  One pass of the kernel, or
    two ``lax.ragged_dot``."""
    if takes_kernel(xs.shape[0], *gate.shape, xs.dtype.itemsize):
        return grouped_experts(xs, (gate, up), sizes)
    return jax.nn.silu(lax.ragged_dot(xs, gate, sizes)) \
        * lax.ragged_dot(xs, up, sizes)


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
    rows, k = xs.shape
    groups, _, n = mats[0].shape
    item = xs.dtype.itemsize
    if any(m.shape != (groups, k, n) or m.dtype != xs.dtype for m in mats):
        raise ValueError(
            f"grouped_experts: rows {xs.shape} {xs.dtype} need matrices "
            f"[groups, {k}, n] of their type, got "
            f"{[(m.shape, str(m.dtype)) for m in mats]}")
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
