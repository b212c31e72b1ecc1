"""Pallas TPU kernel: write one new KV row a sequence into a cache
buffer, in place, each at its sequence's OWN position.

The serving engine (``serve/engine.py``) holds ``[b, kv, L, hd]`` cache
buffers whose sequences sit at different positions.  XLA:TPU keeps such
an f32 array with the positions on the lanes when ``hd`` is under a
lane row (128): ``hd`` 64 would otherwise be padded to twice its size.
What the obvious forms of the write cost in that layout, at gpt2-xl,
16 sequences, 192 positions (docs/DECODE_CLIFF.md, "The engine"):

* ``jax.vmap`` of a ``dynamic_update_slice`` over the positions is a
  batched scatter: the compiler copies the whole buffer into the
  scatter's layout and back.
* one scalar-indexed ``dynamic_update_slice`` a sequence touches one
  lane of every tile, which XLA runs as a read-modify-write of the
  sequence's item: 5.6 us a row, 8.4 ms a step for 1,536 rows.

Here the buffer is viewed as ``[b, kv, hd, L]`` — the same bytes, so
both ``swapaxes`` compile to bitcasts — and aliased to the output.  Each
grid step moves the one 128-position window that holds its sequence's
position through VMEM and replaces one lane of it.

Off-TPU the identical kernel runs in interpreter mode, as the other
kernels of this package do.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions a window holds: one lane row
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
