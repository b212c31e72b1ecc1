"""Pallas TPU kernel for block-scale int8 wire quantization.

One VMEM pass fuses the whole quantize step the jnp reference
(``ops/quant.py``) expresses as amax -> scale -> divide -> round -> clip:
each grid step DMAs one row-tile of the transfer buffer into VMEM, computes
per-256-value-block scales, and stores the int8 payload plus f32 scales.
This is the hot half of the ``wire="int8"`` path (it runs every pipeline
step on every device, immediately before the stage->stage ``ppermute`` —
runtime/spmd.py); dequantize stays plain jnp because XLA fuses a single
multiply into the consuming stage for free.

Off-TPU the identical kernel runs in interpreter mode (same math, one
implementation) — the pattern established by ``ops/flash_attention.py``.
On a TPU backend Mosaic compiles it and a compile error raises.
Established on the v5e (libtpu 0.0.34, ``chip_smoke.py`` and its bring-up
probe): the direct f32 -> int8 cast after ``jnp.round`` and the
``(_ROWS, 1)`` f32 scale block compile, inside ``lax.scan`` /
``shard_map`` too, and the output is bit-identical to the jnp reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .quant import BLOCK

#: quant blocks handled per grid step.  The kernel views the input as
#: [n_blocks, BLOCK] — one 256-value quant block per row — so the Pallas
#: block shape is (_ROWS, BLOCK): both dims satisfy the TPU tiling rule
#: (rows divisible by 8, lanes divisible by 128), and the scale output's
#: (_ROWS, 1) block is legal because 1 IS its array's full last dim.
#: 128 rows x 256 lanes = 128 KiB f32 in VMEM per step.
_ROWS = 128


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)          # [_ROWS, BLOCK]
    x = jnp.where(jnp.isfinite(x), x, 0.0)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8_blocks_pallas(x: jnp.ndarray,
                                interpret: bool | None = None):
    """Drop-in Pallas version of ``quant.quantize_int8_blocks``.

    [..., L] float -> ([..., L] int8, [..., L/BLOCK] f32), L % BLOCK == 0.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    *lead, n = x.shape
    if n % BLOCK:
        raise ValueError(f"last dim {n} not a multiple of {BLOCK}")
    rows = 1
    for d in lead:
        rows *= d
    nblocks = rows * (n // BLOCK)
    xf = x.reshape(nblocks, BLOCK)

    # ragged edge is safe: each row is one independent quant block, so the
    # garbage Pallas pads the final partial tile with never reaches a real
    # row's scale or payload
    grid = (pl.cdiv(nblocks, _ROWS),)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((_ROWS, BLOCK), lambda r: (r, 0))],
        out_specs=[
            pl.BlockSpec((_ROWS, BLOCK), lambda r: (r, 0)),
            pl.BlockSpec((_ROWS, 1), lambda r: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((nblocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xf)
    return q.reshape(*lead, n), s.reshape(*lead, n // BLOCK)
