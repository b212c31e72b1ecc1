"""The int8 formats: block-scale quantization of inter-stage transfers on
the device, and channel-scale quantization of resident weights (W8A16).

The TPU-idiomatic analogue of the reference's lossy ZFP activation
compression (reference src/node.py:107, src/dispatcher.py:92): instead of
CPU-side compression of the wire payload, activations are quantized to int8
with one float32 scale per 256-value block *in HBM, inside the compiled
program*, immediately before the stage-to-stage ``ppermute`` — ICI moves
~1.016 bytes/value instead of 2 (bf16) or 4 (f32) — and dequantized right
after.  Pure jnp; XLA fuses both sides into the neighboring stage programs.

Relative error is <= 1/254 of each block's max |value| (symmetric int8),
comparable to the default ZFP tolerance the reference ships.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

#: values per shared scale
BLOCK = 256


class Int8Weight(NamedTuple):
    """A weight leaf held int8 (W8A16): its values in the leaf's own
    shape and one float32 scale a channel of its last axis.  A pytree
    node, so what maps over a weight tree's arrays maps over both."""

    q: jax.Array | np.ndarray
    scale: jax.Array | np.ndarray


def quantize_weight(leaf) -> Int8Weight:
    """Symmetric int8 with channel-wise (last-axis) scales, on the host.
    A 1-D leaf (a norm's scale, a bias) gets a scale an element —
    exactly invertible."""
    a = np.asarray(leaf, np.float32)
    red = tuple(range(a.ndim - 1))      # every axis but the last
    scale = np.maximum(np.abs(a).max(axis=red) / 127.0, 1e-12)
    q = np.clip(np.rint(a / scale), -127, 127).astype(np.int8)
    return Int8Weight(q, scale.astype(np.float32))


def dequantize_weights(tree, dtype):
    """``tree`` with every :class:`Int8Weight` in it a ``dtype`` array
    (inside jit).  The multiply stays next to the consuming op, where
    XLA fuses it: HBM traffic is the int8 bytes plus the scales."""
    def held(x):
        return isinstance(x, Int8Weight)

    return jax.tree.map(
        lambda x: x.q.astype(dtype) * x.scale.astype(dtype) if held(x)
        else x, tree, is_leaf=held)


def quantize_int8_blocks(x: jnp.ndarray, use_pallas: bool | None = None):
    """[..., L] float -> ([..., L] int8, [..., L/BLOCK] f32 scales).

    L must be a multiple of BLOCK (the pipeline pads its transfer buffer
    up-front).  Non-finite inputs are flushed to 0 like the host codec.
    On TPU the fused Pallas kernel (``ops/quant_pallas.py``) runs instead
    of this jnp reference; pass ``use_pallas`` to force either path.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if use_pallas:
        from .quant_pallas import quantize_int8_blocks_pallas
        return quantize_int8_blocks_pallas(x)
    *lead, n = x.shape
    if n % BLOCK:
        raise ValueError(f"last dim {n} not a multiple of {BLOCK}")
    xb = x.reshape(*lead, n // BLOCK, BLOCK).astype(jnp.float32)
    xb = jnp.where(jnp.isfinite(xb), xb, 0.0)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(*lead, n), scale


def quantized_ring_hop(y: jnp.ndarray, axis: str, perm, out_dtype):
    """The int8 stage->successor hop: block-quantize in HBM, ppermute the
    int8 payload + scales over ICI, dequantize on arrival.

    The single definition shared by the inference engine and the trainer's
    straight-through forward — training's forward must stay byte-identical
    to the wire it deploys."""
    from jax import lax
    q, s = quantize_int8_blocks(y)
    q = lax.ppermute(q, axis, perm)
    s = lax.ppermute(s, axis, perm)
    return dequantize_int8_blocks(q, s, out_dtype)


def dequantize_int8_blocks(q: jnp.ndarray, scale: jnp.ndarray,
                           dtype=jnp.float32):
    """Inverse of :func:`quantize_int8_blocks`."""
    *lead, n = q.shape
    xb = q.reshape(*lead, n // BLOCK, BLOCK).astype(jnp.float32)
    return (xb * scale[..., None]).reshape(*lead, n).astype(dtype)
