"""Fused flash attention as a Pallas TPU kernel.

The reference framework has no attention at all (CNN workloads only —
SURVEY.md §2.3); attention is first-class here because the BERT-Base/12
baseline config and the long-context (ring attention) path both spend their
FLOPs in it.  This kernel computes exact softmax attention in O(T) memory by
streaming K/V blocks through VMEM with an online-softmax accumulator —
neither the score matrix [Tq, Tk] nor the full K/V sequence is ever resident
on-chip.

Tiling: grid = (batch*heads, Tq/block_q, Tk/block_k) with the K axis
innermost; Pallas DMAs one [block_k, d] K/V tile per step while the
(running max, running denominator, rescaled accumulator) state persists in
VMEM scratch across the sequential K iterations.  Both matmuls per block
(QK^T and PV) hit the MXU at [block_q, d] x [d, block_k] and
[block_q, block_k] x [block_k, d].

Causal masking uses bottom-right alignment: query row i attends to key
positions <= i + (Tk - Tq), so decode-style calls (Tq=1 against a long K/V
prefix) attend to the whole prefix.

Grouped queries and a window (``flash_attention(..., window=W)`` or
``k`` / ``v`` with fewer heads than ``q``) take a second kernel,
:func:`_band_kernel`: query head ``j`` reads KV head ``j // (H / Hkv)``
*by index* (repeating 8 heads to 128 would be 16x the prompt's keys),
row ``t`` attends keys ``s`` with ``0 <= t - s < W``, and a key block
wholly outside a query block's band — behind the window or ahead of the
diagonal — is neither fetched nor computed: the grid's key axis runs
over the band's blocks alone and its index map starts at the band's
first.  Its products take the operands in their own type (bf16 on the
matrix unit at full rate) and accumulate in f32; only a band's edge
blocks pay for masks.

A latent-attention layer's prompt takes a third kernel,
:func:`flash_latent`: a score is the sum of a head's own product and
one over a key every head shares, and the value is narrower than the
key.

On non-TPU backends (CPU tests) the same kernel runs in interpreter mode, so
there is exactly one implementation of the math.  On a TPU backend it is
compiled by Mosaic and a compile error raises — there is no XLA-attention
or interpret-mode fallback.  Established on the v5e (libtpu 0.0.34,
``chip_smoke.py`` and its bring-up probe): Mosaic accepts the 8-row clamp
for bf16 blocks (below the (16, 128) bf16 tile), the lane-1 ``[:, :1]``
reads of the (block_q, 128) m/l scratch, f32 and bf16 operands, ragged
Tq/Tk, and the kernel inside ``lax.switch`` / ``shard_map`` /
``jax.export``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
#: lane width of the m/l scratch rows (per-row scalars broadcast across it)
_LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, block_q, block_k, num_kb, t_q, t_k, causal):
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # bottom-right causal alignment: q row r is global position
    # r + qi*block_q + (t_k - t_q) in key coordinates
    causal_off = t_k - t_q
    if causal:
        # this K block is fully in the future of every query row -> skip
        live = kb * block_k <= qi * block_q + block_q - 1 + causal_off
    else:
        live = True

    @pl.when(live)
    def _accumulate():
        q = q_ref[0].astype(jnp.float32)  # [block_q, d]
        k = k_ref[0].astype(jnp.float32)  # [block_k, d]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < t_k  # drop key padding
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0) + causal_off
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # [bq, 1]
        l_prev = l_ref[:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        # rows with no unmasked key yet carry m = -inf; keep them inert
        safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
        alpha = jnp.where(m_prev == _NEG_INF, 0.0, jnp.exp(m_prev - safe_m))
        p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    rem = -size % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


#: query and key rows of one block of :func:`_band_kernel`: at 128 a
#: prompt of 8192 is 4096 grid steps a head, and their fixed cost is
#: the kernel's time
_BAND_BLOCK = 512


def _band_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, block_q, block_k, num_kb, t_q, t_k, window):
    """One key block of one query block's band: causal, bottom-right
    aligned, reaching back ``window`` keys (the query's own counted)
    where there is one.  q_ref / o_ref ``[1, 1, block_q, d]``, k_ref /
    v_ref ``[1, 1, block_k, d]``: block ``first + kb`` of the keys,
    ``first`` the band's first block (``_band_first``: the index map
    used the same)."""
    qi, kb = pl.program_id(2), pl.program_id(3)
    off = t_k - t_q
    first = _band_first(qi, block_q, block_k, off, window)
    k0 = (first + kb) * block_k                # this block's first key
    r0 = qi * block_q + off                    # first query, as a key

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(edge):
        q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        if edge:
            k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            q_pos = r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(k_pos < t_k, q_pos >= k_pos)
            if window is not None:
                mask = jnp.logical_and(mask, q_pos - k_pos < window)
            s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            # rows with no key yet carry m = -inf; keep them inert
            safe_m = jnp.where(m_new == _NEG_INF, 0.0, m_new)
            alpha = jnp.where(m_prev == _NEG_INF, 0.0,
                              jnp.exp(m_prev - safe_m))
            p = jnp.where(mask, jnp.exp(s - safe_m), 0.0)
        else:
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # a block is in the band when some (query, key) pair of it is
    # allowed, and inside it when every pair is
    r1, k1 = r0 + block_q - 1, k0 + block_k - 1
    live = jnp.logical_and(k0 <= r1, k0 < t_k)
    inside = jnp.logical_and(k1 <= r0, k1 < t_k)
    if window is not None:
        live = jnp.logical_and(live, r0 - k1 < window)
        inside = jnp.logical_and(inside, r1 - k0 < window)
    pl.when(inside)(lambda: accumulate(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(inside)))(
        lambda: accumulate(True))

    @pl.when(kb == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-20)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _band_first(qi, block_q: int, block_k: int, off: int, window):
    """The first key block of query block ``qi``'s band."""
    if window is None:
        return 0 * qi
    return jnp.maximum(qi * block_q + off - window + 1, 0) // block_k


def band_key_steps(t_q: int, t_k: int, block_q: int, block_k: int,
                   window: int | None) -> int:
    """Key blocks the widest band of any query block spans: the length
    of the band kernel's key axis (host integers, padded sizes)."""
    off, last_kb = t_k - t_q, -(-t_k // block_k) - 1
    steps = 1
    for qi in range(-(-t_q // block_q)):
        r0 = qi * block_q + off
        first = 0 if window is None else max(r0 - window + 1, 0) // block_k
        steps = max(steps,
                    min((r0 + block_q - 1) // block_k, last_kb) - first + 1)
    return steps


def _band_attention(q, k, v, *, window, block_q, block_k, interpret):
    """:func:`flash_attention`'s causal path for grouped queries and a
    window (its docstring)."""
    b, h, t_q, d = q.shape
    hkv, t_k = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{hkv} KV heads do not divide {h} query heads")
    g, off = h // hkv, t_k - t_q
    block_q = min(block_q, max(8, 1 << (t_q - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (t_k - 1).bit_length()))
    qp = _pad_to(_pad_to(q, 2, block_q), 3, _LANES)
    kp = _pad_to(_pad_to(k, 2, block_k), 3, _LANES)
    vp = _pad_to(_pad_to(v, 2, block_k), 3, _LANES)
    dp, tqp = qp.shape[-1], qp.shape[2]
    num_qb, last_kb = tqp // block_q, kp.shape[2] // block_k - 1

    # the key axis holds the widest band's blocks; a narrower band's
    # steps past its last block name that block again (not fetched
    # twice) and compute nothing
    num_kb = band_key_steps(tqp, kp.shape[2], block_q, block_k, window)

    def kv_block(bi, hi, qi, kb):
        first = _band_first(qi, block_q, block_k, off, window)
        last = jnp.minimum((qi * block_q + off + block_q - 1) // block_k,
                           last_kb)
        return (bi, hi // g, jnp.minimum(first + kb, last), 0)

    kernel = functools.partial(
        _band_kernel, scale=1.0 / math.sqrt(d), block_q=block_q,
        block_k=block_k, num_kb=num_kb, t_q=t_q, t_k=t_k, window=window)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dp),
                         lambda bi, hi, qi, kb: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dp), kv_block),
            pl.BlockSpec((1, 1, block_k, dp), kv_block),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dp),
                               lambda bi, hi, qi, kb: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, dp), jnp.float32),      # value accumulator
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        # by kind, so that a device trace tells a window layer's calls
        name="flash_band" if window is not None else "flash_grouped",
    )(qp, kp, vp)
    return out[:, :, :t_q, :d]


def _latent_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_ref,
                   l_ref, acc_ref, *, scale, block, t):
    """One key block of one query block of one head of
    :func:`flash_latent`: causal, queries and keys at the same
    positions.  A score is the sum of two products — the head's own
    part ``qn . kn`` and the part every head shares ``qr . kr`` — so
    the shared key is never laid beside each head's own; the value has
    a width of its own.  qn_ref / kn_ref ``[1, 1, block, dn]``, qr_ref
    / kr_ref ``[1, 1, block, dr]``, v_ref / o_ref ``[1, 1, block,
    dv]``."""
    qi, kb = pl.program_id(2), pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(edge):
        nt = (((1,), (1,)), ((), ()))
        v = v_ref[0, 0]
        s = (jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], nt,
                                 preferred_element_type=jnp.float32)
             + jax.lax.dot_general(qr_ref[0, 0], kr_ref[0, 0], nt,
                                   preferred_element_type=jnp.float32)
             ) * scale                                    # [block, block]
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        if edge:
            # the diagonal block: a row sees the keys up to its own, and
            # no padding (every row has its own key or, a padded row,
            # every real one: none is left without)
            k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            s = jnp.where(jnp.logical_and(qi * block + k_pos < t,
                                          q_pos >= k_pos), s, _NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), l_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    # key blocks ahead of the diagonal are neither fetched (the index
    # map names the diagonal block again) nor computed
    pl.when(kb < qi)(lambda: accumulate(False))
    pl.when(kb == qi)(lambda: accumulate(True))

    @pl.when(kb == pl.num_programs(3) - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block", "interpret"))
def flash_latent(q_nope, q_rope, k_nope, k_rope, v, *, scale: float,
                 block: int | None = None, interpret: bool | None = None):
    """Causal attention over the expanded heads of a latent-attention
    layer's prompt, scores never materialized: ``softmax((q_nope .
    k_nope + q_rope . k_rope) * scale) v``, row ``t`` over rows ``<=
    t``.  q_nope / k_nope ``[b, h, t, dn]``, q_rope ``[b, h, t, dr]``,
    **k_rope** ``[b, 1, t, dr]`` — the one rotated key all heads share,
    read by index and not repeated ``h`` times —, **v** ``[b, h, t,
    dv]``, a width of its own (a key of ``dn + dr`` laid out whole and
    a value padded to it would cost 192 + 192 a pair where 192 + 128
    are needed).  ``scale`` is the block's (not one over a width's
    root).  Operands go to the matrix unit in their own type and
    accumulate in f32; blocks of :data:`_BAND_BLOCK` rows; returns
    ``[b, h, t, dv]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, t, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    block = min(block or _BAND_BLOCK, max(8, 1 << (t - 1).bit_length()))
    qn, qr, kn, kr, vp = (_pad_to(a, 2, block)
                          for a in (q_nope, q_rope, k_nope, k_rope, v))
    num_b = qn.shape[2] // block

    def q_block(bi, hi, qi, kb):
        return (bi, hi, qi, 0)

    def k_block(bi, hi, qi, kb):
        return (bi, hi, jnp.minimum(kb, qi), 0)

    def shared_block(bi, hi, qi, kb):
        return (bi, 0, jnp.minimum(kb, qi), 0)

    out = pl.pallas_call(
        functools.partial(_latent_kernel, scale=scale, block=block, t=t),
        grid=(b, h, num_b, num_b),
        in_specs=[pl.BlockSpec((1, 1, block, dn), q_block),
                  pl.BlockSpec((1, 1, block, dr), q_block),
                  pl.BlockSpec((1, 1, block, dn), k_block),
                  pl.BlockSpec((1, 1, block, dr), shared_block),
                  pl.BlockSpec((1, 1, block, dv), k_block)],
        out_specs=pl.BlockSpec((1, 1, block, dv), q_block),
        out_shape=jax.ShapeDtypeStruct((b, h, qn.shape[2], dv), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((block, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block, dv), jnp.float32),      # value accumulator
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_latent",
    )(qn, qr, kn, kr, vp)
    return out[:, :, :t]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = False,
                    window: int | None = None, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None):
    """Exact attention ``softmax(q kᵀ/√d) v`` without materializing scores.

    q: [B, H, Tq, D]; k, v: [B, Hkv, Tk, D], ``Hkv`` dividing ``H``
    (query head ``j`` reads KV head ``j // (H / Hkv)``, by index).  Any
    sizes — inputs are padded to
    MXU-aligned tiles internally and the padding is masked out of the
    softmax.  ``causal=True`` with Tq != Tk uses bottom-right alignment
    (decode semantics); with ``window`` a row attends its ``window``
    newest keys, itself counted, and no key block outside that band is
    fetched.  ``block_q`` / ``block_k`` default to 128, and to
    :data:`_BAND_BLOCK` on the grouped or windowed path.
    ``interpret=None`` auto-selects interpreter mode
    off-TPU so tests exercise the identical kernel on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if window is not None or k.shape[1] != q.shape[1]:
        if not causal:
            raise ValueError("grouped queries and a window are the causal "
                             "kernel's: pass causal=True")
        return _band_attention(
            q, k, v, window=window, block_q=block_q or _BAND_BLOCK,
            block_k=block_k or _BAND_BLOCK, interpret=interpret)
    block_q, block_k = block_q or 128, block_k or 128
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    scale = 1.0 / math.sqrt(d)
    orig_dtype = q.dtype

    block_q = min(block_q, max(8, 1 << (t_q - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (t_k - 1).bit_length()))

    qp = _pad_to(q.reshape(b * h, t_q, d), 1, block_q)
    kp = _pad_to(k.reshape(b * h, t_k, d), 1, block_k)
    vp = _pad_to(v.reshape(b * h, t_k, d), 1, block_k)
    # pad head dim to the 128-lane boundary (zeros are exact: they add
    # nothing to q·k scores and the extra output columns are sliced off)
    qp, kp, vp = (_pad_to(x, 2, _LANES) for x in (qp, kp, vp))
    dp = qp.shape[-1]
    tqp, tkp = qp.shape[1], kp.shape[1]
    num_qb, num_kb = tqp // block_q, tkp // block_k

    kernel = functools.partial(
        _attn_kernel, scale=scale, block_q=block_q, block_k=block_k,
        num_kb=num_kb, t_q=t_q, t_k=t_k, causal=causal)

    out = pl.pallas_call(
        kernel,
        grid=(b * h, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((1, block_q, dp), lambda bh, qi, kb: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, kb: (bh, kb, 0)),
            pl.BlockSpec((1, block_k, dp), lambda bh, qi, kb: (bh, kb, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dp),
                               lambda bh, qi, kb: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, tqp, dp), orig_dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, dp), jnp.float32),      # value accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)

    return out[:, :t_q, :d].reshape(b, h, t_q, d)
