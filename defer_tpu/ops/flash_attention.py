"""Fused flash attention as a Pallas TPU kernel.

The reference framework has no attention at all (CNN workloads only —
SURVEY.md §2.3); attention is first-class here because the BERT-Base/12
baseline config and the long-context (ring attention) path both spend their
FLOPs in it.  This kernel computes exact softmax attention in O(T) memory by
streaming K/V blocks through VMEM with an online-softmax accumulator —
neither the score matrix [Tq, Tk] nor the full K/V sequence is ever resident
on-chip.

Causal calls — every prompt of every decoder family — take
:func:`_band_kernel`: full heads, grouped queries (``k`` / ``v`` with
fewer heads than ``q``: query head ``j`` reads KV head ``j // (H / Hkv)``
*by index*; repeating 8 heads to 128 would be 16x the prompt's keys) and
a window (``flash_attention(..., window=W)``: row ``t`` attends keys
``s`` with ``0 <= t - s < W``) alike.  The mask is bottom-right aligned:
query row i attends key positions <= i + (Tk - Tq), so decode-style
calls (Tq=1 against a long K/V prefix) attend the whole prefix.  Its
products take the operands in their own type — bf16 on the matrix unit
at full rate, ``p`` cast to the value's type before the second product;
f32 operands stay f32 through both — and accumulate in f32; only the
triangle's (or the band's) edge blocks pay for masks.  What the call
can see decides the arithmetic: there is no argument for it.  The
Pallas call is named by kind, for a device trace's readers:
``flash_causal`` (full heads, no window: GPT-2's and OLMoE's prompts),
``flash_grouped``, ``flash_band`` (a window, grouped or not).

Non-causal calls (the encoder blocks of ``graph/ops.py``; full heads
only) keep the first kernel, :func:`_attn_kernel`.  Tiling: grid =
(batch*heads, Tq/block_q, Tk/block_k), the whole rectangle, with the K
axis innermost; Pallas DMAs one [block_k, d] K/V tile per step while
the (running max, running denominator, rescaled accumulator) state
persists in VMEM scratch across the sequential K iterations.  Its
operands are converted to f32 and its statistics live on one lane of a
``[block_q, 128]`` scratch, which is why no causal call takes it: at
896 x 896 on 25 heads of 64 it ran at 2% of the matrix peak where the
paired kernel runs at 9%, lane padding counted (PERF.md PR 58).

A latent-attention layer's prompt takes a third kernel,
:func:`flash_latent`: a score is the sum of a head's own product and
one over a key every head shares, and the value is narrower than the
key.

**How a grid step of the two causal kernels finds its pair.**  Their
grid is ``(batch, heads, pairs)``: the last axis walks the (query
block, key block) pairs that hold an allowed (query, key) — the causal
triangle, cut by the window where there is one — and no others, so a
call's steps are its live blocks (136 a head at 8192 / 512, where the
rectangle over them has 256; 108 under a window of 4096; 3 at GPT-2's
896, where the rectangle of 128-row blocks had 49).  The sizes,
the blocks and the window are static, so :func:`live_pairs` lists the
pairs on the host, query-block-major, with three bits a pair (the
query block's first, its last, an edge that needs the mask); the table
goes in as three scalar-prefetched int32 rows, the index maps read
``qi[step]`` / ``kb[step]`` to name the blocks to fetch, and the kernel
reads the same column: it starts the statistics on a first pair and
writes the output on a last.  Both kernels form and mask their own
scores and hand them to one :func:`_block_update`, whose statistics
stay a whole lane tile wide (a row's max on every lane, the sum a lane:
one cross-lane reduction a block, none of a single lane's broadcasts).
Two gauges, ``prefill.flash.grid_steps`` and ``.live_steps``, hold the
newest traced call's steps and those of them that work.

On non-TPU backends (CPU tests) the same kernel runs in interpreter mode, so
there is exactly one implementation of the math.  On a TPU backend it is
compiled by Mosaic and a compile error raises — there is no XLA-attention
or interpret-mode fallback.  Established on the v5e (libtpu 0.0.34,
``chip_smoke.py`` and its bring-up probe): Mosaic accepts the 8-row clamp
for bf16 blocks (below the (16, 128) bf16 tile), the lane-1 ``[:, :1]``
reads of the (block_q, 128) m/l scratch (:func:`_attn_kernel`'s; on
``[512, 512]`` blocks they and the two reductions behind them were
half and more of the causal kernels' time, PERF.md PR 50), f32 and bf16 operands,
ragged Tq/Tk, and the kernel inside ``lax.switch`` / ``shard_map`` /
``jax.export``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.registry import REGISTRY

_NEG_INF = float("-inf")
#: lane width of the m/l scratch rows (per-row scalars broadcast across it)
_LANES = 128


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale, block_k, num_kb, t_k):
    """One (query block, key block) step of the non-causal call: every
    key is every query's, so the grid is the whole rectangle and the
    only mask is the key padding's."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)  # [block_q, d]
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # [bq, bk]

    k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = k_pos < t_k  # drop key padding
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


#: query and key rows of one block of the causal kernels
#: (:func:`_band_kernel`, :func:`_latent_kernel`): at 128 a prompt of
#: 8192 is 2080 grid steps a head, and their fixed cost is the kernel's
#: time
_BAND_BLOCK = 512

#: what :func:`live_pairs` says of a pair, as bits: the query block's
#: first pair, its last, and a pair that holds a forbidden (query, key)
#: beside its allowed ones (the kernel masks those pairs alone)
_FIRST, _LAST, _EDGE = 1, 2, 4


def live_pairs(t_q: int, t_k: int, block_q: int, block_k: int,
               window: int | None) -> np.ndarray:
    """The (query block, key block) pairs a causal call works on, in
    the order its grid walks them: int32 ``[3, n]``, a column a pair —
    the query block, the key block, the pair's bits (``_FIRST``,
    ``_LAST``, ``_EDGE``).  A pair is listed when it holds an allowed
    (query, key): the query at key position ``r = row + t_k - t_q``
    (bottom-right aligned) sees the keys ``s <= r``, and with
    ``window`` only those with ``r - s < window``.  Query-block-major,
    a query block's key blocks ascending, so that the running
    statistics live across one query block's pairs.  Host integers, of
    the real (unpadded) sizes.  A query block ahead of every key
    (``t_q > t_k``) is listed once all the same, as an edge, so that
    its rows are written."""
    off, cols = t_k - t_q, []
    for qi in range(-(-t_q // block_q)):
        r0 = qi * block_q + off               # the first query, as a key
        r1 = min(r0 + block_q, t_k) - 1       # the last real one
        lo = 0 if window is None else max(r0 - window + 1, 0) // block_k
        hi = max(r1 // block_k, lo)
        for kb in range(lo, hi + 1):
            k0, k1 = kb * block_k, kb * block_k + block_k - 1
            inside = k1 <= r0 and (
                window is None or r0 + block_q - 1 - k0 < window)
            cols.append((qi, kb, _FIRST * (kb == lo) | _LAST * (kb == hi)
                         | _EDGE * (not inside)))
    return np.asarray(cols, np.int32).T


def _paired_call(kernel, name, operands, *, sizes, in_specs, out_spec,
                 out_shape, interpret):
    """A causal kernel called over the live pairs of ``sizes`` =
    ``(t_q, t_k, block_q, block_k, window)``: grid ``(batch, heads,
    pairs)``, :func:`live_pairs`' table handed over as three
    scalar-prefetched rows, which every index map (``(bi, hi, step, qi,
    kb, bits)``) and the kernel read at column ``step``; sets the two
    gauges of the newest traced call."""
    t_q, t_k, block_q, block_k, _ = sizes
    pairs = live_pairs(*sizes)
    (b, h), n = out_shape.shape[:2], pairs.shape[1]
    # :func:`_block_update`'s statistics: rows of a lane tile, or of a
    # whole key block where that is narrower or no multiple of one
    w = _LANES if block_k % _LANES == 0 else block_k
    REGISTRY.gauge("prefill.flash.grid_steps").set(b * h * n)
    # a query block ahead of every key is listed and holds nothing
    REGISTRY.gauge("prefill.flash.live_steps").set(
        b * h * (n - max(t_q - t_k, 0) // block_q))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, n), in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((block_q, w), jnp.float32),   # running max
                pltpu.VMEM((block_q, w), jnp.float32),   # running sum a lane
                pltpu.VMEM((block_q, out_shape.shape[-1]), jnp.float32)]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name,
    )(*(jnp.asarray(row) for row in pairs), *operands)


def _q_block(bi, hi, step, qi, kb, bits):
    """Index map of a query-side operand and of the output."""
    return (bi, hi, qi[step], 0)


def _k_block(group: int):
    """Index map of a key-side operand that ``group`` query heads
    share a head of."""
    return lambda bi, hi, step, qi, kb, bits: (bi, hi // group, kb[step], 0)


def _block_update(s, v, m_ref, l_ref, acc_ref):
    """One block of the online softmax, the causal kernels' one spelling
    of it: ``s`` the block's f32 scores ``[block_q, block_k]`` (``-inf``
    where a pair is forbidden), ``v`` its values ``[block_k, dv]``.
    The scratch rows are ``w = l_ref.shape[1]`` lanes wide, ``block_k``
    a multiple of it: ``m_ref [block_q, w]`` holds a row's running max
    on every lane, ``l_ref [block_q, w]`` the running sum *a lane* —
    lane ``j`` sums the keys ``j, w + j, ...`` of every block, rescaled
    with the rest, and the lanes are added once, in :func:`_finish` —,
    ``acc_ref [block_q, dv]`` the values' sum.  So a block pays one
    cross-lane reduction (its max, after the column groups are folded
    elementwise) and nothing is broadcast from a single lane."""
    w = l_ref.shape[1]
    cols = [s[:, j:j + w] for j in range(0, s.shape[1], w)]
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(
        functools.reduce(jnp.maximum, cols), axis=-1, keepdims=True))
    # a row that has seen no key yet carries -inf: keep it inert
    safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
    alpha = jnp.exp(m_prev - safe)
    ps = [jnp.exp(c - safe) for c in cols]
    l_ref[...] = l_ref[...] * alpha + functools.reduce(jnp.add, ps)
    p = ps[0] if len(ps) == 1 else jnp.concatenate(ps, axis=-1)
    acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) \
        + jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _lanes(x, n: int):
    """``x`` ``[rows, w]``, a row's one value on every lane, as ``[rows,
    n]``."""
    w = x.shape[1]
    if n % w == 0:
        return x if n == w else jnp.concatenate([x] * (n // w), axis=-1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _start(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _finish(o_ref, l_ref, acc_ref):
    l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l, 1e-20)).astype(o_ref.dtype)


def _masked(s, qi, kb, t_q: int, t_k: int, window):
    """An edge pair's scores ``s`` (query block ``qi``, key block
    ``kb``) with ``-inf`` where :func:`live_pairs`' rule forbids the
    (query, key) or the key is padding."""
    k_pos = kb * s.shape[1] + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = qi * s.shape[0] + (t_k - t_q) + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    mask = jnp.logical_and(k_pos < t_k, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return jnp.where(mask, s, _NEG_INF)


def _pair_step(table, scores, v_ref, o_ref, scratch, *, t_q, t_k, window):
    """One grid step of a causal kernel: the step's pair from the
    ``table`` (the three prefetched rows), the statistics started on a
    query block's first pair, the kernel's own ``scores()`` (f32,
    ``[block_q, block_k]``), masked on an edge, through
    :func:`_block_update`, the output written on the last pair."""
    qi, kb, bits = (row[pl.program_id(2)] for row in table)
    pl.when(bits & _FIRST != 0)(lambda: _start(*scratch))
    pl.when(bits & _EDGE == 0)(
        lambda: _block_update(scores(), v_ref[0, 0], *scratch))
    pl.when(bits & _EDGE != 0)(
        lambda: _block_update(_masked(scores(), qi, kb, t_q, t_k, window),
                              v_ref[0, 0], *scratch))
    pl.when(bits & _LAST != 0)(lambda: _finish(o_ref, *scratch[1:]))


def _band_kernel(qi_ref, kb_ref, bits_ref, q_ref, k_ref, v_ref, o_ref,
                 *scratch, scale, **band):
    """One (query block, key block) pair of the band: causal,
    bottom-right aligned, reaching back ``window`` keys (the query's own
    counted) where there is one.  q_ref / o_ref ``[1, 1, block_q, d]``,
    k_ref / v_ref ``[1, 1, block_k, d]``."""
    def scores():
        return jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale    # [bq, bk]

    _pair_step((qi_ref, kb_ref, bits_ref), scores, v_ref, o_ref, scratch,
               **band)


def _band_attention(q, k, v, *, window, block_q, block_k, interpret):
    """:func:`flash_attention`'s causal path (its docstring): full
    heads, grouped queries, a window."""
    b, h, t_q, d = q.shape
    hkv, t_k = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{hkv} KV heads do not divide {h} query heads")
    block_q = min(block_q, max(8, 1 << (t_q - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (t_k - 1).bit_length()))
    qp = _pad_to(_pad_to(q, 2, block_q), 3, _LANES)
    kp = _pad_to(_pad_to(k, 2, block_k), 3, _LANES)
    vp = _pad_to(_pad_to(v, 2, block_k), 3, _LANES)
    dp, kv_block = qp.shape[-1], _k_block(h // hkv)
    out = _paired_call(
        functools.partial(_band_kernel, scale=1.0 / math.sqrt(d), t_q=t_q,
                          t_k=t_k, window=window),
        # by kind, so that a device trace tells a window layer's calls
        # from a grouped layer's and a full-head one's
        "flash_band" if window is not None else
        "flash_grouped" if h != hkv else "flash_causal",
        (qp, kp, vp), sizes=(t_q, t_k, block_q, block_k, window),
        in_specs=[pl.BlockSpec((1, 1, block_q, dp), _q_block),
                  pl.BlockSpec((1, 1, block_k, dp), kv_block),
                  pl.BlockSpec((1, 1, block_k, dp), kv_block)],
        out_spec=pl.BlockSpec((1, 1, block_q, dp), _q_block),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=interpret)
    return out[:, :, :t_q, :d]


def _latent_kernel(qi_ref, kb_ref, bits_ref, qn_ref, qr_ref, kn_ref, kr_ref,
                   v_ref, o_ref, *scratch, scale, t):
    """One (query block, key block) pair of one head of
    :func:`flash_latent`: causal, queries and keys at the same
    positions (an edge is a diagonal block).  A score is the sum of two
    products — the head's own part ``qn . kn`` and the part every head
    shares ``qr . kr`` — so the shared key is never laid beside each
    head's own; the value has a width of its own.  qn_ref / kn_ref
    ``[1, 1, block, dn]``, qr_ref / kr_ref ``[1, 1, block, dr]``, v_ref
    / o_ref ``[1, 1, block, dv]``."""
    def scores():
        nt = (((1,), (1,)), ((), ()))
        return (jax.lax.dot_general(qn_ref[0, 0], kn_ref[0, 0], nt,
                                    preferred_element_type=jnp.float32)
                + jax.lax.dot_general(qr_ref[0, 0], kr_ref[0, 0], nt,
                                      preferred_element_type=jnp.float32)
                ) * scale                                 # [block, block]

    _pair_step((qi_ref, kb_ref, bits_ref), scores, v_ref, o_ref, scratch,
               t_q=t, t_k=t, window=None)


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
    accumulate in f32; blocks of :data:`_BAND_BLOCK` rows, the causal
    triangle's alone (:func:`live_pairs`); returns ``[b, h, t, dv]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, t, dn = q_nope.shape
    dr, dv = q_rope.shape[-1], v.shape[-1]
    block = min(block or _BAND_BLOCK, max(8, 1 << (t - 1).bit_length()))
    qn, qr, kn, kr, vp = (_pad_to(a, 2, block)
                          for a in (q_nope, q_rope, k_nope, k_rope, v))
    own, shared = _k_block(1), _k_block(h)    # the one rotated key: head 0
    out = _paired_call(
        functools.partial(_latent_kernel, scale=scale, t=t), "flash_latent",
        (qn, qr, kn, kr, vp), sizes=(t, t, block, block, None),
        in_specs=[pl.BlockSpec((1, 1, block, dn), _q_block),
                  pl.BlockSpec((1, 1, block, dr), _q_block),
                  pl.BlockSpec((1, 1, block, dn), own),
                  pl.BlockSpec((1, 1, block, dr), shared),
                  pl.BlockSpec((1, 1, block, dv), own)],
        out_spec=pl.BlockSpec((1, 1, block, dv), _q_block),
        out_shape=jax.ShapeDtypeStruct((b, h, qn.shape[2], dv), v.dtype),
        interpret=interpret)
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
    softmax.  ``causal=True`` takes the paired kernel (the module's
    docstring), whose products run in the operands' own type; with Tq !=
    Tk it uses bottom-right alignment (decode semantics); with
    ``window`` a row attends its ``window`` newest keys, itself counted,
    and no key block outside that band is fetched; grouped queries and a
    window are causal calls only.  ``block_q`` / ``block_k`` default to
    :data:`_BAND_BLOCK` on the causal path (the fastest of 128, 256 and
    512 at 512, 896 and 1024 rows on the v5e, PERF.md PR 58) and to 128
    on the non-causal one, clamped to the sizes.
    ``interpret=None`` auto-selects interpreter mode
    off-TPU so tests exercise the identical kernel on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if causal:
        return _band_attention(
            q, k, v, window=window, block_q=block_q or _BAND_BLOCK,
            block_k=block_k or _BAND_BLOCK, interpret=interpret)
    if window is not None or k.shape[1] != q.shape[1]:
        raise ValueError("grouped queries and a window are the causal "
                         "kernel's: pass causal=True")
    block_q, block_k = block_q or 128, block_k or 128
    b, h, t_q, d = q.shape
    t_k = k.shape[2]

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
        _attn_kernel, scale=1.0 / math.sqrt(d), block_k=block_k,
        num_kb=num_kb, t_k=t_k)

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
        out_shape=jax.ShapeDtypeStruct((b * h, tqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, dp), jnp.float32),      # value accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)

    return out[:, :t_q, :d].reshape(b, h, t_q, d)
