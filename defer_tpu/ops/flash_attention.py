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

**Which calls are padded, and which read the operand as it lies.**
``flash_attention`` takes heads laid head-major, ``[b, h, t, d]``, and
pads them in HBM: rows to a whole block, a head's width to the 128
lanes (zeros add nothing to a score, the output's padding is sliced
off).  At heads of 128 on prompts of whole blocks that is nothing; at
GPT-2's 25 heads of 64 on 896 rows it was 2.3x the bytes, three pads,
four transposes and a slice a layer around a kernel that fetched the
zeros (PERF.md PR 58 / PR 70).  :func:`flash_causal_columns` is the
entry for a projection's own columns, ``[b, t, heads * d]``: full,
unwindowed heads that are a whole fraction of a lane row wide (``d <
128``, ``128 % d == 0``) go to :func:`_lane_row_kernel` *token-major,
unpadded* — grid ``(batch, lane rows, pairs)``, a block ``[block, 128]``
columns of the operand, ``128 // d`` heads side by side, each with its
own statistics (their queries stacked into one ``[heads * block_q,
128]`` operand, the other heads' columns zeroed: one product a pair,
the contraction a padded head costs and no more), the output written
with the heads already merged.  Every other geometry is laid head-major
by that entry and takes the padded path: one algorithm whose block holds
``128 // d`` heads, 1 for heads of 128.  The gauge
``prefill.flash.heads_a_block`` says which.  **The out-of-bounds rule
an edge keeps:** what an unpadded operand's block holds beyond the
array — the last lane row of an odd head count (25 heads are 12 lane
rows and a half), the rows past the prompt's end in its last block — is
undefined and may be NaN, and ``0 * NaN`` is NaN.  So masking scores is
not enough: the keys' dead columns are zeroed before the contraction
(every pair of the last lane row), the values' dead rows before the
second product (an edge pair), and a dead query row or column only ever
reaches output rows and columns that lie beyond the array and are not
written back.

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

**How a grid step of the causal kernels finds its pair.**  Their
grid is ``(batch, heads, pairs)`` (``(batch, lane rows, pairs)``
token-major): the last axis walks the (query
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
writes the output on a last.  The kernels form and mask their own
scores and hand them to one :func:`_block_update`, whose statistics
stay a whole lane tile wide (a row's max on every lane, the sum a lane:
one cross-lane reduction a block, none of a single lane's broadcasts).
Three gauges, ``prefill.flash.grid_steps``, ``.live_steps`` and
``.heads_a_block``, hold the newest traced call's steps, those of them
that work and the heads one block holds.

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


def _paired_call(kernel, name, operands, *, sizes, lead, dv, in_specs,
                 out_spec, out_shape, interpret, heads=1):
    """A causal kernel called over the live pairs of ``sizes`` =
    ``(t_q, t_k, block_q, block_k, window)``: grid ``lead + (pairs,)``,
    ``lead`` the call's ``(batch, heads)`` — of a token-major call
    ``(batch, lane rows)``, ``heads`` heads a block, their statistics
    and their sums of ``dv`` columns stacked in the scratch —,
    :func:`live_pairs`' table handed over as three scalar-prefetched
    rows, which every index map (``(bi, hi, step, qi, kb, bits)``) and
    the kernel read at column ``step``; sets the three gauges of the
    newest traced call."""
    t_q, t_k, block_q, block_k, _ = sizes
    pairs = live_pairs(*sizes)
    (b, h), n = lead, pairs.shape[1]
    # :func:`_block_update`'s statistics: rows of a lane tile, or of a
    # whole key block where that is narrower or no multiple of one
    w = _LANES if block_k % _LANES == 0 else block_k
    REGISTRY.gauge("prefill.flash.grid_steps").set(b * h * n)
    # a query block ahead of every key is listed and holds nothing
    REGISTRY.gauge("prefill.flash.live_steps").set(
        b * h * (n - max(t_q - t_k, 0) // block_q))
    REGISTRY.gauge("prefill.flash.heads_a_block").set(heads)
    rows = heads * block_q
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, n), in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, w), jnp.float32),   # running max
                pltpu.VMEM((rows, w), jnp.float32),   # running sum a lane
                pltpu.VMEM((rows, dv), jnp.float32)]),
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
    with the rest, and the lanes are added once, in :func:`_normed` —,
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


def _normed(l_ref, acc_ref):
    """The values' sum over the rows' sums: a query block's output."""
    l = jnp.sum(l_ref[...], axis=-1, keepdims=True)
    return acc_ref[...] / jnp.maximum(l, 1e-20)


def _masked(s, qi, kb, t_q: int, t_k: int, window, heads: int = 1):
    """An edge pair's scores ``s`` (query block ``qi``, key block
    ``kb``; ``heads`` heads' rows stacked) with ``-inf`` where
    :func:`live_pairs`' rule forbids the (query, key) or the key is
    padding, or lies beyond the operand."""
    shape = (s.shape[0] // heads, s.shape[1])
    k_pos = kb * shape[1] + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    q_pos = qi * shape[0] + (t_k - t_q) + jax.lax.broadcasted_iota(
        jnp.int32, shape, 0)
    mask = jnp.logical_and(k_pos < t_k, q_pos >= k_pos)
    if window is not None:
        mask = jnp.logical_and(mask, q_pos - k_pos < window)
    if heads > 1:
        mask = jnp.concatenate([mask] * heads, axis=0)
    return jnp.where(mask, s, _NEG_INF)


def _pair_step(table, scores, values, write, scratch, *, t_q, t_k, window,
               heads=1):
    """One grid step of a causal kernel: the step's pair from the
    ``table`` (the three prefetched rows), the statistics started on a
    query block's first pair, the kernel's own ``scores()`` (f32,
    ``[heads * block_q, block_k]``), masked on an edge, through
    :func:`_block_update` with its ``values(kb)`` (``kb`` None, or on
    an edge the key block: an operand that is not padded leaves what it
    likes in a block's rows beyond ``t_k``, and the kernel zeroes
    them), the query block's output handed to ``write`` on the last
    pair."""
    qi, kb, bits = (row[pl.program_id(2)] for row in table)
    pl.when(bits & _FIRST != 0)(lambda: _start(*scratch))
    pl.when(bits & _EDGE == 0)(
        lambda: _block_update(scores(), values(None), *scratch))
    pl.when(bits & _EDGE != 0)(
        lambda: _block_update(
            _masked(scores(), qi, kb, t_q, t_k, window, heads),
            values(kb), *scratch))
    pl.when(bits & _LAST != 0)(lambda: write(_normed(*scratch[1:])))


def _store(o_ref):
    """``write`` of a kernel whose output block is one head's."""
    def write(y):
        o_ref[0, 0] = y.astype(o_ref.dtype)
    return write


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

    _pair_step((qi_ref, kb_ref, bits_ref), scores, lambda kb: v_ref[0, 0],
               _store(o_ref), scratch, **band)


def _clamped(block: int, t: int) -> int:
    """A block of ``block`` rows, or the power of two that holds ``t``."""
    return min(block, max(8, 1 << (t - 1).bit_length()))


def _band_attention(q, k, v, *, window, block_q, block_k, interpret):
    """:func:`flash_attention`'s causal path (its docstring): full
    heads, grouped queries, a window."""
    b, h, t_q, d = q.shape
    hkv, t_k = k.shape[1], k.shape[2]
    if h % hkv:
        raise ValueError(f"{hkv} KV heads do not divide {h} query heads")
    block_q, block_k = _clamped(block_q, t_q), _clamped(block_k, t_k)
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
        lead=(b, h), dv=dp,
        in_specs=[pl.BlockSpec((1, 1, block_q, dp), _q_block),
                  pl.BlockSpec((1, 1, block_k, dp), kv_block),
                  pl.BlockSpec((1, 1, block_k, dp), kv_block)],
        out_spec=pl.BlockSpec((1, 1, block_q, dp), _q_block),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=interpret)
    return out[:, :, :t_q, :d]


#: rows of a token-major block's scores, its heads' stacked: twice
#: :data:`_BAND_BLOCK` (two heads of 64 a lane row), so that narrower
#: heads' blocks hold fewer queries and no more fast memory
_STACK_ROWS = 2 * _BAND_BLOCK


def _lane_row_kernel(qi_ref, kb_ref, bits_ref, q_ref, k_ref, v_ref, o_ref,
                     *scratch, scale, d, cols, t_q, t_k):
    """One (query block, key block) pair of one *lane row* of a
    token-major call: ``128 // d`` heads side by side in the 128 columns
    of q_ref / o_ref ``[1, block_q, 128]`` and k_ref / v_ref ``[1,
    block_k, 128]``, blocks of operands ``[b, t, cols]`` that nothing
    pads — what a block holds beyond ``cols`` columns (the last lane row
    of an odd head count) or beyond ``t`` rows is undefined, may be NaN,
    and ``0 * NaN`` is NaN: the keys' dead columns and the values' dead
    rows are zeroed, not only their scores masked.  A head's queries
    are the block's with the other heads' columns zeroed, so its scores
    are one contraction over the lane row (what a head of 64 padded to
    128 costs the matrix unit, no more); the heads' queries are stacked
    into one ``[heads * block_q, 128]`` operand, so a pair is one
    product, one :func:`_block_update` over the stacked rows and one
    product with the values, whose ``[heads * block_q, 128]`` sum holds
    head ``j``'s output in rows ``j`` and columns ``j``."""
    heads, (block_q, block_k) = _LANES // d, (q_ref.shape[1], k_ref.shape[1])
    ragged = cols % _LANES != 0
    # the lane row's real columns: all 128 but for the last's
    live = cols - pl.program_id(1) * _LANES

    def lane(rows):
        return jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

    def scores():
        q, k, at = q_ref[0], k_ref[0], lane(block_q)
        if ragged:
            k = jnp.where(lane(block_k) < live, k, jnp.zeros_like(k))
        stack = []      # head j: its own columns, the others' zero
        for j in range(heads):
            own = jnp.logical_and(at >= j * d, at < (j + 1) * d)
            if ragged:
                own = jnp.logical_and(own, at < live)
            stack.append(jnp.where(own, q, jnp.zeros_like(q)))
        return jax.lax.dot_general(
            jnp.concatenate(stack, axis=0), k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [heads * bq, bk]

    def values(kb):
        v = v_ref[0]
        if kb is None:
            return v
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        return jnp.where(row < t_k - kb * block_k, v, jnp.zeros_like(v))

    def write(y):
        at, out = lane(block_q), y[:block_q]
        for j in range(1, heads):
            out = jnp.where(at >= j * d, y[j * block_q:(j + 1) * block_q],
                            out)
        o_ref[0] = out.astype(o_ref.dtype)

    _pair_step((qi_ref, kb_ref, bits_ref), scores, values, write, scratch,
               t_q=t_q, t_k=t_k, window=None, heads=heads)


def _lane_row_attention(q, k, v, *, d, t_q, t_k, cols, block_q, block_k,
                        interpret):
    """:func:`flash_causal_columns`' own path: ``q`` ``[b, >= t_q, >=
    cols]``, ``k`` / ``v`` ``[b, >= t_k, >= cols]`` token-major,
    ``cols`` columns of heads of ``d``; what an operand holds beyond
    the sizes named is never read into a result."""
    heads = _LANES // d
    # the fewest query blocks that hold the prompt, all alike (896 rows:
    # two of 448, where two of 512 work on 128 rows that are not there;
    # 0.71 against 0.80 ms a call on the v5e, PERF.md PR 70), in whole
    # bf16 tiles; a key block's rows are the scores' lanes, and stay a
    # power of two
    block_q = min(block_q, _STACK_ROWS // heads)
    block_q = -(-t_q // (16 * -(-t_q // block_q))) * 16
    block_k = _clamped(block_k, t_k)
    b, lane_rows = q.shape[0], -(-cols // _LANES)

    def q_block(bi, li, step, qi, kb, bits):
        return (bi, qi[step], li)

    def k_block(bi, li, step, qi, kb, bits):
        return (bi, kb[step], li)

    return _paired_call(
        functools.partial(_lane_row_kernel, scale=1.0 / math.sqrt(d), d=d,
                          cols=cols, t_q=t_q, t_k=t_k),
        "flash_causal", (q, k, v),
        sizes=(t_q, t_k, block_q, block_k, None), lead=(b, lane_rows),
        dv=_LANES, heads=heads,
        in_specs=[pl.BlockSpec((1, block_q, _LANES), q_block),
                  pl.BlockSpec((1, block_k, _LANES), k_block),
                  pl.BlockSpec((1, block_k, _LANES), k_block)],
        out_spec=pl.BlockSpec((1, block_q, _LANES), q_block),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "window", "block_q", "block_k", "interpret"))
def flash_causal_columns(q, k, v, *, heads: int, kv_heads: int | None = None,
                         window: int | None = None,
                         block_q: int | None = None,
                         block_k: int | None = None,
                         interpret: bool | None = None):
    """Causal attention on a projection's own columns, token-major:
    ``q`` ``[b, t_q, heads * d]``, ``k`` / ``v`` ``[b, t_k, kv_heads *
    d]``, returns ``[b, t_q, heads * d]``, the heads merged — what
    :func:`flash_attention` ``(causal=True)`` returns for the same
    heads laid head-major, bottom-right aligned where ``t_q != t_k``.

    Where the heads are full, unwindowed and a whole fraction of a lane
    row wide (``d < 128``, ``128 % d == 0``: GPT-2's 64), the kernel
    reads the columns as they lie: a block is a lane row, ``128 // d``
    heads side by side (:func:`_lane_row_kernel`), and no head-major
    copy, no lane or row pad and no slice of a padded output exists.
    Every other call — heads of 128, grouped queries, a window — is
    laid head-major and takes :func:`flash_attention`'s path, a head a
    block.  The shapes decide, nothing else."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kv_heads = kv_heads or heads
    (b, t_q, cols), t_k = q.shape, k.shape[1]
    d = cols // heads
    block_q, block_k = block_q or _BAND_BLOCK, block_k or _BAND_BLOCK
    if heads == kv_heads and window is None and d < _LANES \
            and _LANES % d == 0:
        return _lane_row_attention(
            q, k, v, d=d, t_q=t_q, t_k=t_k, cols=cols, block_q=block_q,
            block_k=block_k, interpret=interpret)
    qh = q.reshape(b, t_q, heads, d).transpose(0, 2, 1, 3)
    kh, vh = (a.reshape(b, t_k, kv_heads, d).transpose(0, 2, 1, 3)
              for a in (k, v))
    y = _band_attention(qh, kh, vh, window=window, block_q=block_q,
                        block_k=block_k, interpret=interpret)
    return y.transpose(0, 2, 1, 3).reshape(b, t_q, cols)


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

    _pair_step((qi_ref, kb_ref, bits_ref), scores, lambda kb: v_ref[0, 0],
               _store(o_ref), scratch, t_q=t, t_k=t, window=None)


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
    block = _clamped(block or _BAND_BLOCK, t)
    qn, qr, kn, kr, vp = (_pad_to(a, 2, block)
                          for a in (q_nope, q_rope, k_nope, k_rope, v))
    own, shared = _k_block(1), _k_block(h)    # the one rotated key: head 0
    out = _paired_call(
        functools.partial(_latent_kernel, scale=scale, t=t), "flash_latent",
        (qn, qr, kn, kr, vp), sizes=(t, t, block, block, None),
        lead=(b, h), dv=dv,
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
    sizes — these head-major inputs are padded in HBM, rows to whole
    blocks and a head's width to the 128 lanes, and the padding is
    masked out of the softmax (a projection's own columns of heads
    under a lane row wide go to :func:`flash_causal_columns`, which
    pads nothing: the module's docstring).  ``causal=True`` takes the paired kernel (the module's
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

    block_q, block_k = _clamped(block_q, t_q), _clamped(block_k, t_k)

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
