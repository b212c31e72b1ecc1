"""The retention state's format: the one module that knows how a layer's
power-retention memory is kept on the device, updated and read.

A power-retention layer (Manifest AI, arXiv:2507.04239) weighs position
``u`` for the query at ``t`` by ``(s q_t.k_u)^2 * prod_{r=u+1..t} g_r``
(``s = 1/sqrt(d)``, ``g`` a learned decay a KV head a token) and
normalises by the sum of the weights.  With ``phi(a)`` the degree-2
symmetric power of ``a`` (``phi(a).phi(b) = (a.b)^2``) that is a
recurrence whose memory does not grow with the position:

    S(t) = g(t) S(t-1) + phi(k(t)) v(t)^T        z(t) = g(t) z(t-1) + phi(k(t))
    y(t) = s^2 phi(q(t))^T S(t) / (s^2 phi(q(t)).z(t) + 1e-6)

The blocks (``models/decoder.py::RetentionBlock``) hand over ``q, k, v``
and ``log g`` and take ``y`` back; they know nothing of what follows.

**The format.**  One layer is a dict of two float32 buffers (a sum over
thousands of positions under a decay near 1 does not survive a
bfloat16 mantissa): ``S [batch, kv_heads, D, d]`` and ``z [batch,
kv_heads, D]``, behind a leading ``groups`` axis for the ring.  The
query heads of a group read their KV head's state.  ``D`` counts the
symmetric power *by tiles of 8*: ``d`` is cut into ``d/8`` blocks and a
row of the state is ``(I, m, J, n)`` — block ``I``, its element ``m``,
a block ``J >= I``, its element ``n`` — holding the products ``k[8I+m]
k[8J+n]``, so that eight consecutive rows are one (8, 128) tile of the
device and a tile needs one element of ``k`` and eight consecutive
others.  The diagonal blocks hold both ``(m, n)`` and ``(n, m)``: ``D =
(d/8)(d/8+1)/2 * 64``, 8704 at ``d`` 128 where the untiled power has
8256; the full outer product would have 16384.  The stored side
carries the plain products, the reading side (``phi(.., query=True)``)
the factor 2 of the blocks ``J > I``.  :func:`dense` unpacks a state to
``[.., d, d, dv]`` for whoever compares it with something that knows no
layout.

Unlike a KV cache a state has **no scratch group and no scratch row**:
a pipeline's bubble is the identity update (``g = 1, k = 0``), which
:meth:`RetentionFormat.step` and :meth:`RetentionFormat.prefill` make
of a call whose ``valid`` is false.  The state of several layers is a
tuple of buffers a key, never stacked (``ops/layered.py``).

* :meth:`RetentionFormat.step` — one token a sequence: the aliased
  Pallas kernel :func:`retention_step` streams each KV head's ``S``
  through VMEM once (decay, add ``phi(k) v^T``, read out the group's
  queries, write back in place); ``z``, 1/128 of the state, is updated
  beside it in plain XLA.
* :meth:`RetentionFormat.prefill` — a whole prompt from an empty
  memory, the chunked form: inside a chunk the attention form with the
  cumulative log-decay (:func:`power_attention`), between chunks
  (:data:`CHUNK` positions) the state.
* :func:`step_reference` / :func:`prefill_reference` — the same in plain
  ``jnp``, the tests' oracle.
"""

from __future__ import annotations

import dataclasses
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .layered import LayeredState

_TILE = 8
#: added to the normaliser (the published kernels' guard against 0 / 0)
EPS = 1e-6
#: the most one temporary of a prefill may hold: sizes its blocks
_BLOCK_BYTES = 384 << 20
#: positions of a prefill's chunk: read through the state a query costs
#: ``2 D d`` operations, over a chunk of ``C`` positions ``4 C d``, so
#: under ``D / 2`` positions the attention form is the cheaper and the
#: chunk is long
CHUNK = 2048


def state_rows(head_dim: int) -> int:
    """``D``: rows of one KV head's state (module docstring)."""
    nb = head_dim // _TILE
    return nb * (nb + 1) // 2 * _TILE * _TILE


def phi(a, *, query: bool = False):
    """The tiled symmetric power of ``a`` [..., d] -> [..., D], rows in
    the state's order ``(I, m, J >= I, n)``: ``a[8I+m] a[8J+n]``, and
    with ``query`` twice that where ``J > I``, so that ``phi(q,
    query=True) . phi(k) = (q.k)^2``."""
    d = a.shape[-1]
    parts = []
    for lo in range(0, d, _TILE):
        right = a[..., lo:]
        if query and lo + _TILE < d:
            right = jnp.concatenate(
                [right[..., :_TILE], 2 * right[..., _TILE:]], axis=-1)
        outer = a[..., lo:lo + _TILE, None] * right[..., None, :]
        parts.append(outer.reshape(a.shape[:-1] + (-1,)))
    return jnp.concatenate(parts, axis=-1)


def dense(rows, axis: int = -2) -> np.ndarray:
    """A state's rows unpacked on the host to the full symmetric form:
    ``rows`` [..., D, ...] with ``D`` at ``axis`` (``S``: -2; ``z``: -1)
    -> [..., d, d, ...], entry ``(a, b)`` the decayed sum of ``k_a k_b``
    (times ``v``): what a comparison that knows no layout reads."""
    rows = np.moveaxis(np.asarray(rows), axis, 0)
    d = next(n for n in range(_TILE, 1 << 12, _TILE)
             if state_rows(n) >= rows.shape[0])
    if state_rows(d) != rows.shape[0]:
        raise ValueError(f"{rows.shape[0]} rows are no retention state's")
    out = np.zeros((d, d) + rows.shape[1:], rows.dtype)
    at = 0
    for lo in range(0, d, _TILE):
        width = d - lo
        blk = rows[at:at + _TILE * width].reshape(
            (_TILE, width) + rows.shape[1:])
        out[lo:lo + _TILE, lo:] = blk
        out[lo + _TILE:, lo:lo + _TILE] = np.swapaxes(blk[:, _TILE:], 0, 1)
        at += _TILE * width
    axis = axis % (rows.ndim)
    return np.moveaxis(out, (0, 1), (axis, axis + 1))


# -- the step kernel ------------------------------------------------------------

def _step_kernel(group_ref, q_ref, kvg_ref, s_ref, num_ref, out_ref,
                 a_ref, w_ref, bq_ref):
    """One KV head of one sequence: ``s_ref`` / ``out_ref`` ``[1, 1, 1,
    D/8, 8, d]``, a tile ``(I, m, J)`` at a time — its 8 rows the ``n``
    of block ``J``, its lanes the value's ``d`` columns.

    A tile's update is ``decay * S + k[8I+m] * (k[8J:8J+8] v^T)``: one
    element of ``k`` spread over the tile (``a_ref[0, 8I+m]``) times the
    block's outer product with ``v`` (``w_ref[J]``).  Its read-out for
    query ``j`` is summed over ``J`` first — ``U = sum_J S_new *
    q_j[8J:8J+8]`` (``bq_ref[j, J]``), the blocks ``J > I`` twice — and
    only then multiplied by ``q_j[8I+m]``, so a tile costs two
    operations a query.  q_ref / num_ref ``[1, 1, g, d]`` (the group's
    queries / their numerators); kvg_ref ``[1, 1, 3, d]``: rows ``k``,
    ``v``, the decay on every lane."""
    del group_ref                       # the index map reads it
    g, d = q_ref.shape[2:]
    nb = d // _TILE
    eye = (lax.broadcasted_iota(jnp.int32, (d, d), 0)
           == lax.broadcasted_iota(jnp.int32, (d, d), 1))

    def down(row):
        """``row`` [1, d] -> [d, d] with ``row[r]`` on every lane of
        row ``r``: the row spread over the diagonal and reduced."""
        col = jnp.sum(jnp.where(eye, jnp.broadcast_to(row, (d, d)), 0.0),
                      axis=1, keepdims=True)
        return jnp.broadcast_to(col, (d, d))

    v_tile = jnp.broadcast_to(kvg_ref[0, 0, 1:2, :], (_TILE, d))
    decay = jnp.broadcast_to(kvg_ref[0, 0, 2:3, :], (_TILE, d))
    subs = [down(kvg_ref[0, 0, 0:1, :])] + [
        down(q_ref[0, 0, j:j + 1, :]) for j in range(g)]
    for j, sub in enumerate(subs):
        for r in range(d):
            a_ref[j, r] = jnp.broadcast_to(sub[r:r + 1, :], (_TILE, d))
    for blk in range(nb):
        rows = slice(blk * _TILE, (blk + 1) * _TILE)
        w_ref[blk] = subs[0][rows, :] * v_tile
        for j in range(g):
            bq_ref[j, blk] = subs[1 + j][rows, :]

    accs = tuple(jnp.zeros((_TILE, d), jnp.float32) for _ in range(g))
    base = 0
    for i in range(nb):
        width = nb - i

        def body(m, accs, i=i, width=width, base=base):
            r = i * _TILE + m
            ak = a_ref[0, r]
            diag, off = None, None
            for dj in range(width):
                at = base + m * width + dj
                s_new = decay * s_ref[0, 0, 0, at] + ak * w_ref[i + dj]
                out_ref[0, 0, 0, at] = s_new
                terms = [s_new * bq_ref[j, i + dj] for j in range(g)]
                if dj == 0:
                    diag = terms
                else:
                    off = terms if off is None else [
                        o + t for o, t in zip(off, terms)]
            sums = diag if off is None else [
                a + 2.0 * o for a, o in zip(diag, off)]
            return tuple(acc + a_ref[1 + j, r] * sums[j]
                         for j, acc in enumerate(accs))

        accs = lax.fori_loop(0, _TILE, body, accs)
        base += _TILE * width
    for j in range(g):
        num_ref[0, 0, j:j + 1, :] = jnp.sum(accs[j], axis=0, keepdims=True)


@jax.jit
def retention_step(q, kvg, state, group):
    """``S <- decay S + phi(k) v^T`` in place, and the numerators
    ``phi(q, query=True)^T S`` of the group's queries against the new
    state.  ``state`` [groups, b, kv, D, d] f32, of which group ``group``
    [1] int32; ``q`` [b, kv, g, d] f32; ``kvg`` [b, kv, 3, d] f32: rows
    ``k``, ``v`` and the decay (on every lane).  Returns ``(num [b, kv,
    g, d], state)``; the state aliases its argument: donate it.

    One grid step a (sequence, KV head): its whole state crosses VMEM
    once, 4.46 MB in and as much out at ``d`` 128.  Every operation is
    on the vector unit in float32: with a handful of queries a head
    there is no matrix for the matrix unit (a state tile would be a
    weight load for 8 rows), and bfloat16 products would cost the state
    its digits.  Off-TPU the identical kernel runs in interpreter mode,
    as the package's others do.  Jitted so that a step program that
    calls it once a layer traces and lowers it once."""
    groups, b, kv, rows, d = state.shape
    g, tiles, nb = q.shape[2], rows // _TILE, d // _TILE
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)

    def small(n):
        return pl.BlockSpec((1, 1, n, d), lambda i, h, group_ref: (i, h, 0, 0))

    big = pl.BlockSpec((1, 1, 1, tiles, _TILE, d),
                       lambda i, h, group_ref: (group_ref[0], i, h, 0, 0, 0))
    tile = _TILE * d * 4
    num, out = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, kv),
            in_specs=[small(g), small(3), big], out_specs=[small(g), big],
            scratch_shapes=[
                pltpu.VMEM((1 + g, d, _TILE, d), jnp.float32),
                pltpu.VMEM((nb, _TILE, d), jnp.float32),
                pltpu.VMEM((g, nb, _TILE, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32),
                   jax.ShapeDtypeStruct((groups, b, kv, tiles, _TILE, d),
                                        jnp.float32)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state's block in and out, each double-buffered, and the
            # scratch; the default limit holds less than one head's
            vmem_limit_bytes=(4 * tiles + (1 + g) * (d + nb) + nb) * tile
            + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="retention_step",
    )(group, q, kvg, state.reshape(groups, b, kv, tiles, _TILE, d))
    return num, out.reshape(state.shape)


# -- the attention form ------------------------------------------------------

def _block_rows(bytes_a_row: int, length: int) -> int:
    """Rows of a prefill's block over ``length`` positions: the largest
    power of two that divides ``length`` and whose temporary stays
    inside :data:`_BLOCK_BYTES`."""
    rows = max(_TILE, _BLOCK_BYTES // max(bytes_a_row, 1))
    rows = min(length, 1 << (rows.bit_length() - 1))
    while length % rows:
        rows //= 2
    return rows


def _cum_decay(lg):
    """``lg`` [b, t, kv] -> the inclusive running sum over ``t``, f32."""
    return jnp.cumsum(lg.astype(jnp.float32), axis=1)


def power_attention(q, k, v, lg):
    """The layer in its attention form over a whole sequence from an
    empty memory: ``q`` [b, t, kv, g, d], ``k`` / ``v`` [b, t, kv, d],
    ``lg`` [b, t, kv] -> ``(num [b, t, kv, g, d] f32, den [b, t, kv, g]
    f32)``, the weighted sum of the values and the sum of the weights
    ``(q_t.k_u)^2 exp(sum_{r=u+1..t} lg_r)``, ``u <= t`` (unscaled:
    the caller applies ``s^2``).  Query rows go a block at a time, so
    that one block's ``[b, kv, g, rows, t]`` weights are all that is
    held; the products run in the inputs' type and accumulate in f32."""
    b, t, kv, g, d = q.shape
    cum = _cum_decay(lg)                                    # [b, t, kv]
    rows = _block_rows(b * kv * g * t * 4, t)
    q_blocks = q.reshape(b, t // rows, rows, kv, g, d).swapaxes(0, 1)
    cum_blocks = cum.reshape(b, t // rows, rows, kv).swapaxes(0, 1)
    at = jnp.arange(t)

    def block(args):
        qb, cb, lo = args
        score = jnp.einsum("bqhgd,buhd->bhgqu", qb, k,
                           preferred_element_type=jnp.float32)
        gap = cb.transpose(0, 2, 1)[:, :, None, :, None]             - cum.transpose(0, 2, 1)[:, :, None, None, :]
        live = (lo + jnp.arange(rows))[:, None] >= at[None, :]
        # masked before the exponential: past the diagonal the gap is
        # positive and would overflow
        w = score * score * jnp.exp(jnp.where(live, gap, -jnp.inf))
        num = jnp.einsum("bhgqu,buhd->bqhgd", w.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return num, w.sum(-1).transpose(0, 3, 1, 2)

    num, den = lax.map(block, (q_blocks, cum_blocks,
                               jnp.arange(0, t, rows)))
    return (num.swapaxes(0, 1).reshape(b, t, kv, g, d),
            den.swapaxes(0, 1).reshape(b, t, kv, g))


def normalise(num, den, d: int):
    """``s^2 num / (s^2 den + EPS)``, ``s = 1/sqrt(d)``."""
    s2 = 1.0 / d
    return s2 * num / (s2 * den[..., None] + EPS)


# -- the format --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RetentionFormat(LayeredState):
    """One layer's retention state, described: what the ring builds its
    buffers from and updates and reads them through (``zeros``,
    ``layer`` and ``with_layer`` are ``ops/layered.py``'s)."""

    keys = ("S", "z")

    kv_heads: int
    head_dim: int
    #: the ring's round-robin groups (a leading axis); None for one batch
    groups: int | None = None

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group), by key."""
        lead = () if self.groups is None else (self.groups,)
        z = lead + (batch, self.kv_heads, state_rows(self.head_dim))
        return {"S": jax.ShapeDtypeStruct(z + (self.head_dim,), jnp.float32),
                "z": jax.ShapeDtypeStruct(z, jnp.float32)}

    # -- where a ring step's memory goes: a bubble is an identity update

    @staticmethod
    def decode_slot(valid, pos):
        """What :meth:`step` takes as ``valid``; the position is not
        part of a state's address."""
        del pos
        return valid

    @staticmethod
    def prefill_slot(valid, group, row=None):
        """What :meth:`prefill` takes as ``slot``: the group and whether
        the call is real; with ``row``, also the sequence of the group
        from which a piece's prompts lie."""
        return (group, valid) if row is None else (group, valid, row)

    def _heads(self, q, k, v, lg):
        """The block's columns split into heads: ``q`` [.., kv, g, d],
        ``k`` / ``v`` [.., kv, d], ``lg`` [.., kv] f32."""
        kv, d = self.kv_heads, self.head_dim
        lead = q.shape[:-1]
        return (q.reshape(lead + (kv, -1, d)), k.reshape(lead + (kv, d)),
                v.reshape(lead + (kv, d)), lg.astype(jnp.float32))

    # -- one token a sequence ------------------------------------------------

    def step(self, q, k, v, lg, layer: dict, group=None, valid=True):
        """One token of every sequence (of group ``group``): ``q`` [b,
        heads * d], ``k`` / ``v`` [b, kv_heads * d], ``lg`` [b,
        kv_heads] the log-decay.  The state is decayed, ``phi(k) v^T``
        is added and the queries read the *new* state: returns ``(y [b,
        heads * d]`` in ``q``'s type, the layer``)``.  With ``valid``
        false (a pipeline's bubble) the update is the identity (``lg =
        0, k = 0``) and ``y`` means nothing."""
        b = q.shape[0]
        qh, kh, vh, lg = self._heads(q.astype(jnp.float32),
                                     k.astype(jnp.float32),
                                     v.astype(jnp.float32), lg)
        kh = jnp.where(valid, kh, 0.0)
        decay = jnp.exp(jnp.where(valid, lg, 0.0))          # [b, kv]
        bufs, group = self._group(layer, group)
        kvg = jnp.stack([kh, vh, jnp.broadcast_to(
            decay[..., None], kh.shape)], axis=2)
        num, s_buf = retention_step(qh, kvg, bufs["S"], group)
        # the normaliser: 1/d of the state, beside the kernel in XLA
        # (multiplied and summed on the vector unit: a dot would round
        # its float32 inputs to bfloat16)
        at = (group[0], 0, 0, 0)
        z = lax.dynamic_slice(bufs["z"], at, (1,) + bufs["z"].shape[1:])[0]
        z = decay[..., None] * z + phi(kh)
        den = jnp.sum(phi(qh, query=True) * z[:, :, None, :], axis=-1)
        z_buf = lax.dynamic_update_slice(bufs["z"], z[None], at)
        y = normalise(num, den, self.head_dim)
        return y.reshape(b, -1).astype(q.dtype), \
            self._ungroup({"S": s_buf, "z": z_buf})

    # -- a whole prompt ---------------------------------------------------------

    def prefill(self, q, k, v, lg, layer: dict, slot=(None, True)):
        """A whole prompt of every sequence (of the group ``slot``
        names) into an *empty* memory (as ``zeros`` leaves it): ``q``
        [b, t, heads * d], ``k`` / ``v`` [b, t, kv_heads * d], ``lg``
        [b, t, kv_heads] -> ``(y [b, t, heads * d], the layer)``, ``y``
        of the prompt alone and the layer holding the state after its
        last position.

        The chunked form: a chunk's own positions in the attention form
        (:func:`power_attention`), the chunks before it through the
        state they left; after each chunk the state is decayed over it
        and the chunk's ``phi(k) v^T`` are added, a block of positions
        a product.  ``slot`` is :meth:`prefill_slot`'s: where it says
        the call is a bubble, the update is the identity."""
        group, valid, row = slot if len(slot) == 3 else (*slot, 0)
        b, t = q.shape[:2]
        d = self.head_dim
        qh, kh, vh, lg = self._heads(q, k, v, lg)
        kh = jnp.where(valid, kh, jnp.zeros((), kh.dtype))
        lg = jnp.where(valid, lg, 0.0)
        bufs, group = self._group(layer, group)
        at = (group[0], row) + (0,) * (bufs["S"].ndim - 2)
        s = lax.dynamic_slice(bufs["S"], at,
                              (1, b) + bufs["S"].shape[2:])[0]
        z = lax.dynamic_slice(bufs["z"], at[:-1],
                              (1, b) + bufs["z"].shape[2:])[0]
        ys = []
        for lo in range(0, t, CHUNK):
            part = slice(lo, min(t, lo + CHUNK))
            num, den = power_attention(qh[:, part], kh[:, part],
                                       vh[:, part], lg[:, part])
            cum = _cum_decay(lg[:, part])                   # [b, c, kv]
            if lo:
                n2, d2 = self._read(qh[:, part], s, z)
                reach = jnp.exp(cum)
                num = num + reach[..., None, None] * n2
                den = den + reach[..., None] * d2
            ys.append(normalise(num, den, d))
            s, z = self._absorb(kh[:, part], vh[:, part], cum, s, z)
        y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
        return y.reshape(b, t, -1).astype(q.dtype), self._ungroup({
            "S": lax.dynamic_update_slice(bufs["S"], s[None], at),
            "z": lax.dynamic_update_slice(bufs["z"], z[None], at[:-1])})

    def _read(self, qh, s, z):
        """A chunk's queries ``qh`` [b, c, kv, g, d] against the state
        ``s`` [b, kv, D, d], ``z`` [b, kv, D] the chunks before it left:
        ``(num [b, c, kv, g, d], den [b, c, kv, g])`` f32, a block of
        positions at a time."""
        b, c, kv, g, d = qh.shape
        rows = _block_rows(b * kv * g * s.shape[2] * 4, c)

        def block(qb):
            feats = phi(qb, query=True)                 # [b, r, kv, g, D]
            num = jnp.einsum("bqhgD,bhDd->bqhgd", feats,
                             s.astype(feats.dtype),
                             preferred_element_type=jnp.float32)
            den = jnp.sum(feats.astype(jnp.float32)
                          * z[:, None, :, None, :], axis=-1)
            return num, den

        num, den = lax.map(block, qh.reshape(
            b, c // rows, rows, kv, g, d).swapaxes(0, 1))
        return (num.swapaxes(0, 1).reshape(b, c, kv, g, d),
                den.swapaxes(0, 1).reshape(b, c, kv, g))

    def _absorb(self, kh, vh, cum, s, z):
        """The state after a chunk: ``s``, ``z`` decayed over the whole
        chunk plus each position's ``phi(k) v^T`` (``phi(k)``) decayed
        from there to the chunk's end; ``kh`` / ``vh`` [b, c, kv, d],
        ``cum`` [b, c, kv] the chunk's running log-decay.  The features
        are made and multiplied a block of positions at a time, in the
        inputs' type; the sums are float32."""
        b, c, kv, d = kh.shape
        to_end = jnp.exp(cum[:, -1:] - cum)                 # [b, c, kv]
        whole = jnp.exp(cum[:, -1]).astype(jnp.float32)     # [b, kv]
        rows = _block_rows(b * kv * s.shape[2] * 4, c)

        def block(carry, args):
            s, z = carry
            kb, vb, wb = args
            feats = phi(kb)                                 # [b, r, kv, D]
            s = s + jnp.einsum(
                "buhD,buhd->bhDd", feats,
                (vb.astype(jnp.float32) * wb[..., None]).astype(vb.dtype),
                preferred_element_type=jnp.float32)
            z = z + jnp.sum(feats.astype(jnp.float32) * wb[..., None],
                            axis=1)
            return (s, z), None

        def blocks(a):
            return a.reshape((b, c // rows, rows) + a.shape[2:]
                             ).swapaxes(0, 1)

        (s, z), _ = lax.scan(
            block, (s * whole[..., None, None], z * whole[..., None]),
            (blocks(kh), blocks(vh), blocks(to_end)))
        return s, z


# -- the oracle -----------------------------------------------------------------

def step_reference(q, k, v, lg, item: dict):
    """:meth:`RetentionFormat.step` in plain ``jnp`` over one item
    (``S`` [b, kv, D, d], ``z`` [b, kv, D], no group axis): the oracle
    the tests hold :func:`retention_step` to.  ``q`` [b, kv, g, d],
    ``k`` / ``v`` [b, kv, d], ``lg`` [b, kv], all f32.  Returns ``(y [b,
    kv, g, d], item)``."""
    decay = jnp.exp(lg)
    feats = phi(k)
    s = decay[..., None, None] * item["S"] \
        + feats[..., :, None] * v[..., None, :]
    z = decay[..., None] * item["z"] + feats
    read = phi(q, query=True)                              # [b, kv, g, D]
    num = jnp.sum(read[..., :, None] * s[:, :, None], axis=-2)
    den = jnp.sum(read * z[:, :, None], axis=-1)
    return normalise(num, den, q.shape[-1]), {"S": s, "z": z}


def prefill_reference(q, k, v, lg):
    """The attention form over a whole sequence, one dense ``[t, t]``
    weight matrix a head, and the state after the last position as the
    explicit sum: ``q`` [b, t, kv, g, d], ``k`` / ``v`` [b, t, kv, d],
    ``lg`` [b, t, kv], all f32 -> ``(y [b, t, kv, g, d], item)``."""
    t, d = q.shape[1], q.shape[-1]
    hi = lax.Precision.HIGHEST
    cum = jnp.cumsum(lg, axis=1).transpose(0, 2, 1)         # [b, kv, t]
    score = jnp.einsum("bqhgd,buhd->bhgqu", q, k, precision=hi)
    live = jnp.tril(jnp.ones((t, t), bool))
    gap = cum[:, :, None, :, None] - cum[:, :, None, None, :]
    w = score ** 2 * jnp.exp(jnp.where(live, gap, -jnp.inf))
    num = jnp.einsum("bhgqu,buhd->bqhgd", w, v, precision=hi)
    den = w.sum(-1).transpose(0, 3, 1, 2)
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 2, 1)  # [b, t, kv]
    feats = phi(k) * to_end[..., None]
    return normalise(num, den, d), {
        "S": jnp.einsum("buhD,buhd->bhDd", feats, v, precision=hi),
        "z": feats.sum(1)}
