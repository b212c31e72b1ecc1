"""The delta-rule state's format: the one module that knows how a gated
delta-rule layer's per-sequence memory is kept on the device, updated
and read.

A delta-rule linear-attention layer with a decay a channel (Kimi Delta
Attention, arXiv:2510.26692) keeps, a head, a square state ``S [dk,
dv]`` whose write **reads it**: with ``alpha = exp(g)`` the decay of
each key channel and ``beta`` the write's strength,

    S'   = Diag(alpha(t)) S(t-1)
    S(t) = S' + beta(t) k(t) (v(t) - S'^T k(t))^T     = (I - beta k k^T) S' + beta k v^T
    o(t) = S(t)^T q(t)

— the state forgets what it held under ``k`` before it learns ``v``
there, which no decay-and-add recurrence (``ops/retention.py``,
``ops/ssm.py``) does.  ``q``, ``k`` and ``v`` come out of short causal
convolutions, so beside ``S`` a sequence keeps the convolutions' window
(``ops/conv_window.py::Window``, over the three projections side by
side).  The blocks (``models/decoder.py::DeltaRuleBlock``) hand over
the window's input, then ``q, k, v``, the log-decay ``g`` and ``beta``,
and take the taps and ``o`` back; they know nothing of what follows.

**The format.**  One layer is a dict of two buffers, behind a leading
``groups`` axis for the ring: the window ``conv [d_conv - 1, batch, 3
heads d]`` in the compute type, and ``S`` float32 (a sum over
thousands of positions under decays near 1, and a write that
subtracts what it reads: bfloat16 keeps neither).  ``S`` lies **key
channel outermost, heads innermost**: ``[batch, dk, rows, lanes]``,
the pair (value channel ``v``, head ``h``) flattened as ``v * heads +
h`` and folded into rows of ``lanes`` (128 where the heads divide it:
64 heads put two value channels on a lane row).  One key channel's
slice is then whole (8, 128) tiles in which *every lane is another
head's or value's element*, and the whole update is elementwise over
them: what a head multiplies a key channel by (``alpha``, ``k``,
``alpha k``, ``alpha q``) is one lane row a channel, spread over the
sublanes for nothing, and the two contractions over the key channels
(``S'^T k``, ``S^T q``) are sums of whole tiles — no transpose, no
reduction across lanes, no matrix unit (a head's ``[128, 128]`` would
be a weight load for one row).  :func:`dense` unpacks a state to
``[batch, heads, dk, dv]`` for whoever compares it with something that
knows no layout.  The bytes are the state's own: nothing is padded.

Like a retention state and unlike a KV cache it has **no scratch group
and no scratch row**: a pipeline's bubble is the identity update
(``alpha = 1, k = 0``, and the window kept), which
:meth:`DeltaFormat.step`, :meth:`DeltaFormat.prefill` and the window's
calls make of a call whose ``valid`` is false.

* :meth:`DeltaFormat.step` — one token a sequence: the aliased Pallas
  kernel :func:`delta_step` streams a block of a sequence's state
  through VMEM once (both contractions on the way in, the decay and the
  write on the way out, in place).
* :meth:`DeltaFormat.prefill` — a whole prompt from an empty memory,
  the chunked (WY) form :func:`delta_chunk`: inside a chunk of
  ``chunk`` positions a unit lower-triangular solve gives every
  position's write at once, between chunks the state is carried, so it
  leaves the registers once a chunk and not once a position.
* :func:`step_reference` / :func:`prefill_reference` — the recurrence
  token by token in plain ``jnp``, the tests' oracle.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout, with_layout_constraint
from jax.experimental.pallas import tpu as pltpu

from .conv_window import Window
from .layered import nbytes

_LANES = 128
#: the most one block of :func:`delta_step`'s state may hold
_STEP_BLOCK_BYTES = 2 << 20
#: rows of a chunk whose pairwise decays :func:`delta_chunk` holds at
#: once (``[.., rows, chunk, dk]``)
_PAIR_ROWS = 16
_HI = lax.Precision.HIGHEST


def fold(heads: int, head_dim: int) -> tuple[int, int]:
    """``(rows, lanes)`` of one key channel's slice of a state: the
    ``head_dim * heads`` pairs (value channel, head), heads innermost,
    folded into whole lane rows where the heads divide one."""
    flat = head_dim * heads
    lanes = _LANES if _LANES % heads == 0 and flat % _LANES == 0 else heads
    return flat // lanes, lanes


# -- the step kernel ------------------------------------------------------------

def _step_kernel(group_ref, coef_ref, row_ref, val_ref, s_ref, o_ref,
                 out_ref):
    """A block of one sequence's (value, head) pairs under every key
    channel: ``s_ref`` / ``out_ref`` ``[1, 1, dk, rb, lanes]``;
    ``coef_ref`` ``[1, dk / 2, 8, lanes]``, two key channels a tile,
    four lane rows each — ``alpha k``, ``alpha q``, ``alpha``, ``k``,
    each head's value on its lanes; ``row_ref`` ``[1, 8, lanes]``: rows
    ``beta`` and ``k . q``; ``val_ref`` / ``o_ref`` ``[1, rb, lanes]``
    the values in and the read-out.

    Both contractions run over the *old* state on the way in — ``w =
    S'^T k = sum_c S[c] (alpha k)[c]`` and ``sum_c S[c] (alpha q)[c]``
    — since ``S(t)^T q = that + (k . q) u`` with ``u = beta (v - w)``
    the write; the way out is ``alpha[c] S[c] + k[c] u`` a channel."""
    del group_ref                       # the index map reads it
    pairs = coef_ref.shape[1]
    rb, lanes = val_ref.shape[1:]

    def spread(tile, r):
        return jnp.broadcast_to(tile[r:r + 1, :], (rb, lanes))

    def read(c2, sums):
        w, o = sums
        tile = coef_ref[0, c2]
        for i in range(2):
            s = s_ref[0, 0, 2 * c2 + i]
            w = w + s * spread(tile, 4 * i)
            o = o + s * spread(tile, 4 * i + 1)
        return w, o

    zero = jnp.zeros((rb, lanes), jnp.float32)
    w, o = lax.fori_loop(0, pairs, read, (zero, zero))
    rows = row_ref[0]
    u = spread(rows, 0) * (val_ref[0] - w)
    o_ref[0] = o + spread(rows, 1) * u

    def write(c2, carry):
        tile = coef_ref[0, c2]
        for i in range(2):
            out_ref[0, 0, 2 * c2 + i] = (
                spread(tile, 4 * i + 2) * s_ref[0, 0, 2 * c2 + i]
                + spread(tile, 4 * i + 3) * u)
        return carry

    lax.fori_loop(0, pairs, write, 0)


@jax.jit
def delta_step(coef, rows, val, state, group):
    """``S <- Diag(alpha) S + k u^T``, ``u = beta (v - (Diag(alpha)
    S)^T k)``, in place, and ``o = S^T q`` of the new state, for a state
    laid ``[groups, batch, dk, rows, lanes]`` f32, of which group
    ``group`` [1] int32.  ``coef`` [batch, dk / 2, 8, lanes], ``rows``
    [batch, 8, lanes] and ``val`` [batch, rows, lanes] f32 are
    :func:`_step_kernel`'s, as :meth:`DeltaFormat.step` lays them.
    Returns ``(o [batch, rows, lanes] f32, state)``; the state aliases
    its argument: donate it.

    The grid runs over sequences and blocks of a sequence's (value,
    head) rows, which the update never mixes: at 64 heads of 128 a
    sequence is 4.19 MB, a grid step takes ``[128, 32, 128]`` of it (2
    MB in, 2 MB out) and every value of ``S`` crosses VMEM once.  All
    of it is float32 on the vector unit.  Off-TPU the identical kernel
    runs in interpreter mode, as the package's others do.  Jitted so
    that a step program that calls it once a layer traces and lowers it
    once."""
    groups, batch, dk, nrows, lanes = state.shape
    fit = max(8, _STEP_BLOCK_BYTES // (4 * dk * lanes))
    rb = next((r for r in range(min(nrows, fit), 0, -1)
               if nrows % r == 0 and r % 8 == 0), nrows)
    group = jnp.clip(group.astype(jnp.int32), 0, groups - 1)
    small = pl.BlockSpec((1, 8, lanes), lambda i, j, group_ref: (i, 0, 0))
    vals = pl.BlockSpec((1, rb, lanes), lambda i, j, group_ref: (i, j, 0))
    big = pl.BlockSpec((1, 1, dk, rb, lanes),
                       lambda i, j, group_ref: (group_ref[0], i, 0, j, 0))
    o, out = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(batch, nrows // rb),
            in_specs=[pl.BlockSpec((1, dk // 2, 8, lanes),
                                   lambda i, j, group_ref: (i, 0, 0, 0)),
                      small, vals, big],
            out_specs=[vals, big]),
        out_shape=[jax.ShapeDtypeStruct(val.shape, jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the state's block in and out and the coefficients, each
            # double-buffered
            vmem_limit_bytes=4 * dk * rb * lanes * 4
            + 2 * dk * 4 * lanes * 4 + (8 << 20)),
        interpret=jax.default_backend() != "tpu",
        name="delta_step",
    )(group, coef, rows, val, state)
    return o, out


# -- the chunked form -----------------------------------------------------------

def delta_chunk(q, k, v, g, beta, s0):
    """One chunk of ``C`` positions of every head in the WY form: ``q``
    / ``k`` [.., C, dk], ``v`` [.., C, dv], ``g`` [.., C, dk] the
    log-decay, ``beta`` [.., C], ``s0`` [.., dk, dv] the state before
    it, all f32 -> ``(o [.., C, dv], the state after it)``.

    With ``G_r`` the running sum of ``g`` inside the chunk, the writes
    ``u`` solve the unit lower-triangular ``(I + L) U = Diag(beta) (V -
    (K exp G) S0)``, ``L_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)``
    for ``j < i``; then ``o_r = S0^T (q_r exp G_r) + sum_{i <= r} u_i
    sum_c q_rc k_ic exp(G_rc - G_ic)`` and ``S_C = Diag(exp G_C) S0 +
    sum_i (k_i exp(G_C - G_i)) u_i^T``.  Every exponent is a difference
    ``<= 0`` taken before the exponential (``exp(-G)`` alone overflows
    under a fast channel); the pairwise decays ``[rows, C, dk]`` are
    made :data:`_PAIR_ROWS` rows at a time and never all at once."""
    c = q.shape[-2]
    cum = jnp.cumsum(g, axis=-2)                                # G
    at = jnp.arange(c)
    akk, aqk = [], []
    for lo in range(0, c, _PAIR_ROWS):
        hi = min(c, lo + _PAIR_ROWS)
        gap = cum[..., lo:hi, None, :] - cum[..., None, :hi, :]
        live = (at[lo:hi, None] >= at[None, :hi])[..., None]
        # masked before the exponential: past the diagonal the gap is
        # positive and would overflow
        kj = k[..., None, :hi, :] * jnp.exp(jnp.where(live, gap, -jnp.inf))
        pad = [(0, 0)] * (kj.ndim - 3) + [(0, 0), (0, c - hi)]
        akk.append(jnp.pad(jnp.sum(k[..., lo:hi, None, :] * kj, -1), pad))
        aqk.append(jnp.pad(jnp.sum(q[..., lo:hi, None, :] * kj, -1), pad))
    akk, aqk = jnp.concatenate(akk, -2), jnp.concatenate(aqk, -2)
    strict = at[:, None] > at[None, :]
    tri = jnp.where(strict, beta[..., None] * akk, 0.0)
    reach = jnp.exp(cum)
    rhs = beta[..., None] * (v - jnp.einsum(
        "...ck,...kv->...cv", k * reach, s0, precision=_HI))
    u = jax.scipy.linalg.solve_triangular(tri, rhs, lower=True,
                                          unit_diagonal=True)
    o = jnp.einsum("...ck,...kv->...cv", q * reach, s0, precision=_HI) \
        + jnp.einsum("...ri,...iv->...rv", aqk, u, precision=_HI)
    to_end = jnp.exp(cum[..., -1:, :] - cum)
    s = jnp.swapaxes(reach[..., -1:, :], -1, -2) * s0 + jnp.einsum(
        "...ck,...cv->...kv", k * to_end, u, precision=_HI)
    return o, s


# -- the format --------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DeltaFormat(Window):
    """One layer's delta-rule memory, described: ``heads`` square states
    of ``head_dim`` key by ``head_dim`` value channels and the window
    of the convolutions over ``q``, ``k`` and ``v`` (``zeros``,
    ``layer`` and ``with_layer`` are ``ops/layered.py``'s; ``shift``
    and ``prefill_shift`` ``ops/conv_window.py::Window``'s)."""

    heads: int
    head_dim: int
    d_conv: int
    #: positions of one chunk of the prefill's WY form
    chunk: int
    #: the window's type, the block's compute type (``S`` is float32)
    dtype: Any
    #: the ring's round-robin groups (a leading axis); None for one batch
    groups: int | None = None

    keys = ("conv", "S")

    @property
    def conv_width(self) -> int:
        """``q``, ``k`` and ``v`` side by side."""
        return 3 * self.heads * self.head_dim

    def _state_buffer(self, batch: int) -> jax.ShapeDtypeStruct:
        lead = () if self.groups is None else (self.groups,)
        return jax.ShapeDtypeStruct(
            lead + (batch, self.head_dim) + fold(self.heads, self.head_dim),
            jnp.float32)

    def buffers(self, batch: int) -> dict[str, jax.ShapeDtypeStruct]:
        """One layer's buffers for ``batch`` sequences (a group), by key."""
        return {"conv": self.window_buffer(batch),
                "S": self._state_buffer(batch)}

    def gauges(self, batch: int, stages: int) -> dict[str, int]:
        """The layer's two parts: the states and the window."""
        return {"decode.delta.state_bytes":
                stages * nbytes(self._state_buffer(batch)),
                "decode.delta.window_bytes": self.window_bytes(batch, stages)}

    def _heads(self, a):
        """``a`` [.., heads * n] f32 -> [.., heads, n]."""
        return a.astype(jnp.float32).reshape(
            a.shape[:-1] + (self.heads, -1))

    def _lanes(self, a):
        """``a`` [b, n, heads] -> [b, n, lanes]: a head's value on each
        of its lanes."""
        return jnp.tile(a, (1, 1, fold(self.heads, self.head_dim)[1]
                            // self.heads))

    # -- one token a sequence ------------------------------------------------

    def step(self, q, k, v, g, beta, layer: dict, group=None, valid=True):
        """One token of every sequence (of group ``group``): ``q`` /
        ``k`` / ``v`` [b, heads * d], ``g`` [b, heads * d] the log-decay
        a key channel, ``beta`` [b, heads].  The state is decayed, the
        write that reads it is added and ``q`` reads the *new* state:
        returns ``(o [b, heads * d] float32, the layer)``.  With
        ``valid`` false (a pipeline's bubble) the update is the identity
        (``g = 0, k = 0``) and ``o`` means nothing."""
        b, d = q.shape[0], self.head_dim
        qh, vh = self._heads(q), self._heads(v)
        kh = jnp.where(valid, self._heads(k), 0.0)
        alpha = jnp.exp(jnp.where(valid, self._heads(g), 0.0))
        # [b, d, 4, heads] -> two key channels a tile of eight lane rows
        coef = self._lanes(jnp.stack(
            [alpha * kh, alpha * qh, alpha, kh], axis=1
        ).transpose(0, 3, 1, 2).reshape(b, 4 * d, self.heads))
        rows = jnp.stack([beta.astype(jnp.float32), jnp.sum(kh * qh, -1)]
                         + [jnp.zeros(beta.shape, jnp.float32)] * 6, axis=1)
        bufs, group = self._group(layer, group)
        shape = bufs["S"].shape[-2:]
        o, s = delta_step(coef.reshape((b, d // 2, 8, shape[1])),
                          self._lanes(rows),
                          vh.swapaxes(1, 2).reshape((b,) + shape),
                          bufs["S"], group)
        o = o.reshape(b, d, self.heads).swapaxes(1, 2).reshape(b, -1)
        return o, self._ungroup(dict(bufs, S=s))

    # -- a whole prompt ---------------------------------------------------------

    def prefill(self, q, k, v, g, beta, layer: dict, slot=(None, True)):
        """A whole prompt of every sequence (of the group ``slot``
        names) into an *empty* memory (as ``zeros`` leaves it): ``q`` /
        ``k`` / ``v`` / ``g`` [b, t, heads * d], ``beta`` [b, t, heads]
        -> ``(o [b, t, heads * d] float32, the layer)``, the layer
        holding the state after the last position: chunks of ``chunk``
        positions through :func:`delta_chunk`, the state carried
        between them.  Where ``slot`` says the call is a bubble, the
        state is kept."""
        group, valid, row = slot if len(slot) == 3 else (*slot, 0)
        b, t = q.shape[:2]
        n = -(-t // self.chunk)

        def chunks(a):
            """[b, t, heads, x] -> [n, b, heads, chunk, x]; the last
            chunk filled with positions that change nothing (zeros:
            ``g = 0, k = 0, beta = 0``)."""
            a = jnp.pad(a, ((0, 0), (0, n * self.chunk - t))
                        + ((0, 0),) * (a.ndim - 2))
            return jnp.moveaxis(a.reshape(
                (b, n, self.chunk) + a.shape[2:]), (1, 2), (0, 3))

        def body(s, xs):
            qc, kc, vc, gc, bc = xs
            o, s = delta_chunk(qc, kc, vc, gc, bc[..., 0], s)
            return s, o

        d = self.head_dim
        with jax.named_scope("delta_chunk"):
            last, o = lax.scan(
                body, jnp.zeros((b, self.heads, d, d), jnp.float32),
                tuple(chunks(self._heads(a)) for a in (q, k, v, g))
                + (chunks(beta.astype(jnp.float32)[..., None]),))
        o = jnp.moveaxis(o, (0, 3), (1, 2)).reshape(
            b, n * self.chunk, -1)[:, :t]
        bufs, group = self._group(layer, group)
        # key channel outermost, heads innermost, as the step reads it —
        # and row-major as the buffer lies: left to itself the compiler
        # keeps the transposed states in the order the scan made them
        # and, the write needing one layout on both sides, converts the
        # *buffer* there and back (``Window.prefill_shift``'s finding)
        last = with_layout_constraint(
            last.transpose(0, 2, 3, 1).reshape((b,) + bufs["S"].shape[2:]),
            Layout(major_to_minor=(0, 1, 2, 3)))
        at = (group[0], row, 0, 0, 0)
        old = lax.dynamic_slice(bufs["S"], at, (1,) + last.shape)
        s = lax.dynamic_update_slice(
            bufs["S"], jnp.where(valid, last[None], old), at)
        return o, self._ungroup(dict(bufs, S=s))


def dense(s, heads: int) -> np.ndarray:
    """A layer's states of one group on the host in the form that knows
    no layout: ``s`` [b, dk, rows, lanes] -> ``[b, heads, dk, dv]``."""
    s = np.asarray(s)
    b, dk = s.shape[:2]
    return s.reshape(b, dk, -1, heads).transpose(0, 3, 1, 2)


# -- the oracle -----------------------------------------------------------------

def step_reference(q, k, v, g, beta, s):
    """The recurrence's one step in plain ``jnp`` over dense states:
    ``q`` / ``k`` / ``g`` [b, heads, dk], ``v`` [b, heads, dv], ``beta``
    [b, heads], ``s`` [b, heads, dk, dv], all f32 -> ``(o [b, heads,
    dv], s)``."""
    s = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - jnp.sum(s * k[..., None], axis=-2))
    s = s + k[..., None] * u[..., None, :]
    return jnp.sum(s * q[..., None], axis=-2), s


def prefill_reference(q, k, v, g, beta):
    """The recurrence position by position from an empty memory: ``q``
    / ``k`` / ``g`` [b, t, heads, dk], ``v`` [b, t, heads, dv], ``beta``
    [b, t, heads], all f32 -> ``(o [b, t, heads, dv], s [b, heads, dk,
    dv])``."""
    def step(s, xs):
        o, s = step_reference(*xs, s)
        return s, o

    start = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:], jnp.float32)
    s, o = lax.scan(step, start, tuple(
        jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), s
