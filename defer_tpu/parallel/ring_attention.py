"""Ring attention: sequence-parallel exact attention over a mesh axis.

Long-context support is absent from the reference (CNN-only workloads —
SURVEY.md §2.3 SP row); it is first-class here.  The sequence axis is sharded
over a ``seq`` mesh axis; each device holds a Q/K/V shard and K/V shards
rotate around the ring via ``lax.ppermute`` (ICI neighbor hops) while a
numerically-stable online-softmax accumulator (flash-attention style: running
max, running denominator, rescaled value accumulator) builds the exact
attention output — memory per device is O(T/N), communication is N-1 ICI
hops of the K/V shard, and the result is bit-for-bit the same math as full
attention up to float reassociation.

The same trick the pipeline engine uses for stages (neighbor ppermute over
ICI) applied to the sequence dimension — both are instances of the
"systolic ring over the mesh" pattern this framework is built on.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


SEQ_AXIS = "seq"


def _online_block(q, k, v, m, l, acc, scale, mask=None):
    """One block of streaming-softmax attention accumulation.

    q: [B,H,Tq,D]; k,v: [B,H,Tk,D]; m,l: [B,H,Tq]; acc: [B,H,Tq,D].
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.asarray(-jnp.inf, s.dtype))
    m_blk = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    # guard fully-masked rows (m_new = -inf): keep accumulators unchanged
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    p = jnp.exp(s - safe_m[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                   causal: bool = False):
    """Exact attention with K/V rotating around the ``axis_name`` ring.

    Call inside ``shard_map``; q/k/v are the local shards [B, H, Tl, D]
    (sequence dim sharded over the ring).  ``causal`` applies a causal mask
    consistent with the *global* sequence order (shard i holds positions
    [i*Tl, (i+1)*Tl)).
    """
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, h, tl, d = q.shape
    scale = 1.0 / math.sqrt(d)
    perm = [(i, (i + 1) % n) for i in range(n)]

    m0 = jnp.full((b, h, tl), -jnp.inf, q.dtype)
    l0 = jnp.zeros((b, h, tl), q.dtype)
    acc0 = jnp.zeros_like(q)

    q_pos = idx * tl + jnp.arange(tl)

    def block(r, k_r, v_r, m, l, acc):
        # k_r/v_r hold the shard originating at device idx - r
        src = (idx - r) % n
        if causal:
            k_pos = src * tl + jnp.arange(tl)
            mask = q_pos[:, None] >= k_pos[None, :]
            mask = jnp.broadcast_to(mask[None, None], (b, h, tl, tl))
        else:
            mask = None
        return _online_block(q, k_r, v_r, m, l, acc, scale, mask)

    def step(carry, r):
        k_r, v_r, m, l, acc = carry
        m, l, acc = block(r, k_r, v_r, m, l, acc)
        k_r = lax.ppermute(k_r, axis_name, perm)
        v_r = lax.ppermute(v_r, axis_name, perm)
        return (k_r, v_r, m, l, acc), ()

    # n-1 (compute, rotate) steps, then a final compute with no rotation —
    # the last ppermute's result would be discarded, and a scan carry can't
    # be dead-code-eliminated by XLA, so keep it out of the loop
    (k_f, v_f, m, l, acc), _ = lax.scan(
        step, (k, v, m0, l0, acc0), jnp.arange(n - 1))
    m, l, acc = block(n - 1, k_f, v_f, m, l, acc)
    return acc / jnp.maximum(l, jnp.asarray(1e-20, l.dtype))[..., None]


def full_attention(q, k, v, *, causal: bool = False):
    """Reference single-device attention (for equivalence tests).

    ``causal`` uses bottom-right alignment when Tq != Tk (query row i sees
    key positions <= i + Tk - Tq), matching ``flash_attention`` decode
    semantics."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(d)
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        q_pos = jnp.arange(tq)[:, None] + (tk - tq)
        mask = q_pos >= jnp.arange(tk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def sequence_parallel_attention(q, k, v, mesh: Mesh, *,
                                axis_name: str = SEQ_AXIS,
                                causal: bool = False):
    """Convenience wrapper: global [B,H,T,D] arrays in, attention out, with
    the sequence dimension sharded over ``mesh[axis_name]`` and K/V ring-
    rotated over ICI."""
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis_name, causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
