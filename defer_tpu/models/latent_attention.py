"""The latent-attention (MLA) half that ``models/kimi_k2.py`` and
``models/longcat_flash.py`` share: one definition of the query and the
row, of the prompt's expanded form and of the step's absorbed form.

Position ``t``, head ``i``, on the normed stream ``h``: ``c_q = rms(h
W_qa)``, ``[q_n, q_r] = c_q W_qb * q_scale`` a head; ``[c', k'] = h
W_kva``, ``c = rms(c') * latent_scale``, ``k_r = rope(k', t)`` (one for
all heads), ``q_r <- rope(q_r, t)``; ``k_n[i] = W_uk[i] c``, ``v[i] = c
W_uv[i]``; scores ``(q_n . k_n + q_r . k_r) * softmax_scale``, causal.

The two scales are LongCat-Flash's (``mla_scale_q_lora``: ``(hidden /
q_rank) ** 0.5`` on the query behind ``W_qb``; ``mla_scale_kv_lora``:
``(hidden / latent) ** 0.5`` on the normalised latent in front of
``W_uk`` / ``W_uv``, so on ``k_n`` and ``v`` and not on ``k_r``); Kimi
has neither (1 and 1: no multiply is traced).  **The latent's scale
lives in the cached row**: the row a position keeps is ``[c *
latent_scale, k_r]``, what both forms read, so the prompt's expanded
heads and a step's absorbed queries agree by construction and ``W_uk``
/ ``W_uv`` stay the checkpoint's.

A block holds the half's parameters as one dict (``in_ln``, ``q_a``,
``q_a_ln``, ``q_b``, ``kv_a``, ``kv_a_ln``, ``k_up``, ``v_up``,
``proj``: :meth:`LatentAttention._attention_init`) — the block's own
tree for Kimi, one a sublayer for LongCat-Flash — and every method here
takes that dict.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..graph.ops import rms_norm
from .cohere_moe import rope_interleaved


def _normal(key, shape, fan_in: int):
    """A matrix as the other families draw theirs: N(0, 1 / fan_in)."""
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


class LatentAttention:
    """What a block with a latent-attention half mixes in.  It names
    ``num_heads``, ``q_rank``, ``latent_dim``, ``nope_dim``,
    ``rope_dim``, ``v_dim``, ``rope_freqs`` (a pair's frequencies),
    ``softmax_scale``, ``rms_eps`` and ``_attention_impl()``
    (``DecoderBlock``'s)."""

    #: what ``[q_n, q_r]`` is multiplied by behind ``W_qb``
    q_scale = 1.0
    #: what the normalised latent is multiplied by, in the cached row
    latent_scale = 1.0

    #: parameters the query-and-row part reads, and the way out's
    _front = ("in_ln", "q_a", "q_a_ln", "q_b", "kv_a", "kv_a_ln", "k_up")
    _back = ("v_up", "proj")

    def _attention_init(self, keys, d: int) -> dict:
        """The half's parameters on a stream ``d`` wide, from
        ``keys[0..5]``."""
        nh, r, c = self.num_heads, self.q_rank, self.latent_dim

        def ones(n):
            return {"scale": jnp.ones((n,), jnp.float32)}

        return {
            "in_ln": ones(d),
            "q_a": {"w": _normal(keys[0], (d, r), d)}, "q_a_ln": ones(r),
            # a head's columns: nope_dim of q_n, then rope_dim of q_r
            "q_b": {"w": _normal(keys[1], (
                r, nh * (self.nope_dim + self.rope_dim)), r)},
            # the latent's columns, then the shared key's
            "kv_a": {"w": _normal(keys[2], (d, c + self.rope_dim), d)},
            "kv_a_ln": ones(c),
            "k_up": {"w": _normal(keys[3], (nh, self.nope_dim, c), c)},
            "v_up": {"w": _normal(keys[4], (nh, c, self.v_dim), c)},
            "proj": {"w": _normal(keys[5], (nh * self.v_dim, d),
                                  nh * self.v_dim)},
        }

    # -- the attention's two forms -----------------------------------------

    def _q_rows(self, p, x, pos):
        """``(q_n [..., t, nh, nope], q_r [..., t, nh, rope] rotated, rows
        [..., t, latent + rope])`` of ``x`` [..., t, d] at positions
        ``pos`` [t]: the rows final, as the cache keeps them."""
        nh, c = self.num_heads, self.latent_dim
        h = rms_norm(x, p["in_ln"]["scale"], self.rms_eps)
        cq = rms_norm(h @ p["q_a"]["w"], p["q_a_ln"]["scale"], self.rms_eps)
        q = (cq @ p["q_b"]["w"]).reshape(x.shape[:-1] + (nh, -1))
        if self.q_scale != 1.0:
            q = q * jnp.asarray(self.q_scale, q.dtype)
        kv = h @ p["kv_a"]["w"]
        latent = rms_norm(kv[..., :c], p["kv_a_ln"]["scale"], self.rms_eps)
        if self.latent_scale != 1.0:
            latent = latent * jnp.asarray(self.latent_scale, latent.dtype)
        k_r = rope_interleaved(kv[..., None, c:], pos, 0.0, self.rope_freqs)
        q_r = rope_interleaved(q[..., self.nope_dim:], pos, 0.0,
                               self.rope_freqs)
        return q[..., :self.nope_dim], q_r, jnp.concatenate(
            [latent, k_r[..., 0, :]], axis=-1)

    def _expanded(self, p, q_n, q_r, rows):
        """Causal attention of a prompt ``[b, t, ...]`` over the expanded
        heads: ``[b, t, nh * v]``."""
        c, f32 = self.latent_dim, jnp.float32
        latent, k_r = rows[..., :c], rows[..., None, c:]
        k_n = jnp.einsum("btc,hnc->bhtn", latent, p["k_up"]["w"],
                         preferred_element_type=f32).astype(rows.dtype)
        v = jnp.einsum("btc,hcv->bhtv", latent, p["v_up"]["w"],
                       preferred_element_type=f32).astype(rows.dtype)
        q_n, q_r, k_r = (a.transpose(0, 2, 1, 3) for a in (q_n, q_r, k_r))
        if self._attention_impl() == "flash":
            from ..ops.flash_attention import flash_latent
            y = flash_latent(q_n, q_r, k_n, k_r, v, scale=self.softmax_scale)
        else:
            att = (jnp.einsum("bhqn,bhkn->bhqk", q_n, k_n)
                   + jnp.einsum("bhqr,bxkr->bhqk", q_r, k_r)) \
                * self.softmax_scale
            t = att.shape[-1]
            att = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :],
                            att, jnp.asarray(-jnp.inf, att.dtype))
            y = jnp.einsum("bhqk,bhkv->bhqv", jax.nn.softmax(att, axis=-1), v)
        return y.transpose(0, 2, 1, 3).reshape(y.shape[0], y.shape[2], -1)

    def _absorbed_q_row(self, p, x, pos):
        """Every head's absorbed query ``[b, nh * (latent + rope)]`` and
        the new row ``[b, latent + rope]`` of ``x`` [b, d] at scalar
        ``pos``; ``p`` holds :attr:`_front`."""
        q_n, q_r, rows = self._q_rows(p, x[:, None], jnp.reshape(pos, (1,)))
        q_abs = jnp.einsum("bhn,hnc->bhc", q_n[:, 0], p["k_up"]["w"],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs.astype(x.dtype), q_r[:, 0]], axis=-1)
        return q.reshape(x.shape[0], -1), rows[:, 0]

    def _out_of_latent(self, p, x, y):
        """The heads' outputs ``y`` [b, nh * latent] out of the latent
        space (``W_uv``): ``[b, nh * v]`` in ``x``'s type."""
        o = jnp.einsum("bhc,hcv->bhv",
                       y.reshape(x.shape[0], self.num_heads, -1),
                       p["v_up"]["w"], preferred_element_type=jnp.float32)
        return o.astype(x.dtype).reshape(x.shape[0], -1)

    def _attention_flops(self, t: int, d: int) -> int:
        nh = self.num_heads
        qk, kv = self.nope_dim + self.rope_dim, self.nope_dim + self.v_dim
        return (2 * t * (d * self.q_rank + self.q_rank * nh * qk
                         + d * (self.latent_dim + self.rope_dim)
                         + self.latent_dim * nh * kv + nh * self.v_dim * d)
                + 2 * t * t * nh * (qk + self.v_dim))
