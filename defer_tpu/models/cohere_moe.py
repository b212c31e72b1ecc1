"""Cohere's routed-expert decoder family (Hugging Face ``model_type``
``cohere2_moe``; ``command-a-plus``): one bias-free LayerNorm feeds an
attention branch and a feed-forward branch *in parallel* (``y = x +
attn(h) + ffn(h)``); grouped queries (many query heads a KV head, a
head's width its own and not the stream's over the heads); layers in a
fixed pattern of **window** layers — interleaved RoPE, attention over
the ``window`` newest positions, the token's own counted — and **full**
layers — no positional signal at all, every earlier position; a
feed-forward branch of routed SwiGLU experts, chosen by *sigmoid*
scores renormalised over the chosen, beside shared experts every token
passes, averaged among themselves; the head tied to the embedding.

**A layer may hold a share of its routed experts** (``experts_held``:
one chip's under expert parallelism).  It still routes over all of
them — the router's columns, the top ``k`` and the renormalisation are
the whole layer's — computes the pairs that fell to the experts it
holds (``ops/routed.py::expert_dispatch_held``) and adds the shared
term; the rest of the routed sum is other chips', and nothing here
stands in for them.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py).  A window
layer publishes its ``window``, so the holder of its memory keeps a
ring buffer of that many rows for it (``ops/kv_cache.py``) beside the
full layers' row a position.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import _cast
from ..ops.routed import held_range, routed_experts
from .decoder import DecoderBlock
from .olmoe import OlmoeEmbedding

WINDOW_LAYER, FULL_LAYER = "sliding_attention", "full_attention"


def layer_norm(x, scale, eps: float):
    """LayerNorm over the last axis with a scale and no bias, statistics
    in float32; the result in ``x``'s type."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


def rope_interleaved(x, pos, theta: float, inv_freq=None):
    """Interleaved RoPE over the whole head (``rope_gptj``): the pair
    ``(x[2i], x[2i + 1])`` turned by ``pos * theta ** (-2i / hd)`` — or
    by ``pos * inv_freq[i]`` where a family scales its frequencies
    (``inv_freq`` [hd / 2]; ``theta`` is then not read);
    ``x`` [..., t, nh, hd] at positions ``pos`` [t] (angles in float32).
    A pair's partner, signed (``-x[2i + 1]`` at ``2i``, ``x[2i]`` at ``2i
    + 1``), is ``x`` times a fixed ``hd x hd`` matrix of 0 and +-1: one
    small product, exact in ``x``'s own type — a strided gather or a
    roll along the lanes costs the chip a padded copy of ``x``."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [t, hd/2]
    cos = jnp.repeat(jnp.cos(ang), 2, axis=-1)[:, None, :]
    sin = jnp.repeat(jnp.sin(ang), 2, axis=-1)[:, None, :]
    i = jnp.arange(hd)
    partner = jnp.where(i % 2 == 0, i + 1, i - 1)
    swap = (i[:, None] == partner[None, :]) \
        * jnp.where(i % 2 == 0, -1, 1)[None, :]           # [from, to]
    rot = jnp.dot(x, swap.astype(x.dtype), precision=lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos
            + rot.astype(jnp.float32) * sin).astype(x.dtype)


@dataclasses.dataclass(frozen=True, repr=False)
class CohereMoeBlock(DecoderBlock, Op):
    """One layer as a single graph node: the parallel block of the
    module docstring.  ``window`` says which kind the layer is (None: a
    full layer, no rotation); ``num_experts`` what its router chooses
    among and ``experts_held`` the half-open range it holds and
    computes (None: all)."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    num_shared: int
    window: int | None = None
    experts_held: tuple | None = None
    rope_theta: float = 50000.0
    ln_eps: float = 1e-5
    attn_impl: str = "auto"

    decode_stats = ("moe.assignments", "moe.held_assignments",
                    "moe.experts_hit", "moe.load_max")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        h, sh = self.expert_hidden, self.num_shared * self.expert_hidden
        e = self.held[1] - self.held[0]
        ks = jax.random.split(key, 11)
        s = 1.0 / math.sqrt(d)

        def mat(k, shape, scale):
            return jax.random.normal(k, shape, jnp.float32) * scale

        return {
            "ln": {"scale": jnp.ones((d,), jnp.float32)},
            "q": {"w": mat(ks[0], (d, qd), s)},
            "k": {"w": mat(ks[1], (d, kvd), s)},
            "v": {"w": mat(ks[2], (d, kvd), s)},
            "proj": {"w": mat(ks[3], (qd, d), 1.0 / math.sqrt(qd))},
            # every expert's column, held or not: the choice is the
            # whole layer's
            "router": {"w": mat(ks[4], (d, self.num_experts), s)},
            "experts": {"gate": mat(ks[5], (e, d, h), s),
                        "up": mat(ks[6], (e, d, h), s),
                        "down": mat(ks[7], (e, h, d), 1.0 / math.sqrt(h))},
            # the shared experts side by side: expert j is columns (of
            # gate and up) and rows (of down) j*h .. (j+1)*h - 1, and
            # one product of each is their sum
            "shared_gate": {"w": mat(ks[8], (d, sh), s)},
            "shared_up": {"w": mat(ks[9], (d, sh), s)},
            "shared_down": {"w": mat(ks[10], (sh, d), 1.0 / math.sqrt(h))},
        }

    # -- the two halves of a layer ----------------------------------------

    def _qkv(self, p, x, pos):
        """Queries [..., t, nh, hd], keys and values [..., t, kv, hd] of
        ``x`` [..., t, d] at positions ``pos`` [t]: rotated in a window
        layer, as they come in a full one."""
        h = layer_norm(x, p["ln"]["scale"], self.ln_eps)

        def heads(a, n):
            return a.reshape(a.shape[:-1] + (n, self.head_dim))

        q = heads(h @ p["q"]["w"], self.num_heads)
        k = heads(h @ p["k"]["w"], self.num_kv_heads)
        v = heads(h @ p["v"]["w"], self.num_kv_heads)
        if self.window is not None:
            q = rope_interleaved(q, pos, self.rope_theta)
            k = rope_interleaved(k, pos, self.rope_theta)
        return q, k, v

    def _finish(self, p, x, y, sow=None):
        """The layer's output from the stream ``x`` [T, d] and the
        attention's heads merged ``y`` [T, nh*hd]: the output projection
        and, from the same normed stream the attention read, the held
        routed experts and the shared ones' mean; all three added to
        the stream in float32, which is rounded once on the way out."""
        f32 = jnp.float32
        h = layer_norm(x, p["ln"]["scale"], self.ln_eps)
        attn = jnp.dot(y, p["proj"]["w"], preferred_element_type=f32)
        routed, shared = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring="sigmoid", num_experts=self.num_experts, held=self.held,
            shared=(p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"]), sow=sow)
        return (x.astype(f32) + attn + routed
                + shared / self.num_shared).astype(x.dtype)

    # -- full sequence ----------------------------------------------------

    def apply(self, params, x):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]))[0]
        return y.reshape(lead + y.shape[-2:])

    def apply_with_kv(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d]; also returns the key
        and value columns [b, t, kv*hd] that :meth:`decode_qkv` would
        have handed over row by row.  A dict ``sow`` is filled as
        :meth:`decode_finish` fills it, over all b*t rows."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x, jnp.arange(t))
        y = self._attend(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                         window=self.window)
        out = self._finish(p, x.reshape(b * t, d),
                           y.transpose(0, 2, 1, 3).reshape(b * t, -1), sow)
        return out.reshape(b, t, d), k.reshape(b, t, -1), v.reshape(b, t, -1)

    # -- one token against the cache --------------------------------------

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d] at scalar
        ``pos``."""
        p = _cast({nm: params[nm] for nm in ("ln", "q", "k", "v")}, x.dtype)
        b = x.shape[0]
        q, k, v = self._qkv(p, x[:, None], jnp.reshape(pos, (1,)))
        return q.reshape(b, -1), k.reshape(b, -1), v.reshape(b, -1)

    def decode_finish(self, params, x, y, sow=None):
        """The output projection of the attention's output ``y`` beside
        the experts; sows :attr:`decode_stats` of this step."""
        p = _cast({nm: params[nm] for nm in (
            "ln", "proj", "router", "experts", "shared_gate", "shared_up",
            "shared_down")}, x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        # q/k/v/o, attention over the window or the whole, the router,
        # experts_per_tok routed and num_shared shared experts a token
        # (the whole layer's: a share holds fewer)
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        reach = t if self.window is None else min(t, self.window)
        return (2 * t * d * (2 * qd + 2 * kvd) + 4 * t * reach * qd
                + 2 * t * d * self.num_experts
                + (self.experts_per_tok + self.num_shared)
                * 2 * t * 3 * d * self.expert_hidden)


@dataclasses.dataclass(frozen=True, repr=False)
class ScaleLayerNorm(Op):
    """LayerNorm with a scale and no bias (:func:`layer_norm`)."""

    eps: float = 1e-5

    def init(self, key, in_specs):
        del key
        (spec,) = in_specs
        return {"scale": jnp.ones((spec.shape[-1],), jnp.float32)}

    def apply(self, params, x):
        return layer_norm(x, params["scale"], self.eps)


@dataclasses.dataclass(frozen=True, repr=False)
class CohereHead(Op):
    """The output head, laid out as the embedding's table is —
    ``[vocab, d]``, ``logits = logit_scale * h w^T`` — so that the tied
    model's head *is* that table (:func:`tie_head`)."""

    vocab: int
    logit_scale: float = 1.0

    def init(self, key, in_specs):
        (spec,) = in_specs
        return {"w": jax.random.normal(
            key, (self.vocab, spec.shape[-1]), jnp.float32) * 0.02}

    def apply(self, params, x):
        w = params["w"].astype(x.dtype)
        out = lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())))
        return out if self.logit_scale == 1.0 else out * self.logit_scale

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        return 2 * spec.size * self.vocab


def tie_head(params: dict) -> dict:
    """``params`` with the head's matrix the embedding's table
    (``tie_word_embeddings``): what a tied checkpoint loads as.  The
    ring's two ends each hold their copy."""
    return dict(params, lm_head={"w": params["embeddings"]["wte"]})


def cohere_moe(num_layers: int, hidden: int, heads: int, kv_heads: int,
               head_dim: int, seq_len: int, vocab: int, num_experts: int,
               experts_per_tok: int, expert_hidden: int, num_shared: int,
               layer_types, window: int, experts_held=None,
               rope_theta: float = 50000.0, ln_eps: float = 1e-5,
               logit_scale: float = 1.0,
               name: str = "cohere_moe") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions (the full-sequence graph's length and the most
    a full layer may cache).  ``layer_types`` names each layer
    ``"sliding_attention"`` (a window layer of ``window`` positions) or
    ``"full_attention"``; a shorter list is the pattern's period and
    repeats.  ``experts_held`` ``(lo, hi)`` makes every layer one
    chip's share of its routed experts.  Initialise with
    ``tie_head(graph.init(key))``: the head is the embedding's table."""
    layer_types = list(layer_types)
    for kind in layer_types:
        if kind not in (WINDOW_LAYER, FULL_LAYER):
            raise ValueError(f"layer type {kind!r} is neither "
                             f"{WINDOW_LAYER!r} nor {FULL_LAYER!r}")
    if experts_held is not None:
        experts_held = tuple(experts_held)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        kind = layer_types[i % len(layer_types)]
        x = b.add(CohereMoeBlock(
            heads, kv_heads, head_dim, num_experts, experts_per_tok,
            expert_hidden, num_shared,
            window=window if kind == WINDOW_LAYER else None,
            experts_held=experts_held, rope_theta=rope_theta,
            ln_eps=ln_eps), x, name=f"block_{i}")
    x = b.add(ScaleLayerNorm(eps=ln_eps), x, name="final_ln")
    x = b.add(CohereHead(vocab, logit_scale), x, name="lm_head")
    return b.build()


def cohere_moe_tiny(seq_len: int = 32, vocab: int = 211,
                    experts_held=(0, 2)) -> LayerGraph:
    """Two periods of three window layers (8 positions) and a full one;
    8 query heads on 2 KV heads; 4 of 16 experts a token, 2 of the 16
    held; 2 shared."""
    return cohere_moe(8, 64, 8, 2, 8, seq_len, vocab, 16, 4, 32, 2,
                      (WINDOW_LAYER,) * 3 + (FULL_LAYER,), 8,
                      experts_held=experts_held, name="cohere_moe_tiny")
