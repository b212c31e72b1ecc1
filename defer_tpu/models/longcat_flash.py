"""Meituan's LongCat-Flash decoder family (Hugging Face ``model_type``
``longcat_flash``): every layer is a **double layer** — two
latent-attention sublayers, each in front of a dense SwiGLU, around
**one shortcut-connected mixture of experts** that reads the stream
behind the first attention and joins it again only at the layer's end:

```
h1  = x  + MLA_0(rms(x))                # latent attention, cache 0
n1  = rms(h1)
s   = MoE(n1)                           # the shortcut: computed here ...
h2  = h1 + FFN_0(n1)                    # dense SwiGLU
h3  = h2 + MLA_1(rms(h2))               # latent attention, cache 1
out = h3 + FFN_1(rms(h3)) + s           # ... added here
```

**Attention** is ``models/latent_attention.py``'s, Kimi's, under the two
LoRA scales (``q_scale = (hidden / q_rank) ** 0.5`` on the query,
``latent_scale = (hidden / latent) ** 0.5`` on the normalised latent, in
the cached row), plain RoPE on adjacent pairs, scores times ``(nope +
rope) ** -0.5``.  A block keeps **two latent caches** and a step takes
two turns around them (``models/decoder.py::LatentBlock``, ``sublayers``
2); ``s`` lives across the second turn inside the block and never
crosses a stage cut.

**The MoE**: router logits in float32 over ``num_experts +
zero_experts`` columns, ``p = softmax`` over all of them, the
``experts_per_tok`` largest of ``p + b`` (the bias chooses and never
weighs), weights ``routed_scale * p`` of the chosen, **not
renormalised** (``ops/routed.py::route_top_k``, ``"softmax_bias"``).  A
chosen id below ``num_experts`` is a routed SwiGLU expert; an id from
there on is a **zero-compute expert**, the identity: it adds ``weight *
n1`` and multiplies by no matrix (``zero_expert_pairs``).  No shared
expert.  How many of a token's choices are real is data (0 to
``experts_per_tok``).  A layer may hold a share of its *routed* experts
(``experts_held``, as Kimi's): it routes over all columns, computes the
pairs that fell to the experts it holds and every zero pair (they have
no weights: a deployment computes them where the token lives), and
leaves the other chips' pairs out.

Residuals are added in float32; the stream is rounded twice a double
layer, behind ``FFN_0`` and on the way out, and ``s`` stays float32
until it is added.  The graph follows the decoder-model contract
(``embeddings`` / ``block_i`` / ``final_ln`` / ``lm_head``), one block
a double layer; an untied head.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, RMSNorm, _cast, rms_norm
from ..ops.routed import held_range, route, routed_experts
from .decoder import LatentBlock
from .kimi_k2 import _BIAS_SPREAD
from .latent_attention import LatentAttention, _normal
from .olmoe import OlmoeEmbedding
from .rotary import yarn_inv_freq


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class LongcatFlashBlock(LatentAttention, LatentBlock, Op):
    """One double layer (the module docstring).  Parameters:
    ``attn_0`` / ``attn_1`` (a latent-attention half each), ``ffn_0`` /
    ``ffn_1`` (``ln``, the norm in front, and a dense SwiGLU's
    ``gate`` / ``up`` / ``down``), ``router`` (``w`` and ``bias`` over
    routed and zero columns) and ``experts`` (the held routed experts'
    stacks)."""

    num_heads: int
    q_rank: int
    latent_dim: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    #: the rotation's frequencies a pair
    rope_freqs: tuple
    softmax_scale: float
    q_scale: float
    latent_scale: float
    #: columns of each of the two dense SwiGLUs
    dense_hidden: int
    #: routed experts the router chooses among (its first columns)
    num_experts: int
    #: zero-compute (identity) experts, the router's columns behind them
    zero_experts: int
    experts_per_tok: int
    expert_hidden: int
    routed_scale: float = 1.0
    #: the half-open range of *routed* experts the layer holds and
    #: computes (None: all); the zero experts are always here
    experts_held: tuple | None = None
    rms_eps: float = 1e-5
    attn_impl: str = "auto"

    sublayers = 2
    #: the router's rule (``ops/routed.py::route_top_k``)
    scoring = "softmax_bias"
    decode_stats = ("moe.assignments", "moe.held_assignments",
                    "moe.experts_hit", "moe.load_max",
                    "moe.zero_assignments", "moe.real_assignments")

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    def init(self, key, in_specs):
        (spec,) = in_specs
        d, ks = spec.shape[-1], jax.random.split(key, 23)
        h, dh = self.expert_hidden, self.dense_hidden
        e = self.held[1] - self.held[0]
        columns = self.num_experts + self.zero_experts

        def dense(keys):
            return {"ln": {"scale": jnp.ones((d,), jnp.float32)},
                    "gate": {"w": _normal(keys[0], (d, dh), d)},
                    "up": {"w": _normal(keys[1], (d, dh), d)},
                    "down": {"w": _normal(keys[2], (dh, d), dh)}}

        return {
            "attn_0": self._attention_init(ks[0:6], d),
            "attn_1": self._attention_init(ks[6:12], d),
            "ffn_0": dense(ks[12:15]), "ffn_1": dense(ks[15:18]),
            # every column and bias, routed (held or not) and zero: the
            # choice is the whole layer's
            "router": {"w": _normal(ks[18], (d, columns), d),
                       "bias": jax.random.normal(
                           ks[19], (columns,), jnp.float32) * _BIAS_SPREAD},
            "experts": {"gate": _normal(ks[20], (e, d, h), d),
                        "up": _normal(ks[21], (e, d, h), d),
                        "down": _normal(ks[22], (e, h, d), h)},
        }

    def widest(self, d_model: int) -> int:
        return max(super().widest(d_model), self.dense_hidden)

    # -- the halves behind an attention ------------------------------------

    def route(self, params, h):
        """``(ids [T, k] over routed and zero columns, their weights [T,
        k])`` of the normed stream ``h`` [T, d] in the type of
        ``params``: what the layer dispatches by."""
        p = _cast(params["router"], params["router"]["w"].dtype)
        return route(h.astype(p["w"].dtype), p, self.experts_per_tok,
                     self.scoring, self.routed_scale)

    def shortcut(self, params, h):
        """The shortcut branch alone on a normed stream ``h`` [T, d],
        in the type of ``params``: ``[T, d]`` float32 (what the
        benchmark's check holds to the reference's ``s``)."""
        dtype = params["router"]["w"].dtype
        return self._moe(_cast({nm: params[nm] for nm in
                                ("router", "experts")}, dtype),
                         h.astype(dtype))

    def _moe(self, p, h, sow=None):
        """The shortcut branch on the normed stream ``h`` [T, d]: the
        held routed pairs' weighted sum and the zero pairs' ``weight *
        h``, float32."""
        return routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring=self.scoring, num_experts=self.num_experts,
            held=self.held, scale=self.routed_scale,
            zero_experts=self.zero_experts, sow=sow)[0]

    def _behind(self, p, x, y, sublayer: int, carry, sow=None):
        """Sublayer ``sublayer`` behind its attention, on the stream
        ``x`` [T, d] and the heads' values merged ``y`` [T, nh * v]:
        the output projection and the dense SwiGLU on the normed sum,
        each added in float32.  Sublayer 0 also computes the shortcut's
        output on that normed sum and hands it on as ``carry``;
        sublayer 1 adds it.  ``(the stream rounded once, carry)``."""
        f32 = jnp.float32
        attn, ffn = p[f"attn_{sublayer}"], p[f"ffn_{sublayer}"]
        x32 = x.astype(f32) + jnp.dot(y, attn["proj"]["w"],
                                      preferred_element_type=f32)
        n = rms_norm(x32, ffn["ln"]["scale"], self.rms_eps).astype(x.dtype)
        if sublayer == 0:
            # named so that a trace tells the shortcut's fusions from
            # the dense path's
            with jax.named_scope("shortcut_moe"):
                carry = self._moe(p, n, sow)
        a = jax.nn.silu(n @ ffn["gate"]["w"]) * (n @ ffn["up"]["w"])
        x32 = x32 + jnp.dot(a, ffn["down"]["w"], preferred_element_type=f32)
        if sublayer == 0:
            return x32.astype(x.dtype), carry
        return (x32 + carry).astype(x.dtype), None

    def _params_behind(self, params, sublayer: int, dtype):
        """What :meth:`_behind` reads of sublayer ``sublayer``, cast: the
        attention's way out, the dense half and, in sublayer 0, the
        shortcut's router and experts."""
        attn, ffn = f"attn_{sublayer}", f"ffn_{sublayer}"
        p = {attn: {nm: params[attn][nm] for nm in self._back},
             ffn: params[ffn]}
        if sublayer == 0:
            p.update(router=params["router"], experts=params["experts"])
        return _cast(p, dtype)

    # -- full sequence -----------------------------------------------------

    def apply_with_rows(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] over the expanded
        heads; also the rows a sublayer, a tuple of two ``[b, t, latent
        + rope]``, as :meth:`round_q_row` would have handed them over
        one by one.  A dict ``sow`` is filled as a step fills it, over
        all b*t rows."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        rows, carry = [], None
        for i in range(self.sublayers):
            q_n, q_r, r = self._q_rows(p[f"attn_{i}"], x, jnp.arange(t))
            y = self._expanded(p[f"attn_{i}"], q_n, q_r, r)
            out, carry = self._behind(p, x.reshape(b * t, d),
                                      y.reshape(b * t, -1), i, carry, sow)
            x = out.reshape(b, t, d)
            rows.append(r)
        return x, tuple(rows)

    # -- one token against the two caches ----------------------------------

    def round_q_row(self, params, x, pos, sublayer: int):
        """Sublayer ``sublayer``'s absorbed queries ``[b, nh * (latent +
        rope)]`` and new row ``[b, latent + rope]`` of ``x`` [b, d] at
        scalar ``pos``."""
        attn = params[f"attn_{sublayer}"]
        return self._absorbed_q_row(
            _cast({nm: attn[nm] for nm in self._front}, x.dtype), x, pos)

    def round_finish(self, params, x, y, sublayer: int, carry, sow=None):
        """The heads' outputs ``y`` [b, nh * latent] of sublayer
        ``sublayer`` out of the latent space, then :meth:`_behind`."""
        p = self._params_behind(params, sublayer, x.dtype)
        o = self._out_of_latent(p[f"attn_{sublayer}"], x, y)
        return self._behind(p, x, o, sublayer, carry, sow)

    def flops(self, in_specs, out_spec):
        # how many of a token's choices are real is data: the count at
        # the mean of a uniform router, experts_per_tok * routed / all
        # columns (8 of 12 as published), for the whole layer's experts
        (spec,) = in_specs
        t, d = spec.shape
        columns = self.num_experts + self.zero_experts
        real = self.experts_per_tok * self.num_experts / columns
        return int(2 * self._attention_flops(t, d)
                   + 2 * 2 * t * 3 * d * self.dense_hidden
                   + 2 * t * d * columns
                   + real * 2 * t * 3 * d * self.expert_hidden)


def longcat_flash(num_layers: int, hidden: int, heads: int, q_rank: int,
                  latent_dim: int, nope_dim: int, rope_dim: int, v_dim: int,
                  dense_hidden: int, seq_len: int, vocab: int,
                  num_experts: int, zero_experts: int, experts_per_tok: int,
                  expert_hidden: int, routed_scale: float = 1.0,
                  experts_held=None, rope_theta: float = 10000000.0,
                  rms_eps: float = 1e-5,
                  name: str = "longcat_flash") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions, ``num_layers`` the number of *double* layers
    (a block each).  ``experts_held`` ``(lo, hi)`` makes every layer
    one chip's share of its routed experts.  The two LoRA scales are
    computed here, once, from the widths."""
    if experts_held is not None:
        experts_held = tuple(experts_held)
    op = LongcatFlashBlock(
        num_heads=heads, q_rank=q_rank, latent_dim=latent_dim,
        nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
        rope_freqs=yarn_inv_freq(rope_dim, rope_theta, 1.0, seq_len),
        softmax_scale=(nope_dim + rope_dim) ** -0.5,
        q_scale=(hidden / q_rank) ** 0.5,
        latent_scale=(hidden / latent_dim) ** 0.5,
        dense_hidden=dense_hidden, num_experts=num_experts,
        zero_experts=zero_experts, experts_per_tok=experts_per_tok,
        expert_hidden=expert_hidden, routed_scale=routed_scale,
        experts_held=experts_held, rms_eps=rms_eps)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def longcat_flash_tiny(seq_len: int = 32, vocab: int = 211,
                       experts_held=(0, 4), num_layers: int = 4
                       ) -> LayerGraph:
    """Four double layers; 4 heads of 16 + 8 over a latent of 32 (LoRA
    scales 1.63 and 1.41); dense halves of 96; 4 a token of 16 routed
    (4 held) + 8 zero-compute experts, x 6."""
    return longcat_flash(num_layers, 64, 4, 24, 32, 16, 8, 16, 96, seq_len,
                         vocab, 16, 8, 4, 32, routed_scale=6.0,
                         experts_held=experts_held,
                         name="longcat_flash_tiny")
