"""Liquid AI's LFM2 mixture-of-experts decoder family (Hugging Face
``model_type`` ``lfm2_moe``; ``LFM2-24B-A2B``, ``LFM2-8B-A1B``): a
sequential pre-norm block — ``h = x + mixer(rms(x))``, ``y = h +
ffn(rms(h))`` — whose mixer is, in most layers, a **gated short
convolution** and, one layer a period, grouped-query softmax attention;
whose second half is a dense SwiGLU in the leading layers and routed
SwiGLU experts (none shared) behind them; the head tied to the
embedding.

**The convolution mixer** (``conv`` layers), on the normed stream ``u``
[t, d]: ``[B, C, X] = u W_in`` (``W_in [d, 3 d]``); ``z = B * X``; ``c(t)
= sum_j w[j] * z(t - d_conv + 1 + j)`` a channel (depthwise, causal,
zeros before the text's start, **no bias and no activation**); ``out =
(C * c) W_out``.  All a sequence keeps is ``z``'s last ``d_conv - 1``
rows — 2 rows of ``d`` values whatever the text's length
(``ops/conv_window.py``) — and no position is read.

**The attention mixer** (``full_attention`` layers): ``q = u W_q``
(``heads`` of ``head_dim``), ``k = u W_k``, ``v = u W_v`` (``kv_heads``),
no bias; ``q`` and ``k`` RMS-normed **a head over its own columns**
(weights ``[head_dim]``), then rotate-half RoPE over the whole head
(``models/olmoe.py::rope``); causal softmax attention, a group of query
heads on each KV head; ``out = att W_o``.  Keys are rotated before they
are cached.

**Routing**: sigmoid scores ``s`` over all experts in float32, the ``k``
largest of ``s + expert_bias`` (the bias chooses and never weighs), the
chosen ``s`` over their sum plus ``1e-6``, times
``routed_scaling_factor``: ``ops/routed.py::route_top_k``'s
``"noaux_tc"`` with this family's term.  Every layer holds all its
experts.

Three kinds of block on one ledger: :class:`Lfm2DenseConvBlock` and
:class:`Lfm2MoeConvBlock` are
:class:`~defer_tpu.models.decoder.ConvWindowBlock`s,
:class:`Lfm2MoeAttentionBlock` a
:class:`~defer_tpu.models.decoder.DecoderBlock`; all sow the routed
layer's three sums (a dense block zeros) and ``conv.updates``, the
sequences whose window a step really moved (an attention block sows 0,
a bubble sows 0).  The graph follows the decoder-model contract
(``embeddings`` / ``block_i`` / ``final_ln`` / ``lm_head``,
models/decoder.py).

Layouts that differ from the published checkpoint's (all of layout,
none of arithmetic): ``conv/w`` is ``[d_conv, d]`` (taps lead); an
expert's ``w1`` / ``w3`` / ``w2`` are the stacks ``experts/gate`` / ``up``
``[experts, d, width]`` and ``down [experts, width, d]``, the dense
layers' ``mlp_gate`` / ``mlp_up`` / ``mlp_down``; ``lm_head/w`` is the
embedding's table.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import RMSNorm, _cast, rms_norm
from ..ops import ssm
from ..ops.routed import route, routed_experts
from .cohere_moe import CohereHead
from .decoder import ConvWindowBlock, DecoderBlock
from .olmoe import OlmoeEmbedding, rope

CONV_LAYER, ATTENTION_LAYER = "conv", "full_attention"

#: what every block sows: OLMoE's three sums of the routed half (a dense
#: block zeros) and the sequences whose window a step really moved
_MOE_STATS = ("moe.assignments", "moe.experts_hit", "moe.load_max")
_STATS = _MOE_STATS + ("conv.updates",)
#: the divisor's term when the chosen scores are renormalised (the
#: published modelling code's)
ROUTE_EPS = 1e-6
#: the spread of a seeded ``expert_bias`` (a checkpoint's is trained):
#: as ``models/kimi_k2.py``'s — large enough to turn choices at
#: near-ties, small beside what would make an expert popular
_BIAS_SPREAD = 0.001


def _normal(key, shape, fan_in: int):
    """A matrix as the other families draw theirs: N(0, 1 / fan_in)."""
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _mat(key, shape, fan_in: int):
    return {"w": _normal(key, shape, fan_in)}


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


# -- the second half: dense in the leading layers, routed behind them --------

@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _DenseHalf:
    """A leading layer's second half (``num_dense_layers``): one SwiGLU
    of ``hidden`` columns behind the second norm."""

    hidden: int

    _ffn_params = ("ln2", "mlp_gate", "mlp_up", "mlp_down")

    def _ffn_init(self, keys, d: int) -> dict:
        return {"ln2": _ones(d),
                "mlp_gate": _mat(keys[0], (d, self.hidden), d),
                "mlp_up": _mat(keys[1], (d, self.hidden), d),
                "mlp_down": _mat(keys[2], (self.hidden, d), self.hidden)}

    def widest(self, d_model: int) -> int:
        return max(super().widest(d_model), self.hidden)

    def _ffn(self, p, h, sow=None):
        if sow is not None:
            # no router: the ledger every block of the graph shares
            # takes zeros from this one
            sow.update({name: jnp.int32(0) for name in _MOE_STATS})
        a = jax.nn.silu(h @ p["mlp_gate"]["w"]) * (h @ p["mlp_up"]["w"])
        return jnp.dot(a, p["mlp_down"]["w"],
                       preferred_element_type=jnp.float32)

    def _ffn_flops(self, t: int, d: int) -> int:
        return 2 * t * 3 * d * self.hidden


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _RoutedHalf:
    """A routed layer's second half: ``experts_per_tok`` of
    ``num_experts`` SwiGLU experts by the biased-sigmoid rule (the
    module docstring), all held, none shared."""

    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    routed_scale: float = 1.0

    _ffn_params = ("ln2", "router", "experts")
    #: the router's rule (``ops/routed.py::route_top_k``)
    scoring = "noaux_tc"

    def _ffn_init(self, keys, d: int) -> dict:
        e, h = self.num_experts, self.expert_hidden
        return {"ln2": _ones(d),
                "router": dict(_mat(keys[0], (d, e), d),
                               bias=jax.random.normal(
                                   keys[1], (e,), jnp.float32)
                               * _BIAS_SPREAD),
                "experts": {"gate": _normal(keys[2], (e, d, h), d),
                            "up": _normal(keys[3], (e, d, h), d),
                            "down": _normal(keys[4], (e, h, d), h)}}

    def widest(self, d_model: int) -> int:
        """A token's ``experts_per_tok`` rows sorted by expert."""
        return max(super().widest(d_model), d_model * self.experts_per_tok)

    def route(self, params, h):
        """``(expert ids [T, k], their weights [T, k])`` of the normed
        stream ``h`` [T, d] in the type of ``params``: what the layer
        dispatches by."""
        p = params["router"]
        return route(h.astype(p["w"].dtype), p, self.experts_per_tok,
                     self.scoring, self.routed_scale, ROUTE_EPS)

    def _ffn(self, p, h, sow=None):
        out, _ = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring=self.scoring, num_experts=self.num_experts,
            scale=self.routed_scale, eps=ROUTE_EPS, sow=sow)
        return out

    def _ffn_flops(self, t: int, d: int) -> int:
        # the router, and experts_per_tok (not num_experts) experts a token
        return (2 * t * d * self.num_experts
                + self.experts_per_tok * 2 * t * 3 * d * self.expert_hidden)


def _second_half(op, p, x32, dtype, sow, updates):
    """``x32 + ffn(rms(x32))`` of the float32 stream ``x32`` [T, d],
    rounded to ``dtype`` once, on the way out; fills ``sow`` with
    :data:`_STATS` of this step, ``conv.updates`` being ``updates``."""
    h = rms_norm(x32, p["ln2"]["scale"], op.rms_eps).astype(dtype)
    out = (x32 + op._ffn(p, h, sow)).astype(dtype)
    if sow is not None:
        sow["conv.updates"] = jnp.int32(updates)
    return out


# -- the two mixers -------------------------------------------------------------

@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _ConvMixer(ConvWindowBlock, Op):
    """The gated short-convolution mixer and the block's two residuals;
    a subclass mixes in its second half."""

    channels: int           #: the convolution's columns: the stream's
    d_conv: int = 3         #: ``conv_L_cache``
    rms_eps: float = 1e-5

    decode_stats = _STATS

    @property
    def mixer_width(self) -> int:
        """The input projection's ``[B, C, X]``."""
        return 3 * self.channels

    def init(self, key, in_specs):
        (spec,) = in_specs
        d, e, k = spec.shape[-1], self.channels, self.d_conv
        ks = jax.random.split(key, 8)
        bound = 1.0 / math.sqrt(k)      # a depthwise Conv1d's default
        return {"ln1": _ones(d),
                "in_proj": _mat(ks[0], (d, 3 * e), d),
                "conv": {"w": jax.random.uniform(ks[1], (k, e), jnp.float32,
                                                 -bound, bound)},
                "out_proj": _mat(ks[2], (e, d), e),
                **self._ffn_init(ks[3:8], d)}

    # -- the mixer's pieces, around the window's format ----------------------

    def mixer_inputs(self, params, x):
        """``z = B * X`` [..., E], the convolution's input and all the
        window keeps, and the output gate ``C`` [..., E] of the stream
        ``x`` [..., d]."""
        p = _cast({nm: params[nm] for nm in ("ln1", "in_proj")}, x.dtype)
        bcx = rms_norm(x, p["ln1"]["scale"], self.rms_eps) @ p["in_proj"]["w"]
        e = self.channels
        return bcx[..., :e] * bcx[..., 2 * e:], bcx[..., e:2 * e]

    def mixer_conv(self, params, taps):
        return ssm.causal_conv(taps, params["conv"]["w"], activation=None)

    def decode_finish(self, params, x, c, c_gate, sow=None):
        """The rest of a layer after the convolution: ``x`` [T, d] the
        residual stream, ``c`` [T, E] the convolution's output,
        ``c_gate`` [T, E] the gate ``C``.  The gate, the output
        projection, then the second half, each added to the stream in
        float32.  Sows :attr:`decode_stats` of this step."""
        f32 = jnp.float32
        p = _cast({nm: params[nm] for nm in
                   ("out_proj",) + self._ffn_params}, x.dtype)
        x32 = x.astype(f32) + jnp.dot(c_gate * c, p["out_proj"]["w"],
                                      preferred_element_type=f32)
        return _second_half(self, p, x32, x.dtype, sow, x.shape[0])

    # -- full sequence ------------------------------------------------------

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], from an
        empty window.  A dict ``sow`` is filled as :meth:`decode_finish`
        fills it, over all rows."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        fmt = self.memory_format(x.shape[-1], x.shape[1], x.dtype)
        y, _ = self.prefill(params, x, fmt.layer(fmt.zeros(x.shape[0], 1), 0),
                            fmt, sow=sow)
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # the mixer's two matrices, the gates and the taps, the second half
        (spec,) = in_specs
        t, d = spec.shape
        e = self.channels
        return (2 * t * d * 4 * e + 2 * t * e * (self.d_conv + 1)
                + self._ffn_flops(t, d))


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _AttentionMixer(DecoderBlock, Op):
    """The grouped-query attention mixer (a norm a head on queries and
    keys, then RoPE) and the block's two residuals; a subclass mixes in
    its second half."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 1000000.0
    rms_eps: float = 1e-5
    attn_impl: str = "auto"

    decode_stats = _STATS
    _front = ("ln1", "q", "q_norm", "k", "k_norm", "v")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        ks = jax.random.split(key, 9)
        return {"ln1": _ones(d),
                "q": _mat(ks[0], (d, qd), d), "q_norm": _ones(self.head_dim),
                "k": _mat(ks[1], (d, kvd), d), "k_norm": _ones(self.head_dim),
                "v": _mat(ks[2], (d, kvd), d),
                "proj": _mat(ks[3], (qd, d), qd),
                **self._ffn_init(ks[4:9], d)}

    def _qkv(self, p, x, pos):
        """Normed, rotated queries [..., t, nh, hd] and keys [..., t,
        kv, hd] and the values of ``x`` [..., t, d] at positions ``pos``
        [t]: the norm runs over a head's own ``hd`` columns."""
        y = rms_norm(x, p["ln1"]["scale"], self.rms_eps)

        def heads(a, n):
            return a.reshape(a.shape[:-1] + (n, self.head_dim))

        q = rms_norm(heads(y @ p["q"]["w"], self.num_heads),
                     p["q_norm"]["scale"], self.rms_eps)
        k = rms_norm(heads(y @ p["k"]["w"], self.num_kv_heads),
                     p["k_norm"]["scale"], self.rms_eps)
        return (rope(q, pos, self.rope_theta), rope(k, pos, self.rope_theta),
                heads(y @ p["v"]["w"], self.num_kv_heads))

    def _finish(self, p, x, y, sow=None):
        x32 = x.astype(jnp.float32) + jnp.dot(
            y, p["proj"]["w"], preferred_element_type=jnp.float32)
        return _second_half(self, p, x32, x.dtype, sow, 0)

    # -- full sequence ------------------------------------------------------

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]),
                               sow)[0]
        return y.reshape(lead + y.shape[-2:])

    def apply_with_kv(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d]; also the key
        (normed and rotated) and value columns [b, t, kv*hd] that
        :meth:`decode_qkv` would have handed over row by row."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x, jnp.arange(t))
        y = self._attend(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
        out = self._finish(p, x.reshape(b * t, d),
                           y.transpose(0, 2, 1, 3).reshape(b * t, -1), sow)
        return out.reshape(b, t, d), k.reshape(b, t, -1), v.reshape(b, t, -1)

    # -- one token against the cache ------------------------------------------

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d] at scalar
        ``pos``."""
        p = _cast({nm: params[nm] for nm in self._front}, x.dtype)
        b = x.shape[0]
        q, k, v = self._qkv(p, x[:, None], jnp.reshape(pos, (1,)))
        return q.reshape(b, -1), k.reshape(b, -1), v.reshape(b, -1)

    def decode_finish(self, params, x, y, sow=None):
        p = _cast({nm: params[nm] for nm in ("proj",) + self._ffn_params},
                  x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        return (2 * t * d * (2 * qd + 2 * kvd) + 4 * t * t * qd
                + self._ffn_flops(t, d))


# -- the three kinds of layer ------------------------------------------------------

@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class Lfm2DenseConvBlock(_DenseHalf, _ConvMixer):
    """A leading layer: the convolution mixer, then a dense SwiGLU."""


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class Lfm2MoeConvBlock(_RoutedHalf, _ConvMixer):
    """A routed convolution layer: the convolution mixer, then the
    routed experts."""


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class Lfm2MoeAttentionBlock(_RoutedHalf, _AttentionMixer):
    """A routed attention layer: grouped-query attention, then the
    routed experts."""


def lfm2_moe(num_layers: int, hidden: int, heads: int, kv_heads: int,
             head_dim: int, dense_hidden: int, seq_len: int, vocab: int,
             layer_types, num_experts: int, experts_per_tok: int,
             expert_hidden: int, dense_layers: int = 2, d_conv: int = 3,
             routed_scale: float = 1.0, rope_theta: float = 1000000.0,
             rms_eps: float = 1e-5, name: str = "lfm2_moe") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length and the most an attention layer may cache).  ``layer_types``
    names each layer ``"conv"`` or ``"full_attention"``; a shorter list
    is the pattern's period and repeats.  The first ``dense_layers``
    layers (``num_dense_layers``) end in a dense SwiGLU of
    ``dense_hidden`` columns, the rest in routed experts; no published
    configuration has an attention layer among the dense ones, and one
    is refused.  ``final_ln`` is the family's ``embedding_norm``.
    Initialise with ``cohere_moe.tie_head(graph.init(key))``: the head
    is the embedding's table."""
    layer_types = list(layer_types)
    for kind in layer_types:
        if kind not in (CONV_LAYER, ATTENTION_LAYER):
            raise ValueError(f"layer type {kind!r} is neither "
                             f"{CONV_LAYER!r} nor {ATTENTION_LAYER!r}")
    routed = dict(num_experts=num_experts, experts_per_tok=experts_per_tok,
                  expert_hidden=expert_hidden, routed_scale=routed_scale)
    conv = dict(channels=hidden, d_conv=d_conv, rms_eps=rms_eps)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        kind = layer_types[i % len(layer_types)]
        if kind == ATTENTION_LAYER:
            if i < dense_layers:
                raise ValueError(
                    f"layer {i} is a dense layer (the first {dense_layers}) "
                    "and an attention layer: this family's dense layers "
                    "are convolution layers")
            op = Lfm2MoeAttentionBlock(
                num_heads=heads, num_kv_heads=kv_heads, head_dim=head_dim,
                rope_theta=rope_theta, rms_eps=rms_eps, **routed)
        elif i < dense_layers:
            op = Lfm2DenseConvBlock(hidden=dense_hidden, **conv)
        else:
            op = Lfm2MoeConvBlock(**conv, **routed)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(CohereHead(vocab), x, name="lm_head")
    return b.build()


def lfm2_moe_tiny(seq_len: int = 32, vocab: int = 211) -> LayerGraph:
    """Two periods of ``conv conv attention conv``, the first two layers
    dense; 4 query heads on 2 KV heads of 16; 2 of 8 experts of 32 a
    token.  Two stages of four layers repeat one pattern of memory."""
    return lfm2_moe(8, 64, 4, 2, 16, 96, seq_len, vocab,
                    (CONV_LAYER,) * 2 + (ATTENTION_LAYER, CONV_LAYER),
                    8, 2, 32, name="lfm2_moe_tiny")
