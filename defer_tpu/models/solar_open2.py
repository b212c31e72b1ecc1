"""Upstage's Solar-Open2 decoder family (Hugging Face ``model_type``
``solar_open2``; ``Solar-Open2-250B``): a sequential pre-norm block —
``h = x + mixer(rms(x))``, ``y = h + moe(rms(h))`` — whose mixer is, in
three layers of four, **Kimi Delta Attention** (a gated delta rule with
a decay a channel, arXiv:2510.26692) and, one layer a period
(``gqa_layers``), grouped-query softmax attention **without any
position** (``use_rope: false``) under a sigmoid gate; every layer's
second half routed SwiGLU experts beside one shared expert; an untied
head.  No position is read anywhere: the delta rule's state and the
causal mask are all the order there is.

**The KDA mixer**, on the normed stream ``u`` [t, d], ``H`` heads of
``D`` key and value channels: ``[q~, k~, v~] = u W_in`` (``W_in [d, 3 H
D]``); a depthwise causal convolution of ``d_conv`` taps over each,
then SiLU, no bias; a head at a time ``q = l2norm(q') / sqrt(D)``, ``k
= l2norm(k')``, ``v = v'``.  The log-decay a head a key channel ``g =
-exp(A_log[h]) * softplus(u W_f_down W_f_up + dt_bias)``; the write's
strength ``beta = 2 sigmoid(u W_beta)`` (in (0, 2): the transition's
eigenvalues reach (-1, 1)).  The recurrence (``ops/delta_rule.py``):
``S' = Diag(exp g) S``, ``S <- S' + beta k (v - S'^T k)^T``, ``o = S^T
q``.  Out: RMSNorm a head (a learned weight of ``D``) of ``o``, times
``sigmoid(u W_g_down W_g_up)``, merged, ``W_o``.  A sequence keeps the
three convolutions' last ``d_conv - 1`` inputs and ``S``, float32.

**The GQA mixer**: ``q = u W_q`` (``heads`` of ``head_dim``), ``k = u
W_k``, ``v = u W_v`` (``kv_heads``), no bias, no norm a head, **no
rotation**; causal softmax at ``1 / sqrt(head_dim)``; the merged heads
times ``sigmoid(u W_gate)``; ``W_o``.  Keys are cached as projected.

**The MoE half**: sigmoid scores over all experts in float32, the ``k``
largest of ``score + bias`` (the bias chooses and never weighs), the
chosen scores over their sum, times ``routed_scaling_factor``:
``ops/routed.py::route_top_k``'s ``"noaux_tc"``; one shared SwiGLU
expert on every token, added whole.  A layer may hold a share of its
routed experts (``experts_held``): it routes over all of them, computes
the pairs that fall to its share and adds the shared expert whole.

Two kinds of block on one ledger: :class:`SolarKdaBlock` is a
:class:`~defer_tpu.models.decoder.DeltaRuleBlock`,
:class:`SolarAttentionBlock` a
:class:`~defer_tpu.models.decoder.DecoderBlock`; both sow the routed
layer's four sums and ``delta.updates``, the sequences whose state a
step really rewrote (an attention block sows 0, a bubble sows 0).  The
graph follows the decoder-model contract (``embeddings`` / ``block_i``
/ ``final_ln`` / ``lm_head``, models/decoder.py).

Layouts that differ from the published checkpoint's (all of layout,
none of arithmetic): ``in_proj/w`` is ``q_proj``, ``k_proj`` and
``v_proj`` side by side and ``conv/w`` ``[d_conv, 3 H D]`` their three
convolutions' (taps lead); an expert's matrices are the stacks
``experts/gate`` / ``up`` ``[experts, d, width]`` and ``down [experts,
width, d]``, the shared expert's ``shared_gate`` / ``shared_up`` /
``shared_down``; ``lm_head/w`` is ``[vocab, d]``.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import RMSNorm, _cast, rms_norm
from ..ops import ssm
from ..ops.routed import held_range, route, routed_experts
from .cohere_moe import CohereHead
from .decoder import DecoderBlock, DeltaRuleBlock
from .lfm2_moe import _mat, _normal, _ones
from .olmoe import OlmoeEmbedding

#: what every block sows: the routed half's four sums and the sequences
#: whose delta-rule state a step rewrote
_STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
          "moe.load_max", "delta.updates")
#: the spread of a seeded selection bias (a checkpoint's is trained):
#: ``models/kimi_k2.py``'s
_BIAS_SPREAD = 0.001
#: under the root of a head's squared sum (the published kernels' l2norm)
L2_EPS = 1e-6


def l2norm(a):
    """``a`` [..., D] over its last axis, in float32."""
    a = a.astype(jnp.float32)
    return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)


# -- the second half, every layer's ---------------------------------------------

@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _ExpertHalf:
    """``experts_per_tok`` of ``num_experts`` routed SwiGLU experts by
    the biased-sigmoid rule beside one shared expert of the same width.
    ``experts_held`` is the half-open range the layer holds and
    computes (None: all)."""

    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    routed_scale: float = 1.0
    experts_held: tuple | None = None
    rms_eps: float = 1e-5

    decode_stats = _STATS
    _ffn_params = ("ln2", "router", "experts", "shared_gate", "shared_up",
                   "shared_down")
    #: the router's rule (``ops/routed.py::route_top_k``)
    scoring = "noaux_tc"

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    def _ffn_init(self, keys, d: int) -> dict:
        h, e = self.expert_hidden, self.held[1] - self.held[0]
        return {
            "ln2": _ones(d),
            # every expert's column and bias, held or not: the choice is
            # the whole layer's
            "router": dict(_mat(keys[0], (d, self.num_experts), d),
                           bias=jax.random.normal(
                               keys[1], (self.num_experts,), jnp.float32)
                           * _BIAS_SPREAD),
            "experts": {"gate": _normal(keys[2], (e, d, h), d),
                        "up": _normal(keys[3], (e, d, h), d),
                        "down": _normal(keys[4], (e, h, d), h)},
            "shared_gate": _mat(keys[5], (d, h), d),
            "shared_up": _mat(keys[6], (d, h), d),
            "shared_down": _mat(keys[7], (h, d), h)}

    def widest(self, d_model: int) -> int:
        """A token's ``experts_per_tok`` rows sorted by expert."""
        return max(super().widest(d_model), d_model * self.experts_per_tok)

    def route(self, params, h):
        """``(expert ids [T, k], their weights [T, k])`` of the normed
        stream ``h`` [T, d] in the type of ``params``: what the layer
        dispatches by."""
        p = params["router"]
        return route(h.astype(p["w"].dtype), p, self.experts_per_tok,
                     self.scoring, self.routed_scale)

    def _second_half(self, p, x32, dtype, sow, updates):
        """``x32 + moe(rms(x32))`` of the float32 stream ``x32`` [T, d],
        rounded to ``dtype`` once, on the way out; fills ``sow`` with
        :data:`_STATS` of this step, ``delta.updates`` being
        ``updates``."""
        h = rms_norm(x32, p["ln2"]["scale"], self.rms_eps).astype(dtype)
        routed, shared = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring=self.scoring, num_experts=self.num_experts,
            held=self.held, scale=self.routed_scale,
            shared=(p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"]), sow=sow)
        if sow is not None:
            sow["delta.updates"] = jnp.int32(updates)
        return (x32 + routed + shared).astype(dtype)

    def _ffn_flops(self, t: int, d: int) -> int:
        # the whole layer's experts_per_tok routed and one shared expert
        # a token (a share holds fewer)
        return (2 * t * d * self.num_experts
                + (self.experts_per_tok + 1) * 2 * t * 3 * d
                * self.expert_hidden)


# -- the two mixers -------------------------------------------------------------

@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class SolarKdaBlock(_ExpertHalf, DeltaRuleBlock, Op):
    """A KDA layer: the delta-rule mixer (the module docstring), then
    the routed experts."""

    heads: int
    head_dim: int
    d_conv: int = 4         #: ``short_conv_kernel_size``
    gate_rank: int = 128    #: the low rank of the decay's and the gate's paths
    chunk: int = 64

    _front = ("ln1", "in_proj", "f_down", "f_up", "g_down", "g_up", "beta")

    @property
    def mixer_width(self) -> int:
        """``q``, ``k`` and ``v`` side by side."""
        return 3 * self.heads * self.head_dim

    def init(self, key, in_specs):
        (spec,) = in_specs
        d, e, r = spec.shape[-1], self.heads * self.head_dim, self.gate_rank
        k = self.d_conv
        ks = jax.random.split(key, 18)
        bound = 1.0 / math.sqrt(k)      # a depthwise Conv1d's default
        # the published layer's initialisation (fla's KDA): a head's
        # rate in [1, 16], a channel's step log-uniform in [1e-3, 1e-1]
        # behind the softplus — memories of under a position to a
        # thousand
        dt = jnp.exp(jax.random.uniform(
            ks[9], (e,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {"ln1": _ones(d),
                "in_proj": _mat(ks[0], (d, 3 * e), d),
                "conv": {"w": jax.random.uniform(
                    ks[1], (k, 3 * e), jnp.float32, -bound, bound)},
                "f_down": _mat(ks[2], (d, r), d),
                "f_up": _mat(ks[3], (r, e), r),
                "decay": {"A_log": jnp.log(jax.random.uniform(
                    ks[8], (self.heads,), jnp.float32, 1.0, 16.0)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt))},
                "beta": _mat(ks[4], (d, self.heads), d),
                "g_down": _mat(ks[5], (d, r), d),
                "g_up": _mat(ks[6], (r, e), r),
                "o_norm": _ones(self.head_dim),
                "out_proj": _mat(ks[7], (e, d), e),
                **self._ffn_init(ks[10:18], d)}

    # -- the mixer's pieces, around the state's format -----------------------

    def mixer_inputs(self, params, x):
        """The convolutions' input ``[q~, k~, v~]`` [..., 3 H D], all
        the window keeps, and what else the layer makes of the normed
        stream ``x`` [..., d]: ``f`` [..., H D] the decay's projection
        and ``beta`` [..., H]'s before their activations (float32) and
        the output gate's ``gate`` [..., H D]."""
        f32 = jnp.float32
        p = _cast({nm: params[nm] for nm in self._front}, x.dtype)
        u = rms_norm(x, p["ln1"]["scale"], self.rms_eps)

        def low_rank(down, up):
            return jnp.dot(u @ p[down]["w"], p[up]["w"],
                           preferred_element_type=f32)

        return u @ p["in_proj"]["w"], {
            "f": low_rank("f_down", "f_up"),
            "beta": jnp.dot(u, p["beta"]["w"], preferred_element_type=f32),
            "gate": low_rank("g_down", "g_up")}

    def mixer_conv(self, params, taps):
        return ssm.causal_conv(taps, params["conv"]["w"])

    def mixer_selection(self, params, c, rest):
        """What the recurrence takes of the convolutions' output ``c``
        [..., 3 H D]: ``q`` and ``k`` normed a head, ``v``, the
        log-decay a key channel and ``beta``, all float32."""
        f32 = jnp.float32
        e = self.heads * self.head_dim

        def normed(a, scale=1.0):
            a = l2norm(a.reshape(a.shape[:-1] + (self.heads, -1))) * scale
            return a.reshape(a.shape[:-2] + (e,))

        p = params["decay"]
        rate = jnp.repeat(jnp.exp(p["A_log"].astype(f32)), self.head_dim)
        g = -rate * jax.nn.softplus(rest["f"] + p["dt_bias"].astype(f32))
        return (normed(c[..., :e], self.head_dim ** -0.5),
                normed(c[..., e:2 * e]), c[..., 2 * e:].astype(f32), g,
                2.0 * jax.nn.sigmoid(rest["beta"]))

    def decode_finish(self, params, x, y, rest, sow=None):
        """The rest of a layer after the recurrence: ``x`` [T, d] the
        residual stream, ``y`` [T, H D] float32 the states' read-out.
        The norm a head, the gate, the output projection, then the
        second half, each added to the stream in float32.  Sows
        :attr:`decode_stats` of this step."""
        f32 = jnp.float32
        p = _cast({nm: params[nm] for nm in
                   ("o_norm", "out_proj") + self._ffn_params}, x.dtype)
        o = rms_norm(y.reshape(y.shape[:-1] + (self.heads, -1)),
                     p["o_norm"]["scale"].astype(f32), self.rms_eps)
        o = o.reshape(y.shape) * jax.nn.sigmoid(rest["gate"])
        x32 = x.astype(f32) + jnp.dot(o.astype(x.dtype), p["out_proj"]["w"],
                                      preferred_element_type=f32)
        return self._second_half(p, x32, x.dtype, sow, x.shape[0])

    # -- full sequence ------------------------------------------------------

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], from an
        empty memory.  A dict ``sow`` is filled as :meth:`decode_finish`
        fills it, over all rows."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        fmt = self.memory_format(x.shape[-1], x.shape[1], x.dtype)
        y, _ = self.prefill(params, x, fmt.layer(fmt.zeros(x.shape[0], 1), 0),
                            fmt, sow=sow)
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # the projections (q, k, v, out; the two low-rank paths; beta),
        # the taps, the state's update and read-out, the second half
        (spec,) = in_specs
        t, d = spec.shape
        e, r = self.heads * self.head_dim, self.gate_rank
        return (2 * t * (d * 4 * e + 2 * r * (d + e) + d * self.heads)
                + 2 * t * 3 * e * self.d_conv + 6 * t * e * self.head_dim
                + self._ffn_flops(t, d))


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class SolarAttentionBlock(_ExpertHalf, DecoderBlock, Op):
    """A GQA layer: gated grouped-query attention without positions
    (the module docstring), then the routed experts."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    attn_impl: str = "auto"

    _front = ("ln1", "q", "k", "v")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        ks = jax.random.split(key, 13)
        return {"ln1": _ones(d),
                "q": _mat(ks[0], (d, qd), d), "k": _mat(ks[1], (d, kvd), d),
                "v": _mat(ks[2], (d, kvd), d),
                "gate": _mat(ks[3], (d, qd), d),
                "proj": _mat(ks[4], (qd, d), qd),
                **self._ffn_init(ks[5:13], d)}

    def _qkv(self, p, x):
        """Queries [..., t, nh, hd] and keys and values [..., t, kv, hd]
        of ``x`` [..., t, d]: as projected, no norm and no rotation."""
        y = rms_norm(x, p["ln1"]["scale"], self.rms_eps)

        def heads(a, n):
            return a.reshape(a.shape[:-1] + (n, self.head_dim))

        return (heads(y @ p["q"]["w"], self.num_heads),
                heads(y @ p["k"]["w"], self.num_kv_heads),
                heads(y @ p["v"]["w"], self.num_kv_heads))

    def _finish(self, p, x, y, sow=None):
        """The layer's output from the stream ``x`` [T, d] and the
        merged heads ``y`` [T, nh * hd]: the gate (of the normed stream,
        as the queries are), the output projection, the second half."""
        f32 = jnp.float32
        gate = jax.nn.sigmoid(jnp.dot(
            rms_norm(x, p["ln1"]["scale"], self.rms_eps), p["gate"]["w"],
            preferred_element_type=f32))
        x32 = x.astype(f32) + jnp.dot(
            (y.astype(f32) * gate).astype(x.dtype), p["proj"]["w"],
            preferred_element_type=f32)
        return self._second_half(p, x32, x.dtype, sow, 0)

    # -- full sequence ------------------------------------------------------

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]),
                               sow)[0]
        return y.reshape(lead + y.shape[-2:])

    def apply_with_kv(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d]; also the key and
        value columns [b, t, kv*hd] that :meth:`decode_qkv` would have
        handed over row by row."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x)
        y = self._attend(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)))
        out = self._finish(p, x.reshape(b * t, d),
                           y.transpose(0, 2, 1, 3).reshape(b * t, -1), sow)
        return out.reshape(b, t, d), k.reshape(b, t, -1), v.reshape(b, t, -1)

    # -- one token against the cache ------------------------------------------

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d]; ``pos``
        is not read."""
        del pos
        p = _cast({nm: params[nm] for nm in self._front}, x.dtype)
        b = x.shape[0]
        q, k, v = self._qkv(p, x)
        return q.reshape(b, -1), k.reshape(b, -1), v.reshape(b, -1)

    def decode_finish(self, params, x, y, sow=None):
        p = _cast({nm: params[nm] for nm in
                   ("ln1", "gate", "proj") + self._ffn_params}, x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        return (2 * t * d * (3 * qd + 2 * kvd) + 4 * t * t * qd
                + self._ffn_flops(t, d))


def solar_open2(num_layers: int, hidden: int, heads: int, kv_heads: int,
                head_dim: int, seq_len: int, vocab: int, gqa_layers,
                num_experts: int, experts_per_tok: int, expert_hidden: int,
                kda_heads: int | None = None, kda_head_dim: int | None = None,
                d_conv: int = 4, gate_rank: int = 128, chunk: int = 64,
                routed_scale: float = 1.0, experts_held=None,
                rms_eps: float = 1e-5,
                name: str = "solar_open2") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length and the most an attention layer may cache).  ``gqa_layers``
    lists the layers whose mixer is grouped-query attention (``heads``
    on ``kv_heads`` of ``head_dim``); every other layer's is KDA
    (``kda_heads`` of ``kda_head_dim``: ``linear_attn_config``'s, the
    attention's where None).  ``experts_held`` — the half-open range of
    routed experts every layer holds — stands for one chip's share of a
    layer under expert parallelism (None: all).  Untied head."""
    gqa_layers = frozenset(gqa_layers)
    if not gqa_layers <= set(range(num_layers)):
        raise ValueError(f"gqa_layers {sorted(gqa_layers)} name layers the "
                         f"model's {num_layers} do not have")
    if experts_held is not None:
        experts_held = tuple(experts_held)
    half = dict(num_experts=num_experts, experts_per_tok=experts_per_tok,
                expert_hidden=expert_hidden, routed_scale=routed_scale,
                experts_held=experts_held, rms_eps=rms_eps)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        if i in gqa_layers:
            op = SolarAttentionBlock(num_heads=heads, num_kv_heads=kv_heads,
                                     head_dim=head_dim, **half)
        else:
            op = SolarKdaBlock(heads=kda_heads or heads,
                               head_dim=kda_head_dim or head_dim,
                               d_conv=d_conv, gate_rank=gate_rank,
                               chunk=chunk, **half)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(CohereHead(vocab), x, name="lm_head")
    return b.build()


def solar_open2_tiny(seq_len: int = 32, vocab: int = 211,
                     experts_held=(0, 4)) -> LayerGraph:
    """Two periods of ``gqa kda kda kda``: 4 query heads on 2 KV heads
    of 16, 4 KDA heads of 16 under chunks of 8; 2 of 16 experts of 32 a
    token, 4 of the 16 held, one shared.  Two stages of four layers
    repeat one pattern of memory."""
    return solar_open2(8, 64, 4, 2, 16, seq_len, vocab, (0, 4), 16, 2, 32,
                       gate_rank=8, chunk=8, experts_held=experts_held,
                       name="solar_open2_tiny")
