"""NVIDIA's Nemotron-H decoder family as Nemotron-3-Super publishes it
(Hugging Face ``model_type`` ``nemotron_h``;
``NVIDIA-Nemotron-3-Super-120B-A12B``): a stack whose **layers are a
mixer or a feed-forward part alone** — ``hybrid_override_pattern`` names
each ``M`` (a Mamba-2 mixer), ``*`` (grouped-query attention) or ``E`` (a
mixture of experts) — every layer ``x + f(rms(x))`` with exactly one
``f``, one norm and one residual.  A block here is a published layer, so
the graph has three kinds of block and none has a second half:

* :class:`NemotronMambaBlock` (``M``), a
  :class:`~defer_tpu.models.decoder.StateSpaceBlock`: Mamba-2 (Dao & Gu,
  arXiv:2405.21060) with **``n_groups`` B/C groups** — the heads form
  groups of consecutive heads that share one ``B`` and one ``C``, the
  convolution runs over the channels and every group's ``B`` and ``C``
  (``E + 2 G N`` columns), and the gated RMSNorm before the output
  projection normalises **a group's channels at a time** (the gate
  first).  The recurrence and how its state lies on the device are
  ``ops/ssm.py``'s (its second shape, with groups).
* :class:`NemotronAttentionBlock` (``*``), a
  :class:`~defer_tpu.models.decoder.DecoderBlock`: grouped-query softmax
  attention, no bias, **no rotation and no position of any kind**,
  scores over ``sqrt(head_dim)``.
* :class:`NemotronExpertBlock` (``E``), a
  :class:`~defer_tpu.models.decoder.MemorylessBlock` — it keeps nothing
  a sequence: a **LatentMoE**.  The router scores the *stream* (sigmoid
  scores, the ``k`` largest of score + ``e_score_correction_bias``, the
  chosen scores over their sum times ``routed_scaling_factor``:
  ``ops/routed.py``'s ``noaux_tc``); the routed experts live in a
  **latent space** a quarter as wide: ``u = h W_down`` once a token,
  expert ``e`` is two matrices and no gate, ``E_e(u) = relu(u W1_e)^2
  W2_e``, and the weighted sum of the chosen experts is projected back
  up *once a token*, ``(sum_e w_e E_e(u)) W_up``; beside them one shared
  expert of the same form on the full stream.  **A layer may hold a
  share of its routed experts** (``experts_held``: one chip's under
  expert parallelism), as ``models/granite_hybrid.py``'s may: it routes
  over all of them, keeps the weights of the full choice and computes
  the pairs that fell to the experts it holds
  (``ops/routed.py::expert_dispatch_held``); the up-projection being
  linear, the shares' up-projected sums add up to the whole layer's.

All three sow one ledger: granite's four ``moe.*`` sums and
``ssm.updates``, and ``moe.latent_rows`` (the rows a step projected into
the latent space).  Untied embedding and head, a final RMSNorm.  The
graph follows the decoder-model contract (``embeddings`` / ``block_i`` /
``final_ln`` / ``lm_head``, models/decoder.py); layer ``i`` of the graph
is character ``i`` of ``layer_pattern``.

Layouts that differ from the published checkpoint's (all of layout,
none of arithmetic): ``conv/w`` is ``[d_conv, E + 2 G N]`` (taps lead);
an expert's matrices are stacked ``experts/up [experts, latent,
width]`` and ``experts/down [experts, width, latent]``.  Multi-token
prediction (``num_nextn_predict_layers``) is not here.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, RMSNorm, _cast, rms_norm
from ..obs.registry import REGISTRY
from ..ops import ssm
from ..ops.routed import held_range, routed_experts, shared_mlp
from .decoder import DecoderBlock, MemorylessBlock, StateSpaceBlock
from .olmoe import OlmoeEmbedding

MAMBA_LAYER, EXPERT_LAYER, ATTENTION_LAYER = "M", "E", "*"

#: what every kind of block sows: granite's five sums (an ``M`` layer
#: sows its sequences under ``ssm.updates`` and zeros elsewhere, an
#: ``E`` layer the four ``moe.*``) and the rows an ``E`` layer projected
#: into the latent space
_STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
          "moe.load_max", "ssm.updates", "moe.latent_rows")
#: the spread of a seeded ``e_score_correction_bias`` (a checkpoint's is
#: trained), as ``models/kimi_k2.py``'s: enough to turn choices at
#: near-ties, small beside what makes an expert popular
_BIAS_SPREAD = 0.001


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)


def _mat(key, shape, fan_in):
    return {"w": _normal(key, shape, fan_in)}


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _sow_zeros(sow, **some):
    """Fill ``sow`` with :data:`_STATS`, zero but for ``some``."""
    if sow is not None:
        for name in _STATS:
            sow[name] = jnp.int32(some.get(name, 0))


@dataclasses.dataclass(frozen=True, repr=False)
class NemotronMambaBlock(StateSpaceBlock, Op):
    """An ``M`` layer as a single graph node: the Mamba-2 mixer with B/C
    groups behind the layer's one norm, added to the stream — and
    nothing after it."""

    heads: int              #: ``mamba_num_heads``
    head_dim: int           #: ``mamba_head_dim``
    states: int             #: ``N``: ``ssm_state_size``
    bc_groups: int          #: ``G``: ``n_groups``
    d_conv: int
    chunk: int              #: ``chunk_size``
    rms_eps: float = 1e-5

    decode_stats = _STATS

    @property
    def channels(self) -> int:
        """``E``: the heads' channels side by side."""
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """The channels, every group's ``B`` and every group's ``C``."""
        return self.channels + 2 * self.bc_groups * self.states

    @property
    def mixer_width(self) -> int:
        """The input projection's ``[z, x B C, dt]``."""
        return self.channels + self.conv_width + self.heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        e, k, nh, w = self.channels, self.d_conv, self.heads, self.conv_width
        ks = jax.random.split(key, 6)
        # Mamba-2's published initialisation (granite_hybrid.py's): A
        # uniform in [1, 16] a head, the step's bias the inverse
        # softplus of a step drawn log-uniformly in [1e-3, 1e-1]
        step = jnp.exp(jax.random.uniform(ks[4], (nh,), jnp.float32)
                       * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        bound = 1.0 / math.sqrt(k)
        return {
            "ln": _ones(d),
            "in_proj": _mat(ks[0], (d, self.mixer_width), d),
            "conv": {"w": jax.random.uniform(ks[1], (k, w), jnp.float32,
                                             -bound, bound),
                     "b": jax.random.uniform(ks[2], (w,), jnp.float32,
                                             -bound, bound)},
            "ssm": {"a_log": jnp.log(jax.random.uniform(
                ks[3], (nh,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "d": jnp.ones((nh,), jnp.float32)},
            "gate_norm": _ones(e),
            "out_proj": _mat(ks[5], (e, d), e),
        }

    # -- the mixer's pieces, around the state's format -----------------------

    def mixer_inputs(self, params, x):
        """The convolution's input ``u`` [..., E + 2 G N] of the stream
        ``x`` [..., d], and ``(z [..., E], r [..., heads])``: the gate
        and the step before its bias and softplus."""
        p = _cast({nm: params[nm] for nm in ("ln", "in_proj")}, x.dtype)
        zur = rms_norm(x, p["ln"]["scale"], self.rms_eps) @ p["in_proj"]["w"]
        e, w = self.channels, self.conv_width
        return zur[..., e:e + w], (zur[..., :e], zur[..., e + w:])

    def mixer_conv(self, params, taps):
        return ssm.causal_conv(taps, params["conv"]["w"],
                               params["conv"]["b"])

    def mixer_selection(self, params, c, rest):
        """The step ``dt`` [..., heads] (float32), the channels, ``B``
        and ``C`` [..., G N] (a group after the other, as the
        convolution's output holds them) and ``A`` [heads]."""
        f32, p = jnp.float32, params["ssm"]
        e, gn = self.channels, self.bc_groups * self.states
        dt = jax.nn.softplus(rest[1].astype(f32) + p["dt_bias"].astype(f32))
        return (dt, c[..., :e], c[..., e:e + gn], c[..., e + gn:],
                -jnp.exp(p["a_log"].astype(f32)))

    def decode_finish(self, params, x, y, xs, rest, sow=None):
        """The rest of the layer after the recurrence: ``x`` [T, d] the
        residual stream, ``y`` [T, E] float32 the state read by ``C``,
        ``xs`` [T, E] the channels the recurrence was fed, ``rest`` the
        gate and the raw step.  The skip term a head, **the gate, then
        the norm a group** (each group's ``E / G`` channels their own
        statistics, one weight of ``E``), the output projection, added
        to the stream in float32: the layer ends here.  Sows
        :attr:`decode_stats`: its rows under ``ssm.updates``."""
        f32 = jnp.float32
        w = _cast(params["out_proj"], x.dtype)["w"]
        skip = jnp.repeat(params["ssm"]["d"].astype(f32), self.head_dim)
        g = (y + skip * xs.astype(f32)) * jax.nn.silu(rest[0].astype(f32))
        t, e = g.shape
        g = rms_norm(g.reshape(t, self.bc_groups, e // self.bc_groups),
                     jnp.ones((), f32), self.rms_eps).reshape(t, e) \
            * params["gate_norm"]["scale"].astype(f32)
        _sow_zeros(sow, **{"ssm.updates": t})
        return (x.astype(f32) + jnp.dot(
            g.astype(x.dtype), w, preferred_element_type=f32)).astype(x.dtype)

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], the
        recurrence from an empty memory (``ops/ssm.py``)."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        fmt = self.memory_format(x.shape[-1], x.shape[1], x.dtype)
        y, _ = self.prefill(params, x, fmt.layer(fmt.zeros(x.shape[0], 1), 0),
                            fmt, sow=sow)
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # the mixer's two matrices and the recurrence (an update and a
        # read of E x N values a token)
        (spec,) = in_specs
        t, d = spec.shape
        e = self.channels
        return 2 * t * d * (self.mixer_width + e) + 6 * t * e * self.states


@dataclasses.dataclass(frozen=True, repr=False)
class NemotronAttentionBlock(DecoderBlock, Op):
    """A ``*`` layer as a single graph node: grouped-query softmax
    attention without bias, QK-norm or any position behind the layer's
    one norm, added to the stream — and nothing after it."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rms_eps: float = 1e-5
    attn_impl: str = "auto"

    decode_stats = _STATS

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        ks = jax.random.split(key, 4)
        return {"ln": _ones(d),
                "q": _mat(ks[0], (d, qd), d), "k": _mat(ks[1], (d, kvd), d),
                "v": _mat(ks[2], (d, kvd), d),
                "proj": _mat(ks[3], (qd, d), qd)}

    def _qkv(self, p, x):
        """Query, key and value columns of ``x`` [..., d]; nothing
        depends on the position."""
        h = rms_norm(x, p["ln"]["scale"], self.rms_eps)
        return h @ p["q"]["w"], h @ p["k"]["w"], h @ p["v"]["w"]

    def _finish(self, p, x, y, sow=None):
        _sow_zeros(sow)
        return (x.astype(jnp.float32) + jnp.dot(
            y, p["proj"]["w"], preferred_element_type=jnp.float32)
                ).astype(x.dtype)

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]),
                               sow)[0]
        return y.reshape(lead + y.shape[-2:])

    def apply_with_kv(self, params, x, sow=None):
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x)

        def heads(a, n):
            return a.reshape(b, t, n, self.head_dim).transpose(0, 2, 1, 3)

        y = self._attend(heads(q, self.num_heads),
                         heads(k, self.num_kv_heads),
                         heads(v, self.num_kv_heads))
        out = self._finish(p, x.reshape(b * t, d),
                           y.transpose(0, 2, 1, 3).reshape(b * t, -1), sow)
        return out.reshape(b, t, d), k, v

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d]; the
        position is not read."""
        del pos
        return self._qkv(_cast({nm: params[nm] for nm in
                                ("ln", "q", "k", "v")}, x.dtype), x)

    def decode_finish(self, params, x, y, sow=None):
        return self._finish(_cast({"proj": params["proj"]}, x.dtype), x, y,
                            sow)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        return 2 * t * d * (2 * qd + 2 * kvd) + 4 * t * t * qd


@dataclasses.dataclass(frozen=True, repr=False)
class NemotronExpertBlock(MemorylessBlock, Op):
    """An ``E`` layer as a single graph node: the LatentMoE behind the
    layer's one norm, added to the stream.  It keeps no memory: the
    ring holds no buffer for it."""

    num_experts: int        #: ``n_routed_experts``: the router's columns
    experts_per_tok: int
    latent: int             #: ``moe_latent_size``: the experts' rows' width
    expert_hidden: int      #: ``moe_intermediate_size``
    shared_hidden: int      #: ``moe_shared_expert_intermediate_size``
    routed_scale: float = 1.0   #: ``routed_scaling_factor``
    experts_held: tuple | None = None
    activation: str = "relu2"   #: ``mlp_hidden_act``
    rms_eps: float = 1e-5

    decode_stats = _STATS

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    @property
    def mixer_width(self) -> int:
        """The widest activation a token has here: the shared expert's."""
        return max(self.shared_hidden, self.expert_hidden)

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        r, h, sh = self.latent, self.expert_hidden, self.shared_hidden
        e = self.held[1] - self.held[0]
        ks = jax.random.split(key, 8)
        return {
            "ln": _ones(d),
            # every expert's column and bias, held or not: the choice is
            # the whole layer's
            "router": {"w": _normal(ks[0], (d, self.num_experts), d),
                       "bias": jax.random.normal(
                           ks[1], (self.num_experts,), jnp.float32)
                       * _BIAS_SPREAD},
            "latent_down": _mat(ks[2], (d, r), d),
            "experts": {"up": _normal(ks[3], (e, r, h), r),
                        "down": _normal(ks[4], (e, h, r), h)},
            "latent_up": _mat(ks[5], (r, d), r),
            "shared_up": _mat(ks[6], (d, sh), d),
            "shared_down": _mat(ks[7], (sh, d), sh)}

    def latent_sum(self, p, h, sow=None):
        """``(the held pairs' weighted sum [T, latent] float32, before
        the up-projection, u [T, latent])`` of the normed stream ``h``
        [T, d] in the type of ``p``: the router on ``h``, the experts on
        ``u = h W_down``."""
        with jax.named_scope("latent_down"):
            u = h @ p["latent_down"]["w"]
        # set where a program is traced, as the formats' own gauges are
        REGISTRY.gauge("decode.moe.latent_width").set(u.shape[-1])
        with jax.named_scope("latent_experts"):
            routed, _ = routed_experts(
                h, p["router"], p["experts"], k=self.experts_per_tok,
                scoring="noaux_tc", num_experts=self.num_experts,
                held=self.held, scale=self.routed_scale, rows=u,
                activation=self.activation, sow=sow)
        if sow is not None:
            # beside the choice, for a check: no statistic
            sow["moe.latent_sum"] = routed
        return routed, u

    def branch(self, p, h, sow=None):
        """``f(h)`` [T, d] float32 of the normed stream ``h`` [T, d]:
        the held pairs' sum projected up once a token, and the shared
        expert on ``h``."""
        f32 = jnp.float32
        routed, _ = self.latent_sum(p, h, sow)
        with jax.named_scope("latent_up"):
            up = jnp.dot(routed.astype(h.dtype), p["latent_up"]["w"],
                         preferred_element_type=f32)
        with jax.named_scope("shared_expert"):
            shared = shared_mlp(h, p["shared_up"]["w"], p["shared_down"]["w"],
                                self.activation)
        return up + shared

    def feed_forward(self, params, x, sow=None):
        """The whole layer on ``x`` [T, d]: ``x + f(rms(x))``, added in
        float32.  Sows :attr:`decode_stats`: the four ``moe.*`` and the
        rows projected into the latent space; with a dict ``sow`` also
        the choice (``moe.chosen`` / ``moe.weights``) and the held
        pairs' sum before the up-projection (``moe.latent_sum``)."""
        p = _cast(params, x.dtype)
        h = rms_norm(x, p["ln"]["scale"], self.rms_eps)
        sown = {} if sow is not None else None
        out = self.branch(p, h, sown)
        if sow is not None:
            _sow_zeros(sow, **{"moe.latent_rows": x.shape[0]})
            sow.update(sown)
        return (x.astype(jnp.float32) + out).astype(x.dtype)

    def flops(self, in_specs, out_spec):
        # the router, the two latent projections, experts_per_tok routed
        # experts a token (the whole layer's: a share holds fewer) and
        # the shared one
        (spec,) = in_specs
        t, d = spec.shape
        r = self.latent
        return 2 * t * (d * self.num_experts + 2 * d * r
                        + 2 * r * self.experts_per_tok * self.expert_hidden
                        + 2 * d * self.shared_hidden)


def nemotron_h(hidden: int, heads: int, kv_heads: int, head_dim: int,
               seq_len: int, vocab: int, layer_pattern: str,
               mamba_heads: int, mamba_head_dim: int, mamba_d_state: int,
               mamba_groups: int, num_experts: int, experts_per_tok: int,
               latent: int, expert_hidden: int, shared_hidden: int,
               routed_scale: float = 1.0, mamba_d_conv: int = 4,
               mamba_chunk: int = 128, experts_held=None,
               rms_eps: float = 1e-5,
               name: str = "nemotron_h") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length and the most the attention layers may cache).
    ``layer_pattern`` names each layer, a character a layer: ``M`` a
    Mamba-2 mixer, ``E`` a LatentMoE, ``*`` attention — the published
    ``hybrid_override_pattern`` or a slice of it.  ``experts_held``
    ``(lo, hi)`` makes every ``E`` layer one chip's share of its routed
    experts.  Untied head."""
    for kind in layer_pattern:
        if kind not in (MAMBA_LAYER, EXPERT_LAYER, ATTENTION_LAYER):
            raise ValueError(
                f"layer kind {kind!r} of pattern {layer_pattern!r} is none "
                f"of {MAMBA_LAYER!r}, {EXPERT_LAYER!r}, {ATTENTION_LAYER!r}")
    if experts_held is not None:
        experts_held = tuple(experts_held)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i, kind in enumerate(layer_pattern):
        if kind == MAMBA_LAYER:
            op = NemotronMambaBlock(mamba_heads, mamba_head_dim,
                                    mamba_d_state, mamba_groups,
                                    mamba_d_conv, mamba_chunk, rms_eps)
        elif kind == ATTENTION_LAYER:
            op = NemotronAttentionBlock(heads, kv_heads, head_dim, rms_eps)
        else:
            op = NemotronExpertBlock(
                num_experts, experts_per_tok, latent, expert_hidden,
                shared_hidden, routed_scale, experts_held, rms_eps=rms_eps)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def nemotron_h_tiny(seq_len: int = 32, vocab: int = 211,
                    experts_held=None,
                    layer_pattern: str = "*EMEME" * 2) -> LayerGraph:
    """Two periods of ``*EMEME``; 4 query heads on 2 KV heads of 16;
    Mamba-2 of 8 heads x 32 in 2 B/C groups (128 channels a group) with
    16 states and a chunk of 8; 3 of 8 relu² experts of 48 in a latent
    space of 32, a shared one of 96, routed scale 2.5."""
    return nemotron_h(
        64, 4, 2, 16, seq_len, vocab, layer_pattern, 8, 32, 16, 2,
        8, 3, 32, 48, 96, routed_scale=2.5, mamba_chunk=8,
        experts_held=experts_held, name="nemotron_h_tiny")
