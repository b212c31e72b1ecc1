"""IBM's Granite 4.0-H decoder family (Hugging Face ``model_type``
``granitemoehybrid``; ``granite-4.0-h-small``): a stack in which most
layers mix the sequence with a **Mamba-2** state-space mixer (Dao & Gu,
arXiv:2405.21060: heads of channels under one scalar decay a head, the
step straight out of the input projection, one convolution over the
channels, ``B`` and ``C`` together, a gated RMSNorm before the output
projection) and one layer a period with grouped-query softmax attention
that carries **no positional signal at all**; every layer's second half
is a mixture of small routed SwiGLU experts — the ``k`` largest router
logits, then a softmax over those ``k`` — beside one shared expert every
token passes.  RMSNorm before each half; the head tied to the embedding.
Four scalar multipliers: the embedding's output times
``embedding_multiplier``, every branch times ``residual_multiplier``
before it is added, attention scores times ``attention_multiplier`` (not
``1 / sqrt(head_dim)``), the logits over ``logits_scaling``.  The
mixer's equations and how its state lies on the device are
``ops/ssm.py``'s (its second shape).

Two kinds of per-sequence memory lie side by side in one graph:
:class:`GraniteMambaBlock` is a
:class:`~defer_tpu.models.decoder.StateSpaceBlock`,
:class:`GraniteAttentionBlock` a
:class:`~defer_tpu.models.decoder.DecoderBlock`.  **A layer may hold a
share of its routed experts** (``experts_held``: one chip's under expert
parallelism), as ``models/cohere_moe.py``'s may: it routes over all of
them, keeps the weights of the full choice, computes the pairs that fell
to the experts it holds (``ops/routed.py::expert_dispatch_held``) and
adds the shared expert whole.  Both kinds of block sow one ledger: the
four ``moe.*`` sums and ``ssm.updates``.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py).

Layouts that differ from the published checkpoint's (all of layout,
none of arithmetic): ``conv/w`` is ``[d_conv, E + 2 N]`` (taps lead); an
expert's fused ``[d, 2 x width]`` input matrix is two, ``experts/gate``
(its first half, the one through the ``silu``) and ``experts/up``, each
``[experts, d, width]``, and the shared expert's likewise; the attention
block multiplies its *queries* by ``attention_multiplier x
sqrt(head_dim)`` (in float32, rounded once), so that the kernels' own
``1 / sqrt(head_dim)`` leaves the published scale.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import RMSNorm, _cast, rms_norm
from ..ops import ssm
from ..ops.routed import held_range, routed_experts
from .cohere_moe import CohereHead
from .decoder import DecoderBlock, StateSpaceBlock
from .olmoe import OlmoeEmbedding

MAMBA_LAYER, ATTENTION_LAYER = "mamba", "attention"

#: what both kinds of block sow: command-a-plus's four sums of the
#: routed half, and the sequences whose state-space state a step really
#: updated (an attention block sows 0, a bubble sows 0)
_STATS = ("moe.assignments", "moe.held_assignments", "moe.experts_hit",
          "moe.load_max", "ssm.updates")
_EXPERT_KEYS = ("ln2", "router", "experts", "shared_gate", "shared_up",
                "shared_down")


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _mat(key, shape, scale):
    return {"w": _normal(key, shape, scale)}


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


class _ExpertHalf:
    """The second half of either kind of layer: the routed experts this
    layer holds and the shared expert, behind the second norm.  A block
    that mixes it in names ``num_experts``, ``experts_per_tok``,
    ``expert_hidden``, ``shared_hidden``, ``experts_held``,
    ``residual_multiplier`` and ``rms_eps``."""

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    def _experts_init(self, keys, d: int) -> dict:
        h, sh = self.expert_hidden, self.shared_hidden
        e = self.held[1] - self.held[0]
        s = 1.0 / math.sqrt(d)
        return {
            "ln2": _ones(d),
            # every expert's column, held or not: the choice is the
            # whole layer's
            "router": _mat(keys[0], (d, self.num_experts), s),
            "experts": {"gate": _normal(keys[1], (e, d, h), s),
                        "up": _normal(keys[2], (e, d, h), s),
                        "down": _normal(keys[3], (e, h, d),
                                        1.0 / math.sqrt(h))},
            "shared_gate": _mat(keys[4], (d, sh), s),
            "shared_up": _mat(keys[5], (d, sh), s),
            "shared_down": _mat(keys[6], (sh, d), 1.0 / math.sqrt(sh))}

    def expert_half(self, p, x32, dtype, sow=None, updates=0):
        """``x32 + residual_multiplier * (routed + shared)`` of the
        float32 stream ``x32`` [T, d], rounded to ``dtype`` once, on
        the way out; ``p`` the layer's parameters in ``dtype``.  Fills
        ``sow`` with :data:`_STATS` of this step, ``ssm.updates`` being
        ``updates``."""
        h = rms_norm(x32, p["ln2"]["scale"], self.rms_eps).astype(dtype)
        routed, shared = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring="softmax_of_chosen", num_experts=self.num_experts,
            held=self.held,
            shared=(p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"]), sow=sow)
        if sow is not None:
            sow["ssm.updates"] = jnp.int32(updates)
        return (x32 + self.residual_multiplier * (routed + shared)
                ).astype(dtype)

    def _experts_flops(self, t: int, d: int) -> int:
        # the router, experts_per_tok routed experts a token (the whole
        # layer's: a share holds fewer) and the shared one
        return (2 * t * d * self.num_experts
                + 2 * t * 3 * d * (self.experts_per_tok * self.expert_hidden
                                   + self.shared_hidden))


@dataclasses.dataclass(frozen=True, repr=False)
class GraniteMambaBlock(_ExpertHalf, StateSpaceBlock, Op):
    """One state-space layer as a single graph node: the Mamba-2 mixer,
    then the experts, each behind a residual."""

    heads: int              #: ``mamba_n_heads``
    head_dim: int           #: ``mamba_d_head``
    states: int             #: ``N``: ``mamba_d_state``
    d_conv: int
    chunk: int              #: ``mamba_chunk_size``
    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    shared_hidden: int
    experts_held: tuple | None = None
    residual_multiplier: float = 1.0
    rms_eps: float = 1e-5

    decode_stats = _STATS

    @property
    def channels(self) -> int:
        """``E``: the heads' channels side by side."""
        return self.heads * self.head_dim

    @property
    def mixer_width(self) -> int:
        """The input projection's ``[z, x B C, dt]``."""
        return 2 * self.channels + 2 * self.states + self.heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        e, n, k, nh = self.channels, self.states, self.d_conv, self.heads
        w = e + 2 * n
        ks = jax.random.split(key, 13)
        # Mamba-2's published initialisation: A uniform in [1, 16] a
        # head, and the step's bias the inverse softplus of a step
        # drawn log-uniformly in [1e-3, 1e-1], so that exp(dt A) leaves
        # a state a memory of tens to hundreds of positions
        step = jnp.exp(jax.random.uniform(ks[4], (nh,), jnp.float32)
                       * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        bound = 1.0 / math.sqrt(k)
        return {
            "ln1": _ones(d),
            "in_proj": _mat(ks[0], (d, self.mixer_width), 1.0 / math.sqrt(d)),
            "conv": {"w": jax.random.uniform(ks[1], (k, w), jnp.float32,
                                             -bound, bound),
                     "b": jax.random.uniform(ks[2], (w,), jnp.float32,
                                             -bound, bound)},
            "ssm": {"a_log": jnp.log(jax.random.uniform(
                ks[3], (nh,), jnp.float32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "d": jnp.ones((nh,), jnp.float32)},
            "gate_norm": _ones(e),
            "out_proj": _mat(ks[5], (e, d), 1.0 / math.sqrt(e)),
            **self._experts_init(ks[6:13], d),
        }

    # -- the mixer's pieces, around the state's format -----------------------

    def mixer_inputs(self, params, x):
        """The convolution's input ``u`` [..., E + 2 N] of the stream
        ``x`` [..., d], and ``(z [..., E], r [..., heads])``: the gate
        and the step before its bias and softplus."""
        p = _cast({nm: params[nm] for nm in ("ln1", "in_proj")}, x.dtype)
        zur = rms_norm(x, p["ln1"]["scale"], self.rms_eps) @ p["in_proj"]["w"]
        e, w = self.channels, self.channels + 2 * self.states
        return zur[..., e:e + w], (zur[..., :e], zur[..., e + w:])

    def mixer_conv(self, params, taps):
        return ssm.causal_conv(taps, params["conv"]["w"],
                               params["conv"]["b"])

    def mixer_selection(self, params, c, rest):
        """The step ``dt`` [..., heads] (float32: the bias and the
        softplus run in it), the channels, ``B`` and ``C`` as the
        convolution's output ``c`` [..., E + 2 N] holds them side by
        side, and ``A`` [heads]."""
        f32, p = jnp.float32, params["ssm"]
        e, n = self.channels, self.states
        dt = jax.nn.softplus(rest[1].astype(f32) + p["dt_bias"].astype(f32))
        return (dt, c[..., :e], c[..., e:e + n], c[..., e + n:],
                -jnp.exp(p["a_log"].astype(f32)))

    def decode_finish(self, params, x, y, xs, rest, sow=None):
        """The rest of a layer after the recurrence: ``x`` [T, d] the
        residual stream, ``y`` [T, E] float32 the state read by ``C``,
        ``xs`` [T, E] the channels the recurrence was fed, ``rest`` the
        gate and the raw step.  The skip term a head, **the gate, then
        the norm** over all channels, the output projection, then the
        experts, each added to the stream in float32 under
        ``residual_multiplier``.  Sows :attr:`decode_stats` of this
        step."""
        f32 = jnp.float32
        p = _cast({nm: params[nm] for nm in ("out_proj",) + _EXPERT_KEYS},
                  x.dtype)
        skip = jnp.repeat(params["ssm"]["d"].astype(f32), self.head_dim)
        g = rms_norm((y + skip * xs.astype(f32))
                     * jax.nn.silu(rest[0].astype(f32)),
                     params["gate_norm"]["scale"], self.rms_eps)
        x32 = x.astype(f32) + self.residual_multiplier * jnp.dot(
            g.astype(x.dtype), p["out_proj"]["w"], preferred_element_type=f32)
        return self.expert_half(p, x32, x.dtype, sow, x.shape[0])

    # -- full sequence ----------------------------------------------------

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], the
        recurrence from an empty memory (``ops/ssm.py``).  A dict
        ``sow`` is filled as :meth:`decode_finish` fills it, over all
        rows."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        fmt = self.memory_format(x.shape[-1], x.shape[1], x.dtype)
        y, _ = self.prefill(params, x, fmt.layer(fmt.zeros(x.shape[0], 1), 0),
                            fmt, sow=sow)
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # the mixer's two matrices, the recurrence (an update and a
        # read of E x N values a token), the experts
        (spec,) = in_specs
        t, d = spec.shape
        e = self.channels
        return (2 * t * d * (self.mixer_width + e)
                + 6 * t * e * self.states + self._experts_flops(t, d))


@dataclasses.dataclass(frozen=True, repr=False)
class GraniteAttentionBlock(_ExpertHalf, DecoderBlock, Op):
    """One attention layer as a single graph node: grouped-query
    softmax attention without bias, QK-norm or any position, its scores
    scaled by ``attention_multiplier``, then the experts, each behind a
    residual."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    attention_multiplier: float
    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    shared_hidden: int
    experts_held: tuple | None = None
    residual_multiplier: float = 1.0
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
        ks = jax.random.split(key, 11)
        s = 1.0 / math.sqrt(d)
        return {"ln1": _ones(d),
                "q": _mat(ks[0], (d, qd), s), "k": _mat(ks[1], (d, kvd), s),
                "v": _mat(ks[2], (d, kvd), s),
                "proj": _mat(ks[3], (qd, d), 1.0 / math.sqrt(qd)),
                **self._experts_init(ks[4:11], d)}

    def _qkv(self, p, x):
        """Query, key and value columns of ``x`` [..., d]: [..., nh*hd]
        and [..., kv*hd] twice.  Nothing depends on the position.  The
        queries carry ``attention_multiplier x sqrt(head_dim)``, so
        that a kernel's ``1 / sqrt(head_dim)`` leaves the family's
        scale."""
        h = rms_norm(x, p["ln1"]["scale"], self.rms_eps)
        q = jnp.dot(h, p["q"]["w"], preferred_element_type=jnp.float32) \
            * (self.attention_multiplier * math.sqrt(self.head_dim))
        return q.astype(x.dtype), h @ p["k"]["w"], h @ p["v"]["w"]

    def _finish(self, p, x, y, sow=None):
        x32 = x.astype(jnp.float32) + self.residual_multiplier * jnp.dot(
            y, p["proj"]["w"], preferred_element_type=jnp.float32)
        return self.expert_half(p, x32, x.dtype, sow, 0)

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
                                ("ln1", "q", "k", "v")}, x.dtype), x)

    def decode_finish(self, params, x, y, sow=None):
        p = _cast({nm: params[nm] for nm in ("proj",) + _EXPERT_KEYS},
                  x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        return (2 * t * d * (2 * qd + 2 * kvd) + 4 * t * t * qd
                + self._experts_flops(t, d))


class GraniteEmbedding(OlmoeEmbedding):
    """The token embedding times ``embedding_multiplier`` (in the
    table's own type)."""

    def __init__(self, vocab: int, features: int, max_len: int,
                 multiplier: float):
        super().__init__(vocab, features, max_len)
        self.multiplier = multiplier

    def apply(self, params, ids):
        return super().apply(params, ids) * self.multiplier

    def embed_at(self, params, ids, pos):
        return super().embed_at(params, ids, pos) * self.multiplier


def granite_hybrid(num_layers: int, hidden: int, heads: int, kv_heads: int,
                   head_dim: int, seq_len: int, vocab: int, layer_types,
                   mamba_heads: int, mamba_head_dim: int, mamba_d_state: int,
                   num_experts: int, experts_per_tok: int,
                   expert_hidden: int, shared_hidden: int,
                   mamba_d_conv: int = 4, mamba_chunk: int = 256,
                   experts_held=None, embedding_multiplier: float = 1.0,
                   residual_multiplier: float = 1.0,
                   attention_multiplier: float | None = None,
                   logits_scaling: float = 1.0, rms_eps: float = 1e-5,
                   name: str = "granite_hybrid") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length and the most the attention layers may cache).
    ``layer_types`` names each layer ``"mamba"`` or ``"attention"``; a
    shorter list is the pattern's period and repeats.  ``experts_held``
    ``(lo, hi)`` makes every layer one chip's share of its routed
    experts.  ``attention_multiplier`` None is ``1 / sqrt(head_dim)``.
    Initialise with ``cohere_moe.tie_head(graph.init(key))``: the head
    is the embedding's table."""
    layer_types = list(layer_types)
    for kind in layer_types:
        if kind not in (MAMBA_LAYER, ATTENTION_LAYER):
            raise ValueError(f"layer type {kind!r} is neither "
                             f"{MAMBA_LAYER!r} nor {ATTENTION_LAYER!r}")
    if experts_held is not None:
        experts_held = tuple(experts_held)
    if attention_multiplier is None:
        attention_multiplier = 1.0 / math.sqrt(head_dim)
    moe = dict(num_experts=num_experts, experts_per_tok=experts_per_tok,
               expert_hidden=expert_hidden, shared_hidden=shared_hidden,
               experts_held=experts_held,
               residual_multiplier=residual_multiplier, rms_eps=rms_eps)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(GraniteEmbedding(vocab, hidden, seq_len, embedding_multiplier),
              x, name="embeddings")
    for i in range(num_layers):
        if layer_types[i % len(layer_types)] == ATTENTION_LAYER:
            op = GraniteAttentionBlock(heads, kv_heads, head_dim,
                                       attention_multiplier, **moe)
        else:
            op = GraniteMambaBlock(mamba_heads, mamba_head_dim,
                                   mamba_d_state, mamba_d_conv, mamba_chunk,
                                   **moe)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(CohereHead(vocab, 1.0 / logits_scaling), x, name="lm_head")
    return b.build()


def granite_hybrid_tiny(seq_len: int = 32, vocab: int = 211,
                        experts_held=None) -> LayerGraph:
    """Two periods of four layers with attention at offset 2; 4 query
    heads on 2 KV heads of 16; Mamba-2 of 8 heads x 16 with 16 states
    and a chunk of 8; 3 of 8 experts of 32 a token beside a shared one
    of 64; no multiplier is 1."""
    return granite_hybrid(
        8, 64, 4, 2, 16, seq_len, vocab,
        (MAMBA_LAYER,) * 2 + (ATTENTION_LAYER, MAMBA_LAYER), 8, 16, 16,
        8, 3, 32, 64, mamba_chunk=8, experts_held=experts_held,
        embedding_multiplier=6.0, residual_multiplier=0.35,
        attention_multiplier=0.1, logits_scaling=4.0,
        name="granite_hybrid_tiny")
