"""OLMoE decoder family (Muennighoff et al. 2024, arXiv:2409.02060; Hugging
Face ``model_type`` ``olmoe``): RMSNorm, bias-free projections, RMSNorm
on the whole query and key projections before the head split (QK-norm),
rotate-half RoPE, and in place of the MLP a routed layer of SwiGLU
experts — softmax over all experts, the ``experts_per_tok`` largest, their
probabilities used as they are.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py) and
:class:`OlmoeBlock` meets its block interface
(:class:`~defer_tpu.models.decoder.DecoderBlock`), so the full-sequence graph
rides ``SpmdPipeline`` and generation rides ``PipelinedDecoder`` like the
GPT family's.  Keys are rotated *before* they are cached: a cache row is
final when it is written.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, RMSNorm, _cast, rms_norm
from ..ops.routed import routed_experts
from .decoder import DecoderBlock


def rope(x, pos, theta: float, inv_freq=None, scale: float = 1.0):
    """Rotate-half RoPE over the whole head: ``x`` [..., t, nh, hd] at
    positions ``pos`` [t] (angles in float32), pair ``(j, j + hd / 2)``
    turned by ``pos * theta ** (-2j / hd)`` — or by ``pos *
    inv_freq[j]`` where a family scales its frequencies (``inv_freq``
    [hd / 2]; ``theta`` is then not read).  ``scale`` multiplies both
    ``cos`` and ``sin`` (YaRN's attention factor: a rotated key carries
    it into the cache)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)) \
        if inv_freq is None else jnp.asarray(inv_freq, jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [t, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : hd // 2], xf[..., hd // 2:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rot * sin).astype(x.dtype)


@dataclasses.dataclass(frozen=True, repr=False)
class OlmoeBlock(DecoderBlock, Op):
    """One OLMoE layer as a single graph node: causal attention with
    QK-norm and RoPE, then the routed experts, each behind a residual."""

    num_heads: int
    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    attn_impl: str = "auto"

    decode_stats = ("moe.assignments", "moe.experts_hit", "moe.load_max")
    #: the router's rule (``ops/routed.py::route_top_k``), and the leaves
    #: :meth:`_qkv` reads: what a family on this block's routed tail
    #: (``models/mellum.py``) says of itself instead of copying the tail
    scoring = "softmax"
    qkv_leaves = ("ln1", "q", "q_norm", "k", "k_norm", "v")

    @property
    def kv_heads(self) -> int:
        return self.num_heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        e, h = self.num_experts, self.expert_hidden
        ks = jax.random.split(key, 8)
        s = 1.0 / math.sqrt(d)

        def mat(k, shape, scale):
            return jax.random.normal(k, shape, jnp.float32) * scale

        def ones():
            return {"scale": jnp.ones((d,), jnp.float32)}

        return {
            "ln1": ones(),
            "q": {"w": mat(ks[0], (d, d), s)}, "q_norm": ones(),
            "k": {"w": mat(ks[1], (d, d), s)}, "k_norm": ones(),
            "v": {"w": mat(ks[2], (d, d), s)},
            "proj": {"w": mat(ks[3], (d, d), s)},
            "ln2": ones(),
            "router": {"w": mat(ks[4], (d, e), s)},
            "experts": {"gate": mat(ks[5], (e, d, h), s),
                        "up": mat(ks[6], (e, d, h), s),
                        "down": mat(ks[7], (e, h, d), 1.0 / math.sqrt(h))},
        }

    # -- the two halves of a layer ----------------------------------------

    def _qkv(self, p, x, pos):
        """Normed, rotated queries and keys and the values of ``x``
        [..., t, d] at positions ``pos`` [t], each [..., t, nh, hd]."""
        nh = self.num_heads
        y = rms_norm(x, p["ln1"]["scale"], self.rms_eps)
        q = rms_norm(y @ p["q"]["w"], p["q_norm"]["scale"], self.rms_eps)
        k = rms_norm(y @ p["k"]["w"], p["k_norm"]["scale"], self.rms_eps)
        v = y @ p["v"]["w"]
        heads = x.shape[:-1] + (nh, x.shape[-1] // nh)
        return (rope(q.reshape(heads), pos, self.rope_theta),
                rope(k.reshape(heads), pos, self.rope_theta),
                v.reshape(heads))

    def _finish(self, p, x, y, sow=None):
        """The rest of a layer after attention: ``x`` [T, d] the residual
        stream, ``y`` [T, heads * hd] the attention's heads merged.  Output
        projection, then the routed experts, each added to the stream in
        float32; the stream is rounded to its own type once, on the way
        out (rounded after each add, bfloat16 moved near-tied logits
        half again as far: PERF.md, PR 26)."""
        f32 = jnp.float32
        x32 = x.astype(f32) + jnp.dot(y, p["proj"]["w"],
                                      preferred_element_type=f32)
        h = rms_norm(x32, p["ln2"]["scale"], self.rms_eps).astype(x.dtype)
        out, _ = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring=self.scoring, num_experts=self.num_experts, sow=sow)
        return (x32 + out).astype(x.dtype)

    # -- full sequence ----------------------------------------------------

    def apply(self, params, x):
        return self.apply_with_kv(params, x)[0]

    def apply_with_kv(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d]; also returns the key
        (normed and rotated) and value columns [b, t, kv*hd] that
        :meth:`decode_qkv` would have handed over row by row.  A dict
        ``sow`` is filled as :meth:`decode_finish` fills it, over all
        b*t rows, and with their chosen experts under ``moe.chosen``."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x, jnp.arange(t))
        y = self._attend(*(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                         window=self.window)
        x = self._finish(p, x.reshape(b * t, d),
                         y.transpose(0, 2, 1, 3).reshape(b * t, -1), sow)
        return x.reshape(b, t, d), k.reshape(b, t, -1), v.reshape(b, t, -1)

    # -- one token against the cache --------------------------------------

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d] at scalar
        ``pos``."""
        p = _cast({nm: params[nm] for nm in self.qkv_leaves}, x.dtype)
        b = x.shape[0]
        q, k, v = self._qkv(p, x[:, None], jnp.reshape(pos, (1,)))
        return q.reshape(b, -1), k.reshape(b, -1), v.reshape(b, -1)

    def decode_finish(self, params, x, y, sow=None):
        """The output projection of the attention's output ``y`` and the
        routed experts; sows :attr:`decode_stats` of this step."""
        p = _cast({nm: params[nm] for nm in
                   ("proj", "ln2", "router", "experts")}, x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        # q/k/v/o, causal attention as the other blocks count it, the
        # router, and experts_per_tok (not num_experts) SwiGLU experts
        (spec,) = in_specs
        t, d = spec.shape
        return (2 * t * d * 4 * d + 4 * t * t * d
                + 2 * t * d * self.num_experts
                + self.experts_per_tok * 2 * t * 3 * d * self.expert_hidden)


class OlmoeEmbedding(Op):
    """Token embedding alone: positions enter through RoPE."""

    def __init__(self, vocab: int, features: int, max_len: int):
        self.vocab = vocab
        self.features = features
        self.max_len = max_len      #: positions the model declares

    def init(self, key, in_specs):
        del in_specs
        return {"wte": jax.random.normal(
            key, (self.vocab, self.features), jnp.float32) * 0.02}

    def apply(self, params, ids):
        return params["wte"][ids.astype(jnp.int32)]

    def embed_at(self, params, ids, pos):
        """Decode-path embedding: ``ids`` [b]; ``pos`` is not read."""
        del pos
        return params["wte"][ids.astype(jnp.int32)]

    def flops(self, in_specs, out_spec):
        return out_spec.size


def olmoe(num_layers: int, hidden: int, heads: int, seq_len: int,
          vocab: int = 50304, num_experts: int = 64,
          experts_per_tok: int = 8, expert_hidden: int = 1024,
          rope_theta: float = 10000.0, rms_eps: float = 1e-5,
          name: str = "olmoe") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions (the full-sequence graph's length and the most a
    decoder may cache).  Untied, bias-free head; RMSNorm ``final_ln``."""
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        x = b.add(OlmoeBlock(heads, num_experts, experts_per_tok,
                             expert_hidden, rope_theta=rope_theta,
                             rms_eps=rms_eps),
                  x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def olmoe_tiny(seq_len: int = 16, vocab: int = 211) -> LayerGraph:
    return olmoe(2, 64, 4, seq_len, vocab=vocab, num_experts=8,
                 experts_per_tok=2, expert_hidden=32, name="olmoe_tiny")
