"""Brumby decoder family (Manifest AI; Hugging Face ``model_type``
``brumby``): a Qwen3-shaped dense decoder — RMSNorm, bias-free
projections, grouped queries, RMSNorm on each query and key head
(QK-norm, one scale vector for all heads), rotate-half RoPE, a SwiGLU
MLP — whose every layer replaces softmax attention by **power
retention** ("Scaling Context Requires Rethinking Attention",
arXiv:2507.04239): weights ``(q.k / sqrt(d))^2`` under a learned decay a
KV head a token, ``log sigmoid`` of one linear map of the normed
stream.  The mechanism's equations, its recurrent form and how its
state lies on the device are ``ops/retention.py``'s.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py) and
:class:`BrumbyBlock` is a
:class:`~defer_tpu.models.decoder.RetentionBlock`: the full-sequence
graph rides ``SpmdPipeline`` and generation rides ``PipelinedDecoder``
like the other families', with a state of fixed size where they keep a
KV cache.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, RMSNorm, _cast, rms_norm
from ..ops import retention
from .decoder import RetentionBlock
from .olmoe import OlmoeEmbedding, rope


@dataclasses.dataclass(frozen=True, repr=False)
class BrumbyBlock(RetentionBlock, Op):
    """One Brumby layer as a single graph node: power retention with
    QK-norm and RoPE, then the SwiGLU MLP, each behind a residual."""

    num_heads: int
    num_kv_heads: int
    mlp_hidden: int
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    #: values of a head; None: the stream's width over ``num_heads``
    #: (the family publishes ``head_dim`` beside ``hidden_size``)
    head_dim: int | None = None

    #: sequences whose state a step really updated (a bubble sows 0)
    decode_stats = ("retention.updates",)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    def _head_dim(self, d: int) -> int:
        return self.head_dim or d // self.num_heads

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        hd = self._head_dim(d)
        qd, kvd, h = self.num_heads * hd, self.num_kv_heads * hd, \
            self.mlp_hidden
        ks = jax.random.split(key, 8)
        s = 1.0 / math.sqrt(d)

        def mat(k, shape, scale):
            return {"w": jax.random.normal(k, shape, jnp.float32) * scale}

        def ones(n):
            return {"scale": jnp.ones((n,), jnp.float32)}

        return {
            "ln1": ones(d),
            "q": mat(ks[0], (d, qd), s), "q_norm": ones(hd),
            "k": mat(ks[1], (d, kvd), s), "k_norm": ones(hd),
            "v": mat(ks[2], (d, kvd), s),
            "decay": mat(ks[3], (d, self.num_kv_heads), s),
            "proj": mat(ks[4], (qd, d), 1.0 / math.sqrt(qd)),
            "ln2": ones(d),
            "mlp_gate": mat(ks[5], (d, h), s),
            "mlp_up": mat(ks[6], (d, h), s),
            "mlp_down": mat(ks[7], (h, d), 1.0 / math.sqrt(h)),
        }

    # -- the two halves of a layer ----------------------------------------

    def qkvg(self, params, x, pos):
        """Normed, rotated queries and keys, the values and the
        log-decay of ``x`` [..., t, d] at positions ``pos`` [t]: ``q``
        [..., t, nh*hd], ``k`` / ``v`` [..., t, kv*hd], ``lg`` [..., t,
        kv] (float32: ``log sigmoid`` of a product that leaves the
        matrix unit in float32)."""
        p = _cast({nm: params[nm] for nm in
                   ("ln1", "q", "q_norm", "k", "k_norm", "v", "decay")},
                  x.dtype)
        nh, kv = self.num_heads, self.num_kv_heads
        hd = self._head_dim(x.shape[-1])
        y = rms_norm(x, p["ln1"]["scale"], self.rms_eps)

        def heads(a, n):
            return a.reshape(a.shape[:-1] + (n, hd))

        q = rms_norm(heads(y @ p["q"]["w"], nh), p["q_norm"]["scale"],
                     self.rms_eps)
        k = rms_norm(heads(y @ p["k"]["w"], kv), p["k_norm"]["scale"],
                     self.rms_eps)
        lg = jax.nn.log_sigmoid(jnp.dot(
            y, p["decay"]["w"], preferred_element_type=jnp.float32))
        flat = x.shape[:-1] + (-1,)
        return (rope(q, pos, self.rope_theta).reshape(flat),
                rope(k, pos, self.rope_theta).reshape(flat),
                y @ p["v"]["w"], lg)

    def decode_finish(self, params, x, y, sow=None):
        """The rest of a layer after the retention: ``x`` [T, d] the
        residual stream, ``y`` [T, nh*hd] the heads' outputs merged.  Output
        projection, then the SwiGLU MLP, each added to the stream in
        float32; the stream is rounded to its own type once, on the way
        out.  Sows :attr:`decode_stats` of this step."""
        p = _cast({nm: params[nm] for nm in
                   ("proj", "ln2", "mlp_gate", "mlp_up", "mlp_down")},
                  x.dtype)
        f32 = jnp.float32
        x32 = x.astype(f32) + jnp.dot(y, p["proj"]["w"],
                                      preferred_element_type=f32)
        h = rms_norm(x32, p["ln2"]["scale"], self.rms_eps).astype(x.dtype)
        a = jax.nn.silu(h @ p["mlp_gate"]["w"]) * (h @ p["mlp_up"]["w"])
        if sow is not None:
            sow["retention.updates"] = jnp.int32(x.shape[0])
        return (x32 + jnp.dot(a, p["mlp_down"]["w"],
                              preferred_element_type=f32)).astype(x.dtype)

    # -- full sequence ----------------------------------------------------

    def apply(self, params, x):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], the
        retention in its attention form (``ops/retention.py``)."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        b, t, d = x.shape
        kv = self.num_kv_heads
        hd = self._head_dim(d)
        q, k, v, lg = self.qkvg(params, x, jnp.arange(t))
        num, den = retention.power_attention(
            q.reshape(b, t, kv, -1, hd), k.reshape(b, t, kv, hd),
            v.reshape(b, t, kv, hd), lg)
        y = retention.normalise(num, den, hd).reshape(b * t, -1)
        out = self.decode_finish(params, x.reshape(b * t, d),
                                 y.astype(x.dtype))
        return out.reshape(lead + (t, d))

    def flops(self, in_specs, out_spec):
        # q/k/v/o and the decay's map, the SwiGLU MLP, and the retention
        # by its recurrent form: a KV head's state updated (2 D d) and
        # read by its group's queries (2 D d each), D the state's rows
        (spec,) = in_specs
        t, d = spec.shape
        hd = self._head_dim(d)
        qd, kvd = self.num_heads * hd, self.num_kv_heads * hd
        rows = retention.state_rows(hd)
        return (2 * t * d * (2 * qd + 2 * kvd + self.num_kv_heads)
                + 2 * t * 3 * d * self.mlp_hidden
                + 2 * t * rows * hd * (self.num_kv_heads + self.num_heads))


def brumby(num_layers: int, hidden: int, heads: int, kv_heads: int,
           mlp_hidden: int, seq_len: int, vocab: int = 151936,
           rope_theta: float = 1e6, rms_eps: float = 1e-6,
           head_dim: int | None = None,
           name: str = "brumby") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length; a retention state holds any number); ``head_dim`` the
    published one where it is not ``hidden / heads``.  Untied,
    bias-free head; RMSNorm ``final_ln``."""
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        x = b.add(BrumbyBlock(heads, kv_heads, mlp_hidden,
                              rope_theta=rope_theta, rms_eps=rms_eps,
                              head_dim=head_dim),
                  x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def brumby_tiny(seq_len: int = 16, vocab: int = 211) -> LayerGraph:
    return brumby(2, 64, 4, 2, 96, seq_len, vocab=vocab, name="brumby_tiny")
