"""AI21's Jamba decoder family (Hugging Face ``model_type`` ``jamba``;
``AI21-Jamba2-3B``): a stack in which most layers mix the sequence with
a **Mamba-1** selective state-space mixer (Gu & Dao, arXiv:2312.00752)
and one layer a period with grouped-query softmax attention that
carries **no positional signal at all** — the state-space layers give
the order.  Every layer's second half is a dense SwiGLU MLP; RMSNorm
before each half; the head tied to the embedding.  The family's
addition to Mamba-1: the three projections of the selection (the
step's low-rank input, ``B`` and ``C``) each pass an RMSNorm of their
own.  The mixer's equations and how its state lies on the device are
``ops/ssm.py``'s.

Two kinds of per-sequence memory therefore lie side by side in one
graph: :class:`JambaMambaBlock` is a
:class:`~defer_tpu.models.decoder.StateSpaceBlock` (a convolution window
and a state of fixed size), :class:`JambaAttentionBlock` a
:class:`~defer_tpu.models.decoder.DecoderBlock` (a KV cache, many query
heads on few KV heads).  The graph follows the decoder-model contract
(``embeddings`` / ``block_i`` / ``final_ln`` / ``lm_head``,
models/decoder.py).

Layouts that differ from the published checkpoint's (all of layout,
none of arithmetic): ``conv/w`` is ``[d_conv, E]`` (taps lead),
``ssm/a_log`` is ``[N, E]`` (states lead, as the state's buffer).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import RMSNorm, _cast, rms_norm
from ..ops import ssm
from .cohere_moe import CohereHead
from .decoder import DecoderBlock, StateSpaceBlock
from .olmoe import OlmoeEmbedding

#: what both kinds of block sow: sequences whose state-space state a
#: step really updated (an attention block sows 0, a bubble sows 0)
_STATS = ("ssm.updates",)


def _mat(key, shape, scale):
    return {"w": jax.random.normal(key, shape, jnp.float32) * scale}


def _ones(n):
    return {"scale": jnp.ones((n,), jnp.float32)}


def _mlp_init(keys, d: int, h: int) -> dict:
    s = 1.0 / math.sqrt(d)
    return {"ln2": _ones(d),
            "mlp_gate": _mat(keys[0], (d, h), s),
            "mlp_up": _mat(keys[1], (d, h), s),
            "mlp_down": _mat(keys[2], (h, d), 1.0 / math.sqrt(h))}


def _mlp(p, x32, dtype, eps: float):
    """The second half of a layer: the dense SwiGLU behind its norm,
    added to the float32 stream ``x32``, which is rounded to ``dtype``
    once, on the way out."""
    f32 = jnp.float32
    h = rms_norm(x32, p["ln2"]["scale"], eps).astype(dtype)
    a = jax.nn.silu(h @ p["mlp_gate"]["w"]) * (h @ p["mlp_up"]["w"])
    return (x32 + jnp.dot(a, p["mlp_down"]["w"],
                          preferred_element_type=f32)).astype(dtype)


_MLP_KEYS = ("ln2", "mlp_gate", "mlp_up", "mlp_down")


@dataclasses.dataclass(frozen=True, repr=False)
class JambaMambaBlock(StateSpaceBlock, Op):
    """One state-space layer as a single graph node: the Mamba-1 mixer
    with the family's three small norms, then the SwiGLU MLP, each
    behind a residual."""

    channels: int           #: ``E``: ``mamba_expand`` x the stream
    states: int             #: ``N``: ``mamba_d_state``
    d_conv: int
    dt_rank: int
    mlp_hidden: int
    rms_eps: float = 1e-6

    decode_stats = _STATS

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        e, n, r, k = self.channels, self.states, self.dt_rank, self.d_conv
        ks = jax.random.split(key, 10)
        # Mamba-1's published initialisation: A = -(1..N) on every
        # channel, and the step's bias the inverse softplus of a step
        # drawn log-uniformly in [1e-3, 1e-1], so that exp(dt A) leaves
        # a state a memory of tens to hundreds of positions
        step = jnp.exp(jax.random.uniform(ks[4], (e,), jnp.float32)
                       * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        bound = 1.0 / math.sqrt(k)
        return {
            "ln1": _ones(d),
            "in_proj": _mat(ks[0], (d, 2 * e), 1.0 / math.sqrt(d)),
            "conv": {"w": jax.random.uniform(ks[1], (k, e), jnp.float32,
                                             -bound, bound),
                     "b": jax.random.uniform(ks[2], (e,), jnp.float32,
                                             -bound, bound)},
            "x_proj": _mat(ks[3], (e, r + 2 * n), 1.0 / math.sqrt(e)),
            "dt_norm": _ones(r), "b_norm": _ones(n), "c_norm": _ones(n),
            "dt_proj": {"w": jax.random.uniform(
                ks[5], (r, e), jnp.float32, -r ** -0.5, r ** -0.5),
                "b": step + jnp.log(-jnp.expm1(-step))},
            "ssm": {"a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=jnp.float32))[:, None], (n, e)),
                "d": jnp.ones((e,), jnp.float32)},
            "out_proj": _mat(ks[6], (e, d), 1.0 / math.sqrt(e)),
            **_mlp_init(ks[7:10], d, self.mlp_hidden),
        }

    # -- the mixer's pieces, around the state's format -----------------------

    @property
    def mixer_width(self) -> int:
        """The input projection's ``[u, z]``."""
        return 2 * self.channels

    def mixer_inputs(self, params, x):
        """``u`` and the gate ``z`` [..., E] of the stream ``x`` [...,
        d]."""
        p = _cast({nm: params[nm] for nm in ("ln1", "in_proj")}, x.dtype)
        uz = rms_norm(x, p["ln1"]["scale"], self.rms_eps) @ p["in_proj"]["w"]
        return uz[..., :self.channels], uz[..., self.channels:]

    def mixer_conv(self, params, taps):
        return ssm.causal_conv(taps, params["conv"]["w"],
                               params["conv"]["b"])

    def mixer_selection(self, params, c, z):
        """The step ``dt`` [..., E] and the projections ``B``, ``C``
        [..., N] of ``c`` [..., E], float32, ``c`` itself as what the
        recurrence is fed, and ``A`` [N, E]: the products leave the
        matrix unit in float32, the three norms and the softplus run
        in it.  The gate is not read."""
        del z
        f32, p = jnp.float32, params
        r, n = self.dt_rank, self.states
        sel = jnp.dot(c, p["x_proj"]["w"].astype(c.dtype),
                      preferred_element_type=f32)
        low = rms_norm(sel[..., :r], p["dt_norm"]["scale"], self.rms_eps)
        b = rms_norm(sel[..., r:r + n], p["b_norm"]["scale"], self.rms_eps)
        c_read = rms_norm(sel[..., r + n:], p["c_norm"]["scale"],
                          self.rms_eps)
        dt = jax.nn.softplus(
            jnp.dot(low.astype(c.dtype), p["dt_proj"]["w"].astype(c.dtype),
                    preferred_element_type=f32)
            + p["dt_proj"]["b"].astype(f32))
        return dt, c, b, c_read, -jnp.exp(p["ssm"]["a_log"].astype(f32))

    def decode_finish(self, params, x, y, c, z, sow=None):
        """The rest of a layer after the recurrence: ``x`` [T, d] the
        residual stream, ``y`` [T, E] float32 the state read by ``C``,
        ``c`` / ``z`` [T, E] the convolution's output and the gate.
        The skip term and the gate, the output projection, then the
        MLP, each added to the stream in float32.  Sows
        :attr:`decode_stats` of this step."""
        f32 = jnp.float32
        p = _cast({nm: params[nm] for nm in ("out_proj",) + _MLP_KEYS},
                  x.dtype)
        g = (y + params["ssm"]["d"].astype(f32) * c.astype(f32)) \
            * jax.nn.silu(z.astype(f32))
        x32 = x.astype(f32) + jnp.dot(g.astype(x.dtype), p["out_proj"]["w"],
                                      preferred_element_type=f32)
        if sow is not None:
            sow["ssm.updates"] = jnp.int32(x.shape[0])
        return _mlp(p, x32, x.dtype, self.rms_eps)

    # -- full sequence ----------------------------------------------------

    def apply(self, params, x):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d], the
        recurrence from an empty memory (``ops/ssm.py``)."""
        lead = x.shape[:-2]
        x = x.reshape((-1,) + x.shape[-2:])
        fmt = self.memory_format(x.shape[-1], x.shape[1], x.dtype)
        y, _ = self.prefill(params, x, fmt.layer(fmt.zeros(x.shape[0], 1), 0),
                            fmt)
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # the mixer's four matrices, the recurrence (an update and a
        # read of E x N values a token), the SwiGLU MLP
        (spec,) = in_specs
        t, d = spec.shape
        e, n, r = self.channels, self.states, self.dt_rank
        return (2 * t * (d * 2 * e + e * (r + 2 * n) + r * e + e * d)
                + 6 * t * e * n + 2 * t * 3 * d * self.mlp_hidden)


@dataclasses.dataclass(frozen=True, repr=False)
class JambaAttentionBlock(DecoderBlock, Op):
    """One attention layer as a single graph node: grouped-query
    softmax attention without bias, QK-norm or any position, then the
    SwiGLU MLP, each behind a residual."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_hidden: int
    rms_eps: float = 1e-6
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
        ks = jax.random.split(key, 7)
        s = 1.0 / math.sqrt(d)
        return {"ln1": _ones(d),
                "q": _mat(ks[0], (d, qd), s), "k": _mat(ks[1], (d, kvd), s),
                "v": _mat(ks[2], (d, kvd), s),
                "proj": _mat(ks[3], (qd, d), 1.0 / math.sqrt(qd)),
                **_mlp_init(ks[4:7], d, self.mlp_hidden)}

    def _qkv(self, p, x):
        """Query, key and value columns of ``x`` [..., d]: [..., nh*hd]
        and [..., kv*hd] twice.  Nothing depends on the position."""
        h = rms_norm(x, p["ln1"]["scale"], self.rms_eps)
        return h @ p["q"]["w"], h @ p["k"]["w"], h @ p["v"]["w"]

    def _finish(self, p, x, y, sow=None):
        x32 = x.astype(jnp.float32) + jnp.dot(
            y, p["proj"]["w"], preferred_element_type=jnp.float32)
        if sow is not None:
            sow["ssm.updates"] = jnp.int32(0)
        return _mlp(p, x32, x.dtype, self.rms_eps)

    def apply(self, params, x):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]))[0]
        return y.reshape(lead + y.shape[-2:])

    def apply_with_kv(self, params, x):
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q, k, v = self._qkv(p, x)

        def heads(a, n):
            return a.reshape(b, t, n, self.head_dim).transpose(0, 2, 1, 3)

        y = self._attend(heads(q, self.num_heads),
                         heads(k, self.num_kv_heads),
                         heads(v, self.num_kv_heads))
        out = self._finish(p, x.reshape(b * t, d),
                           y.transpose(0, 2, 1, 3).reshape(b * t, -1))
        return out.reshape(b, t, d), k, v

    def decode_qkv(self, params, x, pos):
        """Query and new key and value columns of ``x`` [b, d]; the
        position is not read."""
        del pos
        return self._qkv(_cast({nm: params[nm] for nm in
                                ("ln1", "q", "k", "v")}, x.dtype), x)

    def decode_finish(self, params, x, y, sow=None):
        p = _cast({nm: params[nm] for nm in ("proj",) + _MLP_KEYS}, x.dtype)
        return self._finish(p, x, y, sow)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        return (2 * t * d * (2 * qd + 2 * kvd) + 4 * t * t * qd
                + 2 * t * 3 * d * self.mlp_hidden)


def jamba(num_layers: int, hidden: int, heads: int, kv_heads: int,
          head_dim: int, mlp_hidden: int, seq_len: int, vocab: int,
          attn_layer_period: int, attn_layer_offset: int,
          mamba_expand: int = 2, mamba_d_state: int = 16,
          mamba_d_conv: int = 4, mamba_dt_rank: int = 160,
          rms_eps: float = 1e-6, name: str = "jamba") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions the model declares (the full-sequence graph's
    length and the most an attention layer may cache).  Layer ``l`` is
    an attention layer where ``l % attn_layer_period ==
    attn_layer_offset`` and a state-space layer elsewhere.  Initialise
    with ``cohere_moe.tie_head(graph.init(key))``: the head is the
    embedding's table."""
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        if i % attn_layer_period == attn_layer_offset:
            op = JambaAttentionBlock(heads, kv_heads, head_dim, mlp_hidden,
                                     rms_eps=rms_eps)
        else:
            op = JambaMambaBlock(mamba_expand * hidden, mamba_d_state,
                                 mamba_d_conv, mamba_dt_rank, mlp_hidden,
                                 rms_eps=rms_eps)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(CohereHead(vocab), x, name="lm_head")
    return b.build()


def jamba_tiny(seq_len: int = 32, vocab: int = 211) -> LayerGraph:
    """Two periods of four layers with attention at offset 2; 4 query
    heads on 1 KV head; 128 channels of 8 states."""
    return jamba(8, 64, 4, 1, 16, 96, seq_len, vocab, 4, 2,
                 mamba_d_state=8, mamba_dt_rank=4, name="jamba_tiny")
