"""JetBrains' Mellum 2 decoder family (Hugging Face ``model_type``
``mellum``; ``Mellum2-12B-A2.5B-Instruct``): a sequential pre-norm block
— ``h = x + attn(rms(x))``, ``y = h + moe(rms(h))`` — with grouped
queries (a head's width its own and not the stream's over the heads),
layers in a fixed pattern of **window** layers and **full** layers that
*rotate by different tables*, and in place of the MLP routed SwiGLU
experts with none shared; an untied head.

**The rotation is the layer's kind's.**  Both kinds turn the whole head
by rotate-half RoPE (pair ``(j, j + hd / 2)``, ``models/olmoe.py::rope``)
before a key is cached.  A window layer turns pair ``j`` by ``pos *
theta ** (-2j / hd)`` and attends the ``window`` newest positions, its
own counted.  A full layer turns it by YaRN's table
(``models/rotary.py::yarn_inv_freq``: the fast pairs as they are, the
slow ones ``factor`` times slower, a linear ramp between) and multiplies
``cos`` and ``sin`` of queries and keys alike by the attention factor
``c`` (a score by ``c ** 2``; the cached key carries its ``c``), and
attends every earlier position.  A block is handed its table and its
factor when the graph is built; it knows nothing of the other kind.

**Routing**: a softmax over all experts, the ``k`` largest,
renormalised over the chosen (``norm_topk_prob``) — which is a softmax
over the ``k`` chosen logits alone, ``ops/routed.py::route_top_k``'s
``"softmax_of_chosen"``.  Every layer holds all its experts.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py).  A window
layer publishes its ``window``, so the holder of its memory keeps a
ring buffer of that many rows for it (``ops/kv_cache.py``) beside the
full layers' row a position; each kind names its cache kernels
(``kv_attend_window`` / ``kv_attend_full``), so a device trace tells
the two kinds' attention apart.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph
from ..graph.ops import Dense, RMSNorm, rms_norm
from .olmoe import OlmoeBlock, OlmoeEmbedding, rope
from .rotary import yarn_attention_factor, yarn_inv_freq

WINDOW_LAYER, FULL_LAYER = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True, repr=False)
class MellumBlock(OlmoeBlock):
    """One layer as a single graph node (the module docstring): OLMoE's
    block — its routed tail, its full-sequence and cached halves —
    with grouped queries of a width of their own, no q/k norm, a
    rotation the layer's kind chooses and this family's routing rule.
    ``window`` says which kind the layer is (None: a full layer);
    ``rope_freqs`` is the rotation's table a pair where the kind scales
    its frequencies (None: ``rope_theta``'s own) and ``rope_factor``
    what its ``cos`` and ``sin`` are multiplied by."""

    num_kv_heads: int = dataclasses.field(kw_only=True)
    head_dim: int = dataclasses.field(kw_only=True)
    window: int | None = None
    rope_theta: float = 500000.0
    rope_freqs: tuple | None = None
    rope_factor: float = 1.0
    rms_eps: float = 1e-6

    scoring = "softmax_of_chosen"
    qkv_leaves = ("ln1", "q", "k", "v")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads

    @property
    def kind(self) -> str:
        """``"window"`` or ``"full"``: the suffix of this layer's cache
        kernels' names."""
        return "full" if self.window is None else "window"

    def widest(self, d_model: int) -> int:
        """A token's ``experts_per_tok`` rows sorted by expert, or the
        queries' columns."""
        return max(d_model * self.experts_per_tok,
                   self.num_heads * self.head_dim)

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """:meth:`DecoderBlock.memory_format`'s, its kernels named by
        the layer's kind."""
        fmt = super().memory_format(d_model, positions, dtype,
                                    quantized=quantized, groups=groups)
        return dataclasses.replace(fmt, kernel_suffix="_" + self.kind)

    def init(self, key, in_specs):
        (spec,) = in_specs
        d = spec.shape[-1]
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        e, h = self.num_experts, self.expert_hidden
        ks = jax.random.split(key, 8)
        s = 1.0 / math.sqrt(d)

        def mat(k, shape, scale):
            return jax.random.normal(k, shape, jnp.float32) * scale

        def ones():
            return {"scale": jnp.ones((d,), jnp.float32)}

        return {
            "ln1": ones(),
            "q": {"w": mat(ks[0], (d, qd), s)},
            "k": {"w": mat(ks[1], (d, kvd), s)},
            "v": {"w": mat(ks[2], (d, kvd), s)},
            "proj": {"w": mat(ks[3], (qd, d), 1.0 / math.sqrt(qd))},
            "ln2": ones(),
            "router": {"w": mat(ks[4], (d, e), s)},
            "experts": {"gate": mat(ks[5], (e, d, h), s),
                        "up": mat(ks[6], (e, d, h), s),
                        "down": mat(ks[7], (e, h, d), 1.0 / math.sqrt(h))},
        }

    def rotate(self, x, pos):
        """``x`` [..., t, heads, hd] turned to positions ``pos`` [t] by
        this kind's table and factor."""
        return rope(x, pos, self.rope_theta, self.rope_freqs,
                    self.rope_factor)

    def _qkv(self, p, x, pos):
        """Rotated queries [..., t, nh, hd] and keys [..., t, kv, hd] and
        the values of ``x`` [..., t, d] at positions ``pos`` [t]."""
        y = rms_norm(x, p["ln1"]["scale"], self.rms_eps)

        def heads(a, n):
            return a.reshape(a.shape[:-1] + (n, self.head_dim))

        return (self.rotate(heads(y @ p["q"]["w"], self.num_heads), pos),
                self.rotate(heads(y @ p["k"]["w"], self.num_kv_heads), pos),
                heads(y @ p["v"]["w"], self.num_kv_heads))

    def apply(self, params, x):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]."""
        lead = x.shape[:-2]
        y = self.apply_with_kv(params, x.reshape((-1,) + x.shape[-2:]))[0]
        return y.reshape(lead + y.shape[-2:])

    def flops(self, in_specs, out_spec):
        # q/k/v/o, attention over the window or the whole, the router,
        # and experts_per_tok (not num_experts) SwiGLU experts a token
        (spec,) = in_specs
        t, d = spec.shape
        qd, kvd = self.num_heads * self.head_dim, \
            self.num_kv_heads * self.head_dim
        reach = t if self.window is None else min(t, self.window)
        return (2 * t * d * (2 * qd + 2 * kvd) + 4 * t * reach * qd
                + 2 * t * d * self.num_experts
                + self.experts_per_tok * 2 * t * 3 * d * self.expert_hidden)


def mellum(num_layers: int, hidden: int, heads: int, kv_heads: int,
           head_dim: int, seq_len: int, vocab: int, num_experts: int,
           experts_per_tok: int, expert_hidden: int, layer_types,
           window: int, rope_theta: float = 500000.0,
           rope_factor: float = 1.0, rope_original: int = 8192,
           beta_fast: float = 32.0, beta_slow: float = 1.0,
           attention_factor: float | None = None, rms_eps: float = 1e-6,
           name: str = "mellum") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions (the full-sequence graph's length and the most
    a full layer may cache).  ``layer_types`` names each layer
    ``"sliding_attention"`` (a window layer of ``window`` positions,
    plain RoPE) or ``"full_attention"`` (YaRN: ``rope_factor`` from
    ``rope_original`` positions, ``beta_fast`` / ``beta_slow``, and the
    ``attention_factor`` on ``cos`` and ``sin`` — None: YaRN's own ``0.1
    ln(factor) + 1``); a shorter list is the pattern's period and
    repeats.  The full layers' table is computed here, once.  Untied,
    bias-free head; RMSNorm ``final_ln``."""
    layer_types = list(layer_types)
    for kind in layer_types:
        if kind not in (WINDOW_LAYER, FULL_LAYER):
            raise ValueError(f"layer type {kind!r} is neither "
                             f"{WINDOW_LAYER!r} nor {FULL_LAYER!r}")
    full = dict(
        rope_freqs=yarn_inv_freq(head_dim, rope_theta, rope_factor,
                                 rope_original, beta_fast, beta_slow),
        rope_factor=yarn_attention_factor(rope_factor)
        if attention_factor is None else float(attention_factor))
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        kind = layer_types[i % len(layer_types)]
        x = b.add(MellumBlock(
            heads, num_experts, experts_per_tok, expert_hidden,
            num_kv_heads=kv_heads, head_dim=head_dim,
            rope_theta=rope_theta, rms_eps=rms_eps,
            **(dict(window=window) if kind == WINDOW_LAYER else full)),
            x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def mellum_tiny(seq_len: int = 64, vocab: int = 211) -> LayerGraph:
    """Two periods of three window layers (8 positions) and a full one;
    8 query heads on 4 KV heads of 32; 2 of 8 experts a token; YaRN by
    4 from 32 positions on (``original`` shorter than the text), its
    ramp over pairs 1-5 of 16."""
    return mellum(8, 64, 8, 4, 32, seq_len, vocab, 8, 2, 32,
                  (WINDOW_LAYER,) * 3 + (FULL_LAYER,), 8,
                  rope_theta=10000.0, rope_factor=4.0, rope_original=32,
                  beta_fast=2.0, beta_slow=0.5, name="mellum_tiny")
