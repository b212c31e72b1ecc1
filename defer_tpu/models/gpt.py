"""GPT-style causal decoder family: full-sequence graph + KV-cache decode.

The reference framework is CNN-only inference (SURVEY.md §2.3); this family
goes beyond parity: an autoregressive decoder whose full-sequence
(prefill/scoring) forward rides the ordinary ``SpmdPipeline`` — one
``block_k`` node per pipeline stage, exactly like BERT-Base/12 — and whose
token-by-token generation path is served by the pipelined KV-cache engine in
:mod:`defer_tpu.runtime.decode`.

Each :class:`CausalTransformerBlock` is one graph node (a natural
single-tensor cut point) and additionally meets the decode engines' block
interface (:class:`~defer_tpu.models.decoder.DecoderBlock`): the
single-token step in two halves around the key/value cache.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, LayerNorm, TransformerBlock, _cast
from .decoder import DecoderBlock


@dataclasses.dataclass(frozen=True, repr=False)
class CausalTransformerBlock(DecoderBlock, TransformerBlock):
    """Pre-LN decoder block: causal self-attention + MLP.

    Full-sequence ``apply`` masks causally (flash kernel's bottom-right
    alignment, ops/flash_attention.py); ``decode`` is the incremental
    single-token step used by the pipelined decoder.

    ``num_kv_heads`` enables grouped-query attention (MQA at 1): query
    heads share ``num_heads // num_kv_heads``-way KV groups, shrinking the
    decode KV cache — and its per-step HBM read, the decode bottleneck —
    by that factor.  ``None`` keeps classic multi-head attention.
    """

    num_kv_heads: int | None = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def _check_kv(self):
        if self.num_heads % self.kv_heads:
            raise ValueError(
                f"num_heads={self.num_heads} not divisible by "
                f"num_kv_heads={self.kv_heads}")

    def init(self, key, in_specs):
        kv = self.kv_heads
        if kv == self.num_heads:
            return super().init(key, in_specs)
        self._check_kv()
        (spec,) = in_specs
        d = spec.shape[-1]
        hd = d // self.num_heads
        p = super().init(key, in_specs)
        # narrow the fused qkv projection: d query cols + 2*kv*hd KV cols
        w = p["qkv"]["w"]
        p["qkv"] = {
            "w": jnp.concatenate(
                [w[:, :d], w[:, d: d + kv * hd],
                 w[:, 2 * d: 2 * d + kv * hd]], axis=-1),
            "b": jnp.zeros((d + 2 * kv * hd,), jnp.float32),
        }
        return p

    def _split_qkv(self, qkv):
        """Static q/k/v column split: d query cols, kv*hd each for K/V."""
        nh, kv = self.num_heads, self.kv_heads
        hd = qkv.shape[-1] // (nh + 2 * kv)
        dq = nh * hd
        return (qkv[..., :dq], qkv[..., dq: dq + kv * hd],
                qkv[..., dq + kv * hd:])

    def _kv_head_count(self) -> int:
        return self.kv_heads

    def _attend_columns(self, q, k, v):
        """Under ``"flash"`` the kernels take the projection's columns
        as they lie, token-major (``ops/flash_attention.py::
        flash_causal_columns``: heads under a lane row wide are read
        side by side, nothing transposed or padded around the call;
        any other geometry is laid head-major there); plain XLA splits
        the heads out (the base's)."""
        if self._attention_impl() == "flash":
            from ..ops.flash_attention import flash_causal_columns
            return flash_causal_columns(q, k, v, heads=self.num_heads,
                                        kv_heads=self.kv_heads)
        return super()._attend_columns(q, k, v)

    def flops(self, in_specs, out_spec):
        # base formula assumes a 3d-wide qkv projection; GQA narrows it
        (spec,) = in_specs
        t, d = spec.shape
        qkv_cols = d + 2 * self.kv_heads * (d // self.num_heads)
        return (2 * t * d * (qkv_cols + d + 2 * self.mlp_ratio * d)
                + 4 * t * t * d)

    # apply/apply_with_kv are inherited: the base TransformerBlock forward
    # (graph/ops.py) is the single implementation, made causal here purely
    # through _attend_columns and DecoderBlock's _attend.  apply_with_kv's K/V columns
    # match what decode_qkv hands over row by row (pre-head-split qkv
    # projections), so pipelined prefill bulk-writes cache rows 0..t-1
    # and decoding continues at t.

    def decode_qkv(self, params, x, pos=None):
        """First half of a one-token step: LN + qkv projection of ``x``
        [b, d].  Returns the query and the new key and value columns.
        ``pos`` is part of the block interface and is not read: this
        family's positions come with the embedding."""
        del pos
        p = _cast(params, x.dtype)
        y = self._ln(p["ln1"], x, self.ln_eps)
        qkv = y @ p["qkv"]["w"] + p["qkv"]["b"]
        return self._split_qkv(qkv)

    def decode_finish(self, params, x, y, sow=None):
        """Second half: ``y`` [b, d] the attention's output over the
        cache (the new row already in it), then proj + MLP on the
        residual stream ``x``.  Returns the block's output [b, d]."""
        del sow     # no ``decode_stats``
        p = _cast(params, x.dtype)
        x = x + (y @ p["proj"]["w"] + p["proj"]["b"])

        y = self._ln(p["ln2"], x, self.ln_eps)
        y = jax.nn.gelu(y @ p["fc1"]["w"] + p["fc1"]["b"])
        return x + (y @ p["fc2"]["w"] + p["fc2"]["b"])


class GptEmbedding(Op):
    """Token + learned positional embeddings (GPT-2 style, no post-LN)."""

    def __init__(self, vocab: int, features: int, max_len: int):
        self.vocab = vocab
        self.features = features
        self.max_len = max_len

    def init(self, key, in_specs):
        del in_specs
        k1, k2 = jax.random.split(key)
        return {
            "wte": jax.random.normal(k1, (self.vocab, self.features),
                                     jnp.float32) * 0.02,
            "wpe": jax.random.normal(k2, (self.max_len, self.features),
                                     jnp.float32) * 0.01,
        }

    def apply(self, params, ids):
        t = ids.shape[1]
        return (params["wte"][ids.astype(jnp.int32)]
                + params["wpe"][:t])

    def embed_at(self, params, ids, pos):
        """Decode-path embedding: ``ids`` [b] at scalar position ``pos``."""
        tok = params["wte"][ids.astype(jnp.int32)]
        return tok + lax.dynamic_slice(params["wpe"], (pos, 0),
                                       (1, self.features))[0]

    def embed_rows(self, params, ids, pos):
        """Decode-path embedding of sequences at their own positions:
        ``ids`` [b] at ``pos`` [b].  One ``dynamic_slice`` a row: for a
        gather XLA:TPU first copies the whole table (322 MB of f32 at
        GPT-2's vocabulary) out of the layout it is held in."""
        def rows(table, idx):
            return jnp.concatenate(
                [lax.dynamic_slice(table, (idx[i], 0), (1, self.features))
                 for i in range(idx.shape[0])])
        return (rows(params["wte"], ids.astype(jnp.int32))
                + rows(params["wpe"], pos))

    def flops(self, in_specs, out_spec):
        return out_spec.size


def gpt(num_layers: int, hidden: int, heads: int, seq_len: int,
        vocab: int = 50257, kv_heads: int | None = None,
        ln_eps: float = 1e-6, name: str = "gpt") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab].

    ``block_k`` nodes are the pipeline cut points; the decode engine
    (:mod:`defer_tpu.runtime.decode`) consumes the same graph by node-name
    contract: ``embeddings``, ``block_0..``, ``final_ln``, ``lm_head``.
    ``kv_heads`` < ``heads`` builds a GQA model (MQA at 1).  ``ln_eps``
    is threaded through every block and the final LayerNorm — HF GPT-2
    checkpoints were trained at 1e-5 (see :func:`gpt2_small`).
    """
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(GptEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        x = b.add(CausalTransformerBlock(heads, num_kv_heads=kv_heads,
                                         ln_eps=ln_eps),
                  x, name=f"block_{i}")
    x = b.add(LayerNorm(eps=ln_eps), x, name="final_ln")
    x = b.add(Dense(vocab), x, name="lm_head")
    return b.build()


def gpt_small(seq_len: int = 256, kv_heads: int | None = None) -> LayerGraph:
    """GPT-2 small geometry (12 layers, d=768, 12 heads)."""
    return gpt(12, 768, 12, seq_len, kv_heads=kv_heads, name="gpt_small")


def gpt2_small(seq_len: int = 256) -> LayerGraph:
    """HF-faithful GPT-2 small: same geometry as :func:`gpt_small` but
    with GPT-2's trained LN epsilon (1e-5), so ``gpt2`` checkpoints
    (``utils/pretrained.py: load_pretrained_gpt2``) reproduce HF logits.
    """
    return gpt(12, 768, 12, seq_len, ln_eps=1e-5, name="gpt2_small")


def gpt_tiny(seq_len: int = 16, vocab: int = 97,
             kv_heads: int | None = None) -> LayerGraph:
    return gpt(4, 32, 2, seq_len, vocab=vocab, kv_heads=kv_heads,
               name="gpt_tiny")


def gpt_stage_cuts(num_layers: int, num_stages: int) -> list[str]:
    """Even block-boundary cut points for an ``num_stages``-stage pipeline."""
    if not 1 <= num_stages <= num_layers:
        raise ValueError(f"need 1 <= stages <= {num_layers}")
    per = num_layers / num_stages
    return [f"block_{round(per * (s + 1)) - 1}"
            for s in range(num_stages - 1)]
