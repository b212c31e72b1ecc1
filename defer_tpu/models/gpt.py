"""GPT-style causal decoder family: full-sequence graph + KV-cache decode.

The reference framework is CNN-only inference (SURVEY.md §2.3); this family
goes beyond parity: an autoregressive decoder whose full-sequence
(prefill/scoring) forward rides the ordinary ``SpmdPipeline`` — one
``block_k`` node per pipeline stage, exactly like BERT-Base/12 — and whose
token-by-token generation path is served by the pipelined KV-cache engine in
:mod:`defer_tpu.runtime.decode`.

Each :class:`CausalTransformerBlock` is one graph node (a natural
single-tensor cut point) and additionally exposes :meth:`decode` — the
single-token step against a key/value cache that the decode engine switches
on per stage.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, LayerNorm, TransformerBlock, _cast


class DecoderBlock:
    """What every decoder block shares: causal attention over a whole
    sequence, and the cache contract (how a row is quantized, written
    and attended over).  A block the decode ring (``runtime/decode.py``)
    can run has these, and

    * ``num_heads`` / ``kv_heads`` / ``attn_impl``;
    * ``apply_with_kv(params, x [b, t, d]) -> (y, k, v)``: the
      full-sequence forward, with the key and value columns [b, t, kv*hd]
      as :meth:`decode_qkv` would have written them row by row;
    * ``decode_qkv(params, x [b, d], pos, *, quant) -> (q, rows)``: the
      query and the new cache rows of the token at position ``pos``;
    * ``decode_attend(params, x, q, k_cache, v_cache, pos, k_scale,
      v_scale, sow=None) -> y``: attention over the cache item and the
      rest of the block.  A block that names ``decode_stats`` adds one
      scalar under each of those names to the dict ``sow``;
    * ``stage_arg_keys``: keys of its parameter dict whose leaves the
      ring passes as stage-sharded arguments of their own instead of
      slicing them out of the flat weight row.
    """

    #: per-step scalars ``decode_attend`` sows (summed over a generation)
    decode_stats: tuple = ()
    #: parameter subtrees kept out of the flat weight row
    stage_arg_keys: tuple = ()

    def _attend(self, q, k, v):
        """Causal attention on [b, nh, t, hd] by ``attn_impl``: the
        flash kernel (bottom-right aligned) on a TPU, plain XLA elsewhere."""
        impl = self.attn_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "xla"
        if impl not in ("flash", "xla"):
            raise ValueError(
                f"attn_impl must be 'auto', 'flash' or 'xla', got {impl!r}")
        if impl == "flash":
            from ..ops import flash_attention
            return flash_attention(q, k, v, causal=True)
        hd = q.shape[-1]
        t_q, t_k = q.shape[2], k.shape[2]
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        q_pos = jnp.arange(t_q)[:, None] + (t_k - t_q)
        mask = q_pos >= jnp.arange(t_k)[None, :]
        att = jnp.where(mask, att, jnp.asarray(-jnp.inf, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    @staticmethod
    def quantize_row(row):
        """Symmetric per-(head, position)-row int8: [..., hd] float ->
        ([..., hd] int8, [...] f32 scale).  One scale per cache row keeps
        dequantization a scalar multiply that folds EXACTLY into the
        attention contractions (the scale is constant over the contracted
        head dim), so the int8 cache is read raw by the dots and no
        dequantized copy is ever materialized."""
        rowf = row.astype(jnp.float32)
        amax = jnp.max(jnp.abs(rowf), axis=-1)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        q = jnp.clip(jnp.round(rowf / scale[..., None]), -127, 127)
        return q.astype(jnp.int8), scale

    @classmethod
    def cache_rows(cls, k_new, v_new, kv: int, quant: bool) -> dict:
        """The new rows keyed as the caches are: ``k``/``v`` [b, kv, 1,
        hd] from [b, kv*hd] columns; with ``quant`` int8 by
        :meth:`quantize_row`, their [b, kv, 1] f32 scales as
        ``ks``/``vs``."""
        b = k_new.shape[0]
        rows = {"k": k_new.reshape(b, kv, 1, -1),
                "v": v_new.reshape(b, kv, 1, -1)}
        if quant:
            rows["k"], rows["ks"] = cls.quantize_row(rows["k"])
            rows["v"], rows["vs"] = cls.quantize_row(rows["v"])
        return rows

    @staticmethod
    def write_row(cache, row, pos, lead=()):
        """``cache`` with ``row`` written in place at position ``pos``.
        ``cache`` is [b, kv, L(, hd)] behind ``len(lead)`` more axes, at
        whose indices ``lead`` the row lands; the row is cast to the
        cache's type."""
        row = lax.expand_dims(row, range(len(lead))).astype(cache.dtype)
        at = tuple(lead) + (0, 0, pos) + (0,) * (row.ndim - len(lead) - 3)
        return lax.dynamic_update_slice(cache, row, at)

    @staticmethod
    def cache_attention(q, k_cache, v_cache, pos, k_scale=None,
                        v_scale=None):
        """One query a sequence over its cache item: ``q`` [b, nh*hd]
        against head-major ``k_cache``/``v_cache`` [b, kv, L, hd],
        positions <= ``pos`` live; returns [b, nh*hd].  With scales the
        caches are int8 rows and the scales fold into the dots."""
        b, d = q.shape
        kv, cache_len, hd = k_cache.shape[1:]
        quant = k_scale is not None

        qh = q.reshape(b, kv, d // (kv * hd), hd)
        kh = k_cache.astype(q.dtype)
        vh = v_cache.astype(q.dtype)
        att = jnp.einsum("bkgd,bkld->bkgl", qh, kh) / math.sqrt(hd)
        if quant:
            att = att * k_scale[:, :, None, :].astype(att.dtype)
        live = jnp.arange(cache_len)[None, None, None, :] <= pos
        att = jnp.where(live, att, jnp.asarray(-jnp.inf, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        if quant:
            att = att * v_scale[:, :, None, :].astype(att.dtype)
        return jnp.einsum("bkgl,bkld->bkgd", att, vh).reshape(b, d)


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

    def flops(self, in_specs, out_spec):
        # base formula assumes a 3d-wide qkv projection; GQA narrows it
        (spec,) = in_specs
        t, d = spec.shape
        qkv_cols = d + 2 * self.kv_heads * (d // self.num_heads)
        return (2 * t * d * (qkv_cols + d + 2 * self.mlp_ratio * d)
                + 4 * t * t * d)

    # apply/apply_with_kv are inherited: the base TransformerBlock forward
    # (graph/ops.py) is the single implementation, made causal here purely
    # through DecoderBlock's _attend.  apply_with_kv's K/V columns
    # match what decode() writes row-by-row (pre-head-split qkv
    # projections), so pipelined prefill bulk-writes cache rows 0..t-1
    # (after the head-major relayout) and decoding continues at t.

    def decode_qkv(self, params, x, pos=None, *, quant: bool = False):
        """First half of :meth:`decode`: LN + qkv projection of ``x``
        [b, d], and the new cache rows (the pipelined decoder writes them
        straight into its resident buffers).  Returns ``(q, rows)`` with
        ``rows`` as :meth:`cache_rows` keys them.  ``pos`` is part of
        the ring's block interface and is not read: this family's
        positions come with the embedding."""
        del pos
        p = _cast(params, x.dtype)
        y = self._ln(p["ln1"], x, self.ln_eps)
        qkv = y @ p["qkv"]["w"] + p["qkv"]["b"]
        q, k_new, v_new = self._split_qkv(qkv)
        return q, self.cache_rows(k_new, v_new, self.kv_heads, quant)

    def decode_attend(self, params, x, q, k_cache, v_cache, pos,
                      k_scale=None, v_scale=None, sow=None):
        """Second half of :meth:`decode`: attention of ``q`` over the
        cache item (positions <= ``pos``, the new row already in it),
        then proj + MLP on the residual stream ``x``.  Reads the caches
        only; returns the block's output [b, d]."""
        del sow     # no ``decode_stats``
        p = _cast(params, x.dtype)
        y = self.cache_attention(q, k_cache, v_cache, pos, k_scale, v_scale)
        x = x + (y @ p["proj"]["w"] + p["proj"]["b"])

        y = self._ln(p["ln2"], x, self.ln_eps)
        y = jax.nn.gelu(y @ p["fc1"]["w"] + p["fc1"]["b"])
        return x + (y @ p["fc2"]["w"] + p["fc2"]["b"])

    def decode(self, params, x, k_cache, v_cache, pos,
               k_scale=None, v_scale=None):
        """One-token step: ``x`` [b, d] at position ``pos``.

        ``k_cache``/``v_cache`` are **head-major** [b, kv, L, hd] with
        L > max position — KV heads lead so the attention contractions are
        plain batched dots; a position-major [b, L, d] layout would make
        XLA materialize a transpose of the whole cache every step.  Under
        GQA, kv < num_heads and each cache head serves its whole query
        group without materializing repeats.  The new key/value row is
        written at ``pos`` (callers pass a clamped scratch index for
        bubble steps) and attention covers positions <= ``pos``.

        With ``k_scale``/``v_scale`` ([b, kv, L] f32) the caches are int8
        rows quantized by :meth:`quantize_row`; scales fold into the dots
        exactly (per-row constants), so ICI^W HBM reads shrink to ~1
        byte/value.  Returns ``(y, k_cache, v_cache)`` plus the updated
        scales when quantized.

        The composition of :meth:`decode_qkv`, the row writes and
        :meth:`decode_attend` over one cache item: the oracle the tests
        hold both engines to.  The pipelined decoder and the serving
        engine call the halves and write the rows into their own
        buffers.
        """
        quant = k_scale is not None
        q, rows = self.decode_qkv(params, x, pos, quant=quant)
        if quant:
            k_scale = self.write_row(k_scale, rows["ks"], pos)
            v_scale = self.write_row(v_scale, rows["vs"], pos)
        k_cache = self.write_row(k_cache, rows["k"], pos)
        v_cache = self.write_row(v_cache, rows["v"], pos)
        out = self.decode_attend(params, x, q, k_cache, v_cache, pos,
                                 k_scale, v_scale)
        if quant:
            return out, k_cache, v_cache, k_scale, v_scale
        return out, k_cache, v_cache


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
