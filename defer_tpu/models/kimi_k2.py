"""Moonshot's Kimi K2 decoder family (Hugging Face ``model_type``
``kimi_k2``: the DeepSeek-V3 block): **latent attention** (MLA) — the
keys and values of all heads are made from one compressed row a
position, the normalised latent ``c`` and one rotated key ``k_r`` every
head shares, and that row is all a sequence keeps — in front of a dense
SwiGLU in the leading layer (:class:`KimiDenseBlock`) and of routed
SwiGLU experts beside a shared one in every layer behind it
(:class:`KimiMoeBlock`); RMSNorm before each half, two residuals a
layer; an untied head.

**Attention**, position ``t``, head ``i``: ``c_q = rms(h W_qa)``, ``[q_n,
q_r] = c_q W_qb`` a head; ``[c', k'] = h W_kva``, ``c = rms(c')``, ``k_r =
rope(k', t)`` (one for all heads), ``q_r <- rope(q_r, t)``; ``k_n[i] =
W_uk[i] c``, ``v[i] = c W_uv[i]`` (the two halves of the published
``kv_b_proj``, held as two stacks a head: ``k_up`` ``[heads, nope,
latent]``, ``v_up`` ``[heads, latent, v]``); scores ``(q_n . k_n + q_r .
k_r) * softmax_scale``, causal.  A prompt is computed in exactly this,
*expanded*, form (``ops/flash_attention.py::flash_latent``).  A step is
computed in the *absorbed* form: ``q~[i] = q_n[i] W_uk[i]`` so that a
score is ``[q~[i], q_r[i]] . [c, k_r]``, the heads' outputs
``o~[i] = sum_s p c_s`` stay in the latent space (``ops/latent_cache.py``)
and ``o[i] = o~[i] W_uv[i]`` — the same numbers up to rounding.  The
half is ``models/latent_attention.py``'s, shared with
``models/longcat_flash.py`` (whose two LoRA scales are 1 here).

**RoPE** turns adjacent pairs (the checkpoint's order) by YaRN's
frequencies (``models/rotary.py::yarn_inv_freq``), computed once in
float32 when the graph is built; ``softmax_scale`` is ``(nope + rope)
** -0.5 * m ** 2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1`` (:func:`yarn_softmax_scale`).

**Routing** (``topk_method`` ``noaux_tc`` with one group): sigmoid scores
``p`` over all experts, the ``k`` largest of ``p + b`` (``b``, the
balancing bias, chooses and never weighs), the chosen ``p`` renormalised
and multiplied by ``routed_scaling_factor``
(``ops/routed.py::route_top_k``).  A layer may hold a share of its routed
experts (``experts_held``), as ``models/cohere_moe.py``'s do: it routes
over all of them, computes the pairs that fell to the experts it holds
and adds the shared expert whole.

The graph follows the decoder-model contract (``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``, models/decoder.py); both kinds
of block are :class:`~defer_tpu.models.decoder.LatentBlock`s and sow one
ledger (the dense block zeros).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..graph.ir import GraphBuilder, LayerGraph, Op
from ..graph.ops import Dense, RMSNorm, _cast, rms_norm
from ..ops.routed import held_range, route, routed_experts
from .decoder import LatentBlock
from .latent_attention import LatentAttention, _normal
from .olmoe import OlmoeEmbedding
from .rotary import yarn_attention_factor, yarn_inv_freq


#: the spread of a seeded balancing bias (a checkpoint's is trained):
#: large enough to turn choices at near-ties (among hundreds of experts
#: the last chosen score and the first left out lie a few thousandths
#: apart), small beside what makes an expert popular with a run's
#: sequences (PERF.md, PR 45: at 0.003 and at 0.001 a cell's tokens a
#: second followed the held experts' hits alike)
_BIAS_SPREAD = 0.001


def yarn_softmax_scale(width: int, factor: float,
                       mscale_all_dim: float = 1.0) -> float:
    """What a score is multiplied by under YaRN: ``width ** -0.5 * m **
    2``, ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
    m = yarn_attention_factor(factor, mscale_all_dim)
    return width ** -0.5 * m * m


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class _KimiBlock(LatentAttention, LatentBlock, Op):
    """The attention half both kinds of layer share, and the two
    residuals; a subclass says ``_ffn`` and its parameters."""

    num_heads: int
    q_rank: int
    latent_dim: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    #: the rotation's frequencies a pair (``rotary.yarn_inv_freq``)
    rope_freqs: tuple
    softmax_scale: float
    rms_eps: float = 1e-5
    attn_impl: str = "auto"

    decode_stats = ("moe.assignments", "moe.held_assignments",
                    "moe.experts_hit", "moe.load_max")
    #: the way out's parameters: the attention's and the second norm
    _back = LatentAttention._back + ("ff_ln",)

    def _attention_init(self, keys, d: int) -> dict:
        return dict(super()._attention_init(keys, d),
                    ff_ln={"scale": jnp.ones((d,), jnp.float32)})

    def _finish(self, p, x, y, sow=None):
        """The layer's output from the stream ``x`` [T, d] and the heads'
        values merged ``y`` [T, nh * v]: the output projection, then the
        feed-forward half on the normed sum, each added to the stream in
        float32, which is rounded once on the way out."""
        f32 = jnp.float32
        x32 = x.astype(f32) + jnp.dot(y, p["proj"]["w"],
                                      preferred_element_type=f32)
        h = rms_norm(x32, p["ff_ln"]["scale"], self.rms_eps).astype(x.dtype)
        return (x32 + self._ffn(p, h, sow)).astype(x.dtype)

    # -- full sequence -----------------------------------------------------

    def apply_with_rows(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] over the expanded
        heads; also the rows [b, t, latent + rope] that
        :meth:`decode_q_row` would have handed over one by one.  A dict
        ``sow`` is filled as :meth:`decode_finish` fills it, over all
        b*t rows."""
        p = _cast(params, x.dtype)
        b, t, d = x.shape
        q_n, q_r, rows = self._q_rows(p, x, jnp.arange(t))
        y = self._expanded(p, q_n, q_r, rows)
        out = self._finish(p, x.reshape(b * t, d), y.reshape(b * t, -1), sow)
        return out.reshape(b, t, d), rows

    # -- one token against the cache ---------------------------------------

    def decode_q_row(self, params, x, pos):
        """Every head's absorbed query ``[b, nh * (latent + rope)]`` and
        the new row ``[b, latent + rope]`` of ``x`` [b, d] at scalar
        ``pos``."""
        return self._absorbed_q_row(
            _cast({nm: params[nm] for nm in self._front}, x.dtype), x, pos)

    def decode_finish(self, params, x, y, sow=None):
        """The heads' outputs ``y`` [b, nh * latent] out of the latent
        space (``W_uv``), then the output projection and the
        feed-forward half; sows :attr:`decode_stats` of this step."""
        p = _cast({nm: params[nm] for nm in self._back + self._ffn_params},
                  x.dtype)
        return self._finish(p, x, self._out_of_latent(p, x, y), sow)


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class KimiDenseBlock(_KimiBlock):
    """A leading layer (``first_k_dense_replace``): latent attention,
    then one dense SwiGLU of ``hidden`` columns."""

    hidden: int

    _ffn_params = ("gate", "up", "down")

    def init(self, key, in_specs):
        (spec,) = in_specs
        d, ks = spec.shape[-1], jax.random.split(key, 9)

        return dict(self._attention_init(ks, d),
                    gate={"w": _normal(ks[6], (d, self.hidden), d)},
                    up={"w": _normal(ks[7], (d, self.hidden), d)},
                    down={"w": _normal(ks[8], (self.hidden, d), self.hidden)})

    def widest(self, d_model: int) -> int:
        return max(super().widest(d_model), self.hidden)

    def _ffn(self, p, h, sow=None):
        if sow is not None:
            # no router: the ledger every block of the graph shares
            # takes zeros from this one
            sow.update({name: jnp.int32(0) for name in self.decode_stats})
        a = jax.nn.silu(h @ p["gate"]["w"]) * (h @ p["up"]["w"])
        return jnp.dot(a, p["down"]["w"], preferred_element_type=jnp.float32)

    def flops(self, in_specs, out_spec):
        (spec,) = in_specs
        t, d = spec.shape
        return self._attention_flops(t, d) + 2 * t * 3 * d * self.hidden


@dataclasses.dataclass(frozen=True, repr=False, kw_only=True)
class KimiMoeBlock(_KimiBlock):
    """A routed layer: latent attention, then ``experts_per_tok`` of
    ``num_experts`` routed SwiGLU experts by the ``noaux_tc`` rule beside
    ``num_shared`` shared ones every token passes, added whole.
    ``experts_held`` is the half-open range the layer holds and computes
    (None: all)."""

    num_experts: int
    experts_per_tok: int
    expert_hidden: int
    num_shared: int = 1
    routed_scale: float = 1.0
    experts_held: tuple | None = None

    _ffn_params = ("router", "experts", "shared_gate", "shared_up",
                   "shared_down")
    #: the router's rule (``ops/routed.py::route_top_k``)
    scoring = "noaux_tc"

    @property
    def held(self) -> tuple[int, int]:
        """The routed experts this layer holds, ``[lo, hi)``."""
        return held_range(self.experts_held, self.num_experts)

    def init(self, key, in_specs):
        (spec,) = in_specs
        d, ks = spec.shape[-1], jax.random.split(key, 14)
        h, sh = self.expert_hidden, self.num_shared * self.expert_hidden
        e = self.held[1] - self.held[0]

        return dict(
            self._attention_init(ks, d),
            # every expert's column and bias, held or not: the choice is
            # the whole layer's
            router={"w": _normal(ks[6], (d, self.num_experts), d),
                    "bias": jax.random.normal(
                        ks[7], (self.num_experts,), jnp.float32)
                    * _BIAS_SPREAD},
            experts={"gate": _normal(ks[8], (e, d, h), d),
                     "up": _normal(ks[9], (e, d, h), d),
                     "down": _normal(ks[10], (e, h, d), h)},
            shared_gate={"w": _normal(ks[11], (d, sh), d)},
            shared_up={"w": _normal(ks[12], (d, sh), d)},
            shared_down={"w": _normal(ks[13], (sh, d), h)})

    def route(self, params, h):
        """``(expert ids [T, k], their weights [T, k])`` of the normed
        stream ``h`` [T, d] in the type of ``params``: what the layer
        dispatches by."""
        p = _cast(params["router"], params["router"]["w"].dtype)
        return route(h.astype(p["w"].dtype), p, self.experts_per_tok,
                     self.scoring, self.routed_scale)

    def _ffn(self, p, h, sow=None):
        routed, shared = routed_experts(
            h, p["router"], p["experts"], k=self.experts_per_tok,
            scoring=self.scoring, num_experts=self.num_experts,
            held=self.held, scale=self.routed_scale,
            shared=(p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"]), sow=sow)
        return routed + shared

    def flops(self, in_specs, out_spec):
        # the whole layer's experts_per_tok routed and num_shared shared
        # experts a token (a share holds fewer)
        (spec,) = in_specs
        t, d = spec.shape
        return (self._attention_flops(t, d) + 2 * t * d * self.num_experts
                + (self.experts_per_tok + self.num_shared)
                * 2 * t * 3 * d * self.expert_hidden)


def kimi_k2(num_layers: int, hidden: int, heads: int, q_rank: int,
            latent_dim: int, nope_dim: int, rope_dim: int, v_dim: int,
            dense_hidden: int, seq_len: int, vocab: int, num_experts: int,
            experts_per_tok: int, expert_hidden: int, num_shared: int = 1,
            routed_scale: float = 1.0, dense_layers: int = 1,
            experts_held=None, rope_theta: float = 50000.0,
            rope_factor: float = 1.0, rope_original: int = 4096,
            beta_fast: float = 32.0, beta_slow: float = 1.0,
            mscale_all_dim: float = 1.0, rms_eps: float = 1e-5,
            name: str = "kimi_k2") -> LayerGraph:
    """Causal LM graph: ids [t] -> logits [t, vocab]; ``seq_len`` is the
    number of positions.  The first ``dense_layers`` layers are dense
    (``first_k_dense_replace``), the rest routed; ``experts_held``
    ``(lo, hi)`` makes every routed layer one chip's share of its
    experts.  ``rope_factor`` 1 is plain RoPE; YaRN's frequencies and
    the softmax's scale are computed here, once."""
    if experts_held is not None:
        experts_held = tuple(experts_held)
    attn = dict(
        num_heads=heads, q_rank=q_rank, latent_dim=latent_dim,
        nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
        rope_freqs=yarn_inv_freq(rope_dim, rope_theta, rope_factor,
                                 rope_original, beta_fast, beta_slow),
        softmax_scale=yarn_softmax_scale(nope_dim + rope_dim, rope_factor,
                                         mscale_all_dim),
        rms_eps=rms_eps)
    b = GraphBuilder(name)
    x = b.input((seq_len,), jnp.int32)
    x = b.add(OlmoeEmbedding(vocab, hidden, seq_len), x, name="embeddings")
    for i in range(num_layers):
        op = KimiDenseBlock(hidden=dense_hidden, **attn) \
            if i < dense_layers else KimiMoeBlock(
                num_experts=num_experts, experts_per_tok=experts_per_tok,
                expert_hidden=expert_hidden, num_shared=num_shared,
                routed_scale=routed_scale, experts_held=experts_held, **attn)
        x = b.add(op, x, name=f"block_{i}")
    x = b.add(RMSNorm(eps=rms_eps), x, name="final_ln")
    x = b.add(Dense(vocab, use_bias=False), x, name="lm_head")
    return b.build()


def kimi_k2_tiny(seq_len: int = 32, vocab: int = 211,
                 experts_held=(0, 4)) -> LayerGraph:
    """A dense layer and four routed ones; 4 heads of 16 + 8 over a
    latent of 32; 4 of 16 experts a token, 4 of the 16 held; YaRN from
    8 positions on."""
    return kimi_k2(5, 64, 4, 24, 32, 16, 8, 16, 96, seq_len, vocab, 16, 4,
                   32, routed_scale=2.827, experts_held=experts_held,
                   rope_factor=4.0, rope_original=8, name="kimi_k2_tiny")
