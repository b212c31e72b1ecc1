"""Plain OLMoE forward: float32 ``jax.numpy``, no cache, no kernel.

Follows Muennighoff et al. 2024 (arXiv:2409.02060) and the Hugging Face
``OlmoeForCausalLM``.  Per layer, with ``rms(x, w) = x / sqrt(mean(x^2) +
eps) * w``: ``h = rms(x, w_in)``; ``q = rms(h Wq, w_qn)``, ``k = rms(h Wk,
w_kn)`` (the norms run over all of the projection's columns, before the
head split), ``v = h Wv``; heads split; rotate-half RoPE over the whole
head on q and k; causal ``softmax(q k^T / sqrt(hd)) v``; ``x += (.) Wo``.
Then ``h = rms(x, w_post)``; ``p = softmax(h Wg)`` over all experts; the
``top_k`` largest ``p_e``; ``x += sum_e p_e (silu(h W_gate,e) * (h
W_up,e)) W_down,e`` with ``p_e`` as they are (``norm_topk_prob`` false).
Last ``rms(x, w_norm) W_head``.  The experts are evaluated in a loop
with a mask, all of them on every row: nothing is sorted or grouped, so
this shares no step with the program's dispatch.

Departures from the Hugging Face model, all of layout, taken from the
program under test so that the same weights feed both: a matrix is
stored ``[in, out]`` (``nn.Linear`` stores ``[out, in]``); an expert's
three matrices are stacked over experts as ``experts.gate/up/down``
``[E, in, out]`` (a ``ModuleList`` of ``gate_proj/up_proj/down_proj``
there); the parameter tree is the program's (``embeddings``, ``block_i``,
``final_ln``, ``lm_head``).  QK-norm and its placement are not in
``config.json``; they are the paper's and the modelling code's.

Weights are upcast to float32 here; every product runs at ``highest``
matmul precision, true float32 on a TPU.  One block is jitted and called
layer by layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _rope(x, theta):
    """Rotate-half RoPE on ``x`` [b, h, t, hd], positions 0..t-1."""
    t, hd = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=(
    "n_head", "top_k", "eps", "theta", "qk_norm", "norm_topk_prob"))
def block(p, x, *, n_head: int, top_k: int, eps: float, theta: float,
          qk_norm: bool = True, norm_topk_prob: bool = False):
    """One layer on ``x`` [b, t, d]; also the chosen experts [b, t, k].
    ``qk_norm`` and ``norm_topk_prob`` are OLMoE's as given; the other
    setting of each is there for the tests, which must tell them apart."""
    p = _f32(p)
    b, t, d = x.shape
    hd = d // n_head
    h = _rms(x, p["ln1"]["scale"], eps)
    q, k, v = (_mm(h, p[nm]["w"]) for nm in "qkv")
    if qk_norm:
        q = _rms(q, p["q_norm"]["scale"], eps)
        k = _rms(k, p["k_norm"]["scale"], eps)
    q, k, v = (a.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
               for a in (q, k, v))
    q, k = _rope(q, theta), _rope(k, theta)
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    y = jnp.einsum("bhqk,bhkd->bhqd", att, v, precision=_HI)
    x = x + _mm(y.transpose(0, 2, 1, 3).reshape(b, t, d), p["proj"]["w"])

    h = _rms(x, p["ln2"]["scale"], eps)
    probs = jax.nn.softmax(_mm(h, p["router"]["w"]), axis=-1)   # [b, t, E]
    top_p, chosen = jax.lax.top_k(probs, top_k)
    # p_e where expert e is among the chosen, 0 elsewhere: [b, t, E]
    weight = jnp.where(probs >= top_p[..., -1:], probs, 0.0)
    if norm_topk_prob:
        weight = weight / top_p.sum(-1, keepdims=True)
    ex = p["experts"]

    def one(acc, e):
        g = _mm(h, ex["gate"][e])
        u = _mm(h, ex["up"][e])
        out = _mm(jax.nn.silu(g) * u, ex["down"][e])
        return acc + weight[..., e, None] * out, None

    moe, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          jnp.arange(ex["gate"].shape[0]))
    return x + moe, chosen


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
    return _mm(h, _f32(p_head)["w"])


def logits(params, ids, *, n_layer: int, n_head: int, top_k: int,
           eps: float = 1e-5, theta: float = 10000.0, lo: int = 0,
           experts: bool = False, **block_args):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [b, t] (every position attends causally over the whole of
    ``ids``; ``lo`` only spares the head the positions nobody reads).
    With ``experts`` also each layer's chosen experts, [n_layer, b, t,
    top_k]."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    chosen = []
    for i in range(n_layer):
        x, ch = block(params[f"block_{i}"], x, n_head=n_head, top_k=top_k,
                      eps=eps, theta=theta, **block_args)
        chosen.append(ch)
    out = _head(params["final_ln"], params["lm_head"], x, eps=eps, lo=lo)
    return (out, jnp.stack(chosen)) if experts else out
