"""Plain ``longcat_flash`` forward (LongCat-Flash-Chat): float32
``jax.numpy``, **the expanded form only** — the keys and values of all
heads made from the latent, a masked softmax — no cache, no kernel, no
sort, no absorbed products.

Written from the catalog's ``config`` and ``described_as``
(``/opt/skills/guides/model-configs/architectures.jsonl``) and from the
published ``modeling_longcat_flash.py`` as remembered (there is no
network here); every point that is not in ``config`` is an assumption
the configuration file lists.  ``rms(a; g) = a / sqrt(mean(a^2) + eps)
* g``; ``swiglu(a; G, U, W) = (silu(a G) * (a U)) W``.  A layer is a
**double layer**; on the stream ``x`` [T, d]:

```
h1  = x  + MLA_0(rms(x; g_a0))
n1  = rms(h1; g_f0)
s   = MoE(n1)                          # the shortcut
h2  = h1 + swiglu_0(n1)
h3  = h2 + MLA_1(rms(h2; g_a1))
out = h3 + swiglu_1(rms(h3; g_f1)) + s
```

* ``MLA_i(h)``, row ``t``: ``c_q = rms(h W_qa; g_qa)``; a head's ``[q_n
  (nope), q_r (rope)] = c_q W_qb * q_scale``, ``q_scale = (d / q_rank)
  ** 0.5`` (``mla_scale_q_lora``).  ``[c', k'] = h W_kva``; ``c =
  rms(c'; g_kva) * latent_scale``, ``latent_scale = (d / latent) **
  0.5`` (``mla_scale_kv_lora``: on the latent, so on ``k_n`` and ``v``,
  not on ``k_r``); ``k_r = rope(k', t)``, one for all heads; ``q_r <-
  rope(q_r, t)``; ``k_n[i] = W_uk[i] c``, ``v[i] = c W_uv[i]``; scores
  ``(q_n[i] . k_n[i] + q_r[i] . k_r) * (nope + rope) ** -0.5`` over
  rows ``s <= t``, softmax; the heads' ``softmax(.) v`` through ``W_o``.
* **rope** turns the adjacent pairs ``(2j, 2j + 1)`` by ``t * theta **
  (-2j / rope)``; no scaling.
* ``MoE(n)``: ``p = softmax(n W_r)`` in float32 over ``n_experts +
  n_zero`` columns; the ``top_k`` largest of ``p + b``; ``w_e = scale *
  p_e`` of the chosen, **not renormalised**; a chosen ``e < n_experts``
  adds ``w_e swiglu_e(n)``, a chosen ``e >= n_experts`` is a
  zero-compute expert, the identity: it adds ``w_e n``.  No shared
  expert.
* After the last layer ``rms(x; g_f)``, then ``logits = h W_head``.
* **A share.**  With ``held = (lo, hi)`` the parameters hold routed
  experts ``lo..hi-1`` only.  The router, its ``top_k``, its bias and
  its scale stay the whole layer's; the routed sum runs over the chosen
  real ``e`` that are held, every zero pair is added, and that partial
  stream is the next layer's input.

Departures from the published code: the pairing of RoPE (adjacent
pairs where the published code shuffles to halves: it permutes ``q_r``
and ``k_r`` alike and changes no score), ``kv_b_proj`` held as its two
halves a head, no MTP head, text only.

The held experts are evaluated in a loop with a mask over expert ids,
each on every row; attention runs over blocks of queries so that
``heads x T x T`` scores are never held.  This module imports nothing
from the program under test.

Layout, taken from the program so that the same weights feed both: a
matrix is stored ``[in, out]``; a block's tree is ``attn_0`` / ``attn_1``
(``in_ln``, ``q_a``, ``q_a_ln``, ``q_b``, ``kv_a``, ``kv_a_ln``,
``k_up`` ``[heads, nope, latent]``, ``v_up`` ``[heads, latent, v]``,
``proj``; ``q_b.w``'s columns are a head's ``nope`` of ``q_n`` then its
``rope`` of ``q_r``; ``kv_a.w``'s the latent then the shared key),
``ffn_0`` / ``ffn_1`` (``ln``, ``gate``, ``up``, ``down``), ``router``
(``w`` ``[d, n_experts + n_zero]``, ``bias``) and ``experts``
(``gate`` / ``up`` / ``down`` ``[hi - lo, in, out]``); the whole tree
``embeddings`` / ``block_i`` / ``final_ln`` / ``lm_head``.

Weights are upcast to float32 here; every product runs at ``highest``
matmul precision, true float32 on a TPU.  One block is jitted and
called layer by layer.  The controls' arguments, never the check's:
``inputs`` rounds every product's operands to a narrower float
(``float8_e4m3fn`` is the nearest below the configuration's bfloat16),
``row_dtype`` the rows ``[c, k_r]`` alone, as a cache of that type would
keep them, both by ``reduce_precision``, which the compiler may not
drop; ``no_zero_experts`` leaves the zero pairs out; ``renormalise``
divides the chosen weights by their sum; ``bias_weighs`` lets ``b`` into
the weights; ``plain_lora`` leaves both LoRA scales out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
#: queries a block of the attention
_Q_BLOCK = 128


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _rounder(kind):
    """Values as a float of type ``kind`` would hold them."""
    if kind is None:
        return lambda a: a
    info = jnp.finfo(kind)
    return lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)


def _rms(a, g, eps):
    return a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * g


def rope_frequencies(rope: int, theta: float):
    """``theta ** (-2j / rope)``, ``[rope / 2]`` float32."""
    j = jnp.arange(rope // 2, dtype=jnp.float32)
    return jnp.float32(theta) ** (-2.0 * j / rope)


def _rope_pairs(x, freqs):
    """``x`` [b, h, t, rope], positions 0..t-1: the pair ``(x[2j],
    x[2j+1])`` turned by ``t * freqs[j]``."""
    t = x.shape[-2]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, scale: float, rnd=lambda a: a):
    """Causal softmax attention of ``q`` [b, H, t, dk] over ``k`` [b, H,
    t, dk] and ``v`` [b, H, t, dv], scores times ``scale``; a block of
    queries at a time, the mask made from positions."""
    b, nh, t, dk = q.shape
    blocks = -(-t // _Q_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * _Q_BLOCK - t), (0, 0)))
    q = q.reshape(b, nh, blocks, _Q_BLOCK, dk).transpose(2, 0, 1, 3, 4)
    k, v = rnd(k), rnd(v)
    s_pos = jnp.arange(t)[None, :]

    def one(args):
        i, qb = args
        t_pos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)[:, None]
        att = jnp.einsum("bhqd,bhsd->bhqs", rnd(qb), k, precision=_HI) \
            * scale
        att = jax.nn.softmax(jnp.where(s_pos <= t_pos, att, -jnp.inf),
                             axis=-1)
        return jnp.einsum("bhqs,bhsd->bhqd", rnd(att), v, precision=_HI)

    out = jax.lax.map(one, (jnp.arange(blocks), q))
    return out.transpose(1, 2, 0, 3, 4).reshape(
        b, nh, blocks * _Q_BLOCK, -1)[:, :, :t]


def route(h, p_router, *, top_k: int, scale: float, mm,
          renormalise: bool = False, bias_weighs: bool = False):
    """``(chosen [.., k], their weights [.., k], weight by column [..,
    n_experts + n_zero])`` of the normed stream ``h``: the module
    docstring's rule."""
    score = jax.nn.softmax(mm(h, p_router["w"]), axis=-1)
    picked = score + p_router["bias"]
    _, chosen = jax.lax.top_k(picked, top_k)
    w = jnp.take_along_axis(picked if bias_weighs else score, chosen,
                            axis=-1)
    if renormalise:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    w = scale * w
    by_column = jnp.put_along_axis(jnp.zeros_like(score), chosen, w,
                                   axis=-1, inplace=False)
    return chosen, w, by_column


def mla(p, x, freqs, *, n_head: int, nope: int, rope: int, latent: int,
        eps: float, q_scale: float, latent_scale: float, mm, rnd, keep):
    """One latent-attention sublayer on the stream ``x`` [b, t, d]:
    ``(its output through W_o [b, t, d], the rows [b, t, latent +
    rope])``."""
    b, t, _ = x.shape
    h = _rms(x, p["in_ln"]["scale"], eps)
    cq = _rms(mm(h, p["q_a"]["w"]), p["q_a_ln"]["scale"], eps)
    q = (mm(cq, p["q_b"]["w"]) * q_scale).reshape(
        b, t, n_head, nope + rope).transpose(0, 2, 1, 3)
    kv = mm(h, p["kv_a"]["w"])
    c = keep(_rms(kv[..., :latent], p["kv_a_ln"]["scale"], eps)
             * latent_scale)
    k_r = keep(_rope_pairs(kv[:, None, :, latent:], freqs))     # [b, 1, t, r]
    q_r = _rope_pairs(q[..., nope:], freqs)
    k_n = jnp.einsum("btc,hnc->bhtn", rnd(c), rnd(p["k_up"]["w"]),
                     precision=_HI)
    v = jnp.einsum("btc,hcv->bhtv", rnd(c), rnd(p["v_up"]["w"]),
                   precision=_HI)
    y = attention(
        jnp.concatenate([q[..., :nope], q_r], axis=-1),
        jnp.concatenate([k_n, jnp.broadcast_to(
            k_r, (b, n_head, t, rope))], axis=-1), v,
        (nope + rope) ** -0.5, rnd)
    return mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"]), \
        jnp.concatenate([c, k_r[:, 0]], axis=-1)


@functools.partial(jax.jit, static_argnames=(
    "n_head", "nope", "rope", "latent", "q_rank", "n_experts", "top_k",
    "routed_scale", "held", "eps", "inputs", "row_dtype",
    "no_zero_experts", "renormalise", "bias_weighs", "plain_lora"))
def block(p, x, freqs, *, n_head: int, nope: int, rope: int, latent: int,
          q_rank: int, n_experts: int, top_k: int, routed_scale: float,
          held, eps: float, inputs=None, row_dtype=None,
          no_zero_experts: bool = False, renormalise: bool = False,
          bias_weighs: bool = False, plain_lora: bool = False):
    """One double layer on ``x`` [b, t, d]: ``(y, extras)``.  ``extras``
    holds ``rows`` and ``rows_1`` [b, t, latent + rope] (``[c, k_r]`` of
    the two sublayers: all a sequence would keep), ``chosen`` /
    ``weights`` [b, t, k], ``ffn_in`` [b, t, d] (``n1``) and ``shortcut``
    [b, t, d] (``s``).  ``held``
    ``(lo, hi)`` the routed experts ``p`` holds (None: all)."""
    p = _f32(p)
    rnd, keep = _rounder(inputs), _rounder(row_dtype)
    d = x.shape[-1]

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w), precision=_HI)

    def swiglu(a, g, u, w):
        return mm(jax.nn.silu(mm(a, g)) * mm(a, u), w)

    def dense(a, f):
        return swiglu(a, f["gate"]["w"], f["up"]["w"], f["down"]["w"])

    attn = dict(n_head=n_head, nope=nope, rope=rope, latent=latent, eps=eps,
                q_scale=1.0 if plain_lora else (d / q_rank) ** 0.5,
                latent_scale=1.0 if plain_lora else (d / latent) ** 0.5,
                mm=mm, rnd=rnd, keep=keep)
    y, rows_0 = mla(p["attn_0"], x, freqs, **attn)
    h1 = x + y
    n1 = _rms(h1, p["ffn_0"]["ln"]["scale"], eps)

    chosen, weights, by_column = route(
        n1, p["router"], top_k=top_k, scale=routed_scale, mm=mm,
        renormalise=renormalise, bias_weighs=bias_weighs)
    ex = p["experts"]
    lo = 0 if held is None else held[0]

    def one(acc, e):
        f = swiglu(n1, ex["gate"][e], ex["up"][e], ex["down"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            by_column, lo + e, axis=2, keepdims=True) * f, None

    shortcut, _ = jax.lax.scan(one, jnp.zeros_like(x),
                               jnp.arange(ex["gate"].shape[0]))
    if not no_zero_experts:
        # the identity experts: the sum of their weights times n1
        shortcut = shortcut + by_column[..., n_experts:].sum(
            -1, keepdims=True) * n1

    h2 = h1 + dense(n1, p["ffn_0"])
    y, rows_1 = mla(p["attn_1"], h2, freqs, **attn)
    h3 = h2 + y
    out = h3 + dense(_rms(h3, p["ffn_1"]["ln"]["scale"], eps), p["ffn_1"]) \
        + shortcut
    return out, {"rows": rows_0, "rows_1": rows_1, "chosen": chosen,
                 "weights": weights, "ffn_in": n1, "shortcut": shortcut}


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
    return jnp.matmul(h, _f32(p_head)["w"], precision=_HI)


def forward(params, ids, *, n_layer: int, n_head: int, nope: int, rope: int,
            latent: int, q_rank: int, n_experts: int, top_k: int,
            routed_scale: float, theta: float, held=None, eps: float = 1e-5,
            lo: int = 0, keep=(), **control):
    """``(logits [b, t - lo, vocab], extras)`` of ``ids`` [b, t]
    (``lo`` only spares the head the positions nobody reads).  ``extras``
    is a list, a double layer an entry, of what :func:`block` hands back
    under the names in ``keep`` (fetched to the host a layer at a
    time).  ``control``: the module docstring's."""
    held = None if held is None else tuple(held)
    freqs = rope_frequencies(rope, theta)
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    extras = []
    for i in range(n_layer):
        x, ex = block(params[f"block_{i}"], x, freqs, n_head=n_head,
                      nope=nope, rope=rope, latent=latent, q_rank=q_rank,
                      n_experts=n_experts, top_k=top_k,
                      routed_scale=routed_scale, held=held, eps=eps,
                      **control)
        extras.append({nm: jax.device_get(ex[nm]) for nm in keep
                       if nm in ex})
    return _head(params["final_ln"], params["lm_head"], x, eps=eps,
                 lo=lo), extras


def logits(params, ids, **args):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1``."""
    return forward(params, ids, **args)[0]
