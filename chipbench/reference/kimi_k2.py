"""Plain ``kimi_k2`` forward (Kimi K2, the DeepSeek-V3 block): float32
``jax.numpy``, **the expanded form only** — the keys and values of all
heads made from the latent, a masked softmax — no cache, no kernel, no
sort, no absorbed products.

Written from the published ``config.json`` and the catalog's
``described_as`` (``/opt/skills/guides/model-configs/architectures.jsonl``);
what neither settles is an assumption the configuration file lists.
``rms(a; g) = a / sqrt(mean(a^2) + eps) * g``; ``swiglu(a; G, U, W) =
(silu(a G) * (a U)) W``.  For layer ``l`` on the stream ``x`` [T, d], row
``t`` of each sequence:

* ``h = rms(x; g_in)``; ``c_q = rms(h W_qa; g_qa)``; a head's ``[q_n
  (nope), q_r (rope)] = c_q W_qb``.
* ``[c', k'] = h W_kva`` (``latent``, ``rope`` columns); ``c = rms(c';
  g_kva)``; ``k_r = rope(k', t)``, one for all heads; ``q_r <- rope(q_r,
  t)``.
* ``k_n[i] = W_uk[i] c`` (``[nope, latent]`` a head), ``v[i] = c W_uv[i]``
  (``[latent, v]`` a head): the published ``kv_b_proj``'s two halves.
* scores ``(q_n[i] . k_n[i] + q_r[i] . k_r) * sigma`` over rows ``s <=
  t``, softmax; ``sigma = (nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``.
* ``x <- x + [heads' softmax(.) v] W_o``.
* **rope** turns the adjacent pairs ``(2j, 2j + 1)`` by ``t * f_j``;
  **YaRN**: ``e_j = theta ** (-2j / rope)``, ``f_j = e_j (1 - r_j) + (e_j
  / factor) r_j``, ``r_j = clip((j - lo) / (hi - lo), 0, 1)``, ``lo =
  floor(d(beta_fast))``, ``hi = ceil(d(beta_slow))``, ``d(b) = rope *
  ln(original / (2 pi b)) / (2 ln theta)``.
* ``h' = rms(x; g_ff)``.  The first ``n_dense`` layers: ``x <- x +
  swiglu(h'; G, U, W)``.  The others: ``p = sigmoid(h' W_r)`` over all
  ``n_experts``; the ``top_k`` largest of ``p + b``; ``w_e = scale * p_e
  / (sum of the chosen p + 1e-20)``; ``x <- x + sum_e w_e swiglu_e(h') +
  swiglu_shared(h')``.
* After the last layer ``rms(x; g_f)``, then ``logits = h W_head``.
* **A share.**  With ``held = (lo, hi)`` the parameters hold experts
  ``lo..hi-1`` only.  The router, its ``top_k``, its bias, its
  renormalisation and its scale stay the whole layer's; the routed sum
  runs over the chosen ``e`` that are held, the shared expert is added
  whole, and that partial stream is the next layer's input.

The routed experts are evaluated in a loop with a mask over expert ids,
each held expert on every row; attention runs over blocks of queries so
that ``heads x T x T`` scores are never held.  This module imports
nothing from the program under test (its YaRN is its own copy).

Layout, taken from the program so that the same weights feed both: a
matrix is stored ``[in, out]``; ``q_b.w``'s columns are a head's ``nope``
of ``q_n`` then its ``rope`` of ``q_r``; ``kv_a.w``'s the latent then the
shared key; ``k_up.w`` ``[heads, nope, latent]``, ``v_up.w`` ``[heads,
latent, v]``; the held experts are stacked ``experts.gate/up/down``
``[hi - lo, in, out]``; ``router.w`` ``[d, n_experts]`` and
``router.bias`` ``[n_experts]``; the tree is ``embeddings`` / ``block_i``
/ ``final_ln`` / ``lm_head``.

Weights are upcast to float32 here; every product runs at ``highest``
matmul precision, true float32 on a TPU.  One block a kind is jitted
and called layer by layer.  The controls' arguments, never the check's:
``inputs`` rounds every product's operands to a narrower float
(``float8_e4m3fn`` is the nearest below the configuration's bfloat16),
``row_dtype`` the rows ``[c, k_r]`` alone, as a cache of that type would
keep them, both by ``reduce_precision``, which the compiler may not
drop; ``plain_scale`` leaves ``m ** 2`` out of ``sigma``;
``bias_weighs`` lets ``b`` into the weights.
"""

from __future__ import annotations

import functools
import math

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


def yarn_frequencies(rope: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float):
    """``f_j`` of the module docstring, ``[rope / 2]`` float32."""
    def d(b):
        return rope * math.log(original / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    lo, hi = math.floor(d(beta_fast)), math.ceil(d(beta_slow))
    lo, hi = max(lo, 0), min(hi, rope // 2 - 1)
    j = jnp.arange(rope // 2, dtype=jnp.float32)
    e = jnp.float32(theta) ** (-2.0 * j / rope)
    r = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return e * (1.0 - r) + (e / factor) * r


def softmax_scale(width: int, factor: float, mscale_all_dim: float,
                  plain: bool = False) -> float:
    """``sigma`` of the module docstring (``plain``: without ``m ** 2``,
    the control)."""
    m = 0.1 * mscale_all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return width ** -0.5 * (1.0 if plain else m * m)


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
          bias_weighs: bool = False):
    """``(chosen [.., k], their weights [.., k], weight by expert [..,
    E])`` of the normed stream ``h``: the module docstring's rule."""
    score = jax.nn.sigmoid(mm(h, p_router["w"]))
    picked = score + p_router["bias"]
    _, chosen = jax.lax.top_k(picked, top_k)
    basis = picked if bias_weighs else score
    w = jnp.take_along_axis(basis, chosen, axis=-1)
    w = scale * w / (w.sum(-1, keepdims=True) + 1e-20)
    by_expert = jnp.zeros_like(score)
    by_expert = jnp.put_along_axis(by_expert, chosen, w, axis=-1,
                                   inplace=False)
    return chosen, w, by_expert


@functools.partial(jax.jit, static_argnames=(
    "n_head", "nope", "rope", "latent", "top_k", "routed_scale", "held",
    "eps", "sigma", "inputs", "row_dtype", "bias_weighs"))
def block(p, x, freqs, *, n_head: int, nope: int, rope: int, latent: int,
          top_k: int, routed_scale: float, held, eps: float, sigma: float,
          inputs=None, row_dtype=None, bias_weighs: bool = False):
    """One layer on ``x`` [b, t, d]: ``(y, extras)``.  ``extras`` holds
    ``rows`` [b, t, latent + rope] (``[c, k_r]``: all a sequence would
    keep) and, for a routed layer (one whose ``p`` has a ``router``),
    ``chosen`` / ``weights`` [b, t, k] and ``ffn_in`` [b, t, d] (``h'``).
    ``held`` ``(lo, hi)`` the experts ``p`` holds (None: all)."""
    p = _f32(p)
    rnd, keep = _rounder(inputs), _rounder(row_dtype)

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w), precision=_HI)

    b, t, _ = x.shape
    h = _rms(x, p["in_ln"]["scale"], eps)
    cq = _rms(mm(h, p["q_a"]["w"]), p["q_a_ln"]["scale"], eps)
    q = mm(cq, p["q_b"]["w"]).reshape(b, t, n_head, nope + rope) \
        .transpose(0, 2, 1, 3)
    kv = mm(h, p["kv_a"]["w"])
    c = keep(_rms(kv[..., :latent], p["kv_a_ln"]["scale"], eps))
    k_r = keep(_rope_pairs(kv[:, None, :, latent:], freqs))     # [b, 1, t, r]
    q_r = _rope_pairs(q[..., nope:], freqs)
    k_n = jnp.einsum("btc,hnc->bhtn", rnd(c), rnd(p["k_up"]["w"]),
                     precision=_HI)
    v = jnp.einsum("btc,hcv->bhtv", rnd(c), rnd(p["v_up"]["w"]),
                   precision=_HI)
    y = attention(
        jnp.concatenate([q[..., :nope], q_r], axis=-1),
        jnp.concatenate([k_n, jnp.broadcast_to(
            k_r, (b, n_head, t, rope))], axis=-1), v, sigma, rnd)
    x = x + mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"])
    extras = {"rows": jnp.concatenate([c, k_r[:, 0]], axis=-1)}

    h2 = _rms(x, p["ff_ln"]["scale"], eps)

    def swiglu(a, g, u, w):
        return mm(jax.nn.silu(mm(a, g)) * mm(a, u), w)

    if "router" not in p:
        return x + swiglu(h2, p["gate"]["w"], p["up"]["w"],
                          p["down"]["w"]), extras
    chosen, weights, by_expert = route(
        h2, p["router"], top_k=top_k, scale=routed_scale, mm=mm,
        bias_weighs=bias_weighs)
    ex = p["experts"]
    lo = 0 if held is None else held[0]

    def one(acc, e):
        f = swiglu(h2, ex["gate"][e], ex["up"][e], ex["down"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            by_expert, lo + e, axis=2, keepdims=True) * f, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             jnp.arange(ex["gate"].shape[0]))
    shared = swiglu(h2, p["shared_gate"]["w"], p["shared_up"]["w"],
                    p["shared_down"]["w"])
    extras.update(chosen=chosen, weights=weights, ffn_in=h2)
    return x + routed + shared, extras


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
    return jnp.matmul(h, _f32(p_head)["w"], precision=_HI)


def forward(params, ids, *, n_layer: int, n_head: int, nope: int, rope: int,
            latent: int, top_k: int, routed_scale: float, theta: float,
            factor: float, original: int, beta_fast: float = 32.0,
            beta_slow: float = 1.0, mscale_all_dim: float = 1.0,
            held=None, eps: float = 1e-5, lo: int = 0, keep=(),
            inputs=None, row_dtype=None, plain_scale: bool = False,
            bias_weighs: bool = False):
    """``(logits [b, t - lo, vocab], extras)`` of ``ids`` [b, t]
    (``lo`` only spares the head the positions nobody reads).  ``extras``
    is a list, a layer an entry, of what :func:`block` hands back under
    the names in ``keep`` (fetched to the host a layer at a time)."""
    held = None if held is None else tuple(held)
    freqs = yarn_frequencies(rope, theta, factor, original, beta_fast,
                             beta_slow)
    sigma = softmax_scale(nope + rope, factor, mscale_all_dim, plain_scale)
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    extras = []
    for i in range(n_layer):
        x, ex = block(params[f"block_{i}"], x, freqs, n_head=n_head,
                      nope=nope, rope=rope, latent=latent, top_k=top_k,
                      routed_scale=routed_scale, held=held, eps=eps,
                      sigma=sigma, inputs=inputs, row_dtype=row_dtype,
                      bias_weighs=bias_weighs)
        extras.append({nm: jax.device_get(ex[nm]) for nm in keep
                       if nm in ex})
    return _head(params["final_ln"], params["lm_head"], x, eps=eps,
                 lo=lo), extras


def logits(params, ids, **args):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1``."""
    return forward(params, ids, **args)[0]
