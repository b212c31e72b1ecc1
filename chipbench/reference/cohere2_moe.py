"""Plain ``cohere2_moe`` forward (command-a-plus): float32 ``jax.numpy``,
no cache, no kernel, no sort.

Written from the published ``config.json`` and the catalog's
``described_as`` (``/opt/skills/guides/model-configs/architectures.jsonl``);
what neither settles is an assumption the configuration file lists.
For layer ``l`` on the stream ``x`` [T, d], row ``t`` of each sequence:

* ``h = (x - mean x) / sqrt(var x + eps) * g``: LayerNorm, a scale and
  no bias; **one** norm feeds both branches (``use_parallel_block``).
* ``q = h Wq`` (``n_head`` heads of ``head_dim``), ``k = h Wk``, ``v = h
  Wv`` (``n_kv`` heads); no bias, no QK-norm; query head ``j`` reads KV
  head ``j // (n_head / n_kv)``.
* A window layer (``layer_types[l] == "sliding_attention"``): ``q`` and
  ``k`` turned by interleaved RoPE over the whole head (pairs ``(2i,
  2i + 1)``, angle ``t * theta ** (-2i / head_dim)``); row ``t`` attends
  rows ``s`` with ``0 <= t - s < window``.  A full layer: no rotation,
  rows ``s <= t``.  Scores ``q . k / sqrt(head_dim)``, softmax.
* ``a = heads(softmax(.) v) Wo``.
* Router: ``s = sigmoid(h Wr)`` over all ``n_experts``; the ``top_k``
  largest, ``w_e = s_e / sum of the chosen s``.
* An expert, routed or shared: ``f(h) = (silu(h G) * (h U)) D``.
* ``m = sum_{e chosen} w_e f_e(h) + (1 / n_shared) sum_j f_shared_j(h)``.
* ``y = x + a + m``.
* After the last layer the same LayerNorm, then ``logits = logit_scale
  * h Wte^T``: the head is the embedding's table.
* **A share.**  With ``held = (lo, hi)`` the parameters hold experts
  ``lo..hi-1`` only.  The router, its top ``k`` and its renormalisation
  stay the whole layer's; ``m`` sums ``w_e f_e(h)`` over the chosen
  ``e`` that are held and adds the shared term, and that partial ``y``
  is the next layer's input.

The routed experts are evaluated in a loop with a mask over expert
ids, each held expert on every row; the window is a mask; attention
runs over blocks of queries so that ``heads x T x T`` scores are never
held.  This module imports nothing from the program under test.

Layout, taken from the program so that the same weights feed both: a
matrix is stored ``[in, out]``; the held experts are stacked
``experts.gate/up/down`` ``[hi - lo, in, out]``; the shared experts lie
side by side, expert ``j`` the columns ``j*w..(j+1)*w - 1`` of
``shared_gate.w`` / ``shared_up.w`` and the same rows of
``shared_down.w``; the tree is ``embeddings`` / ``block_i`` /
``final_ln`` / ``lm_head`` and ``lm_head`` is not read.

Weights are upcast to float32 here; every product runs at ``highest``
matmul precision, true float32 on a TPU.  One block is jitted and
called layer by layer.  ``inputs`` rounds every product's operands to a
narrower float (the check's control: ``float8_e4m3fn`` is the nearest
below the configuration's bfloat16), by ``reduce_precision``, which the
compiler may not drop.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
WINDOW_LAYER = "sliding_attention"
#: queries a block of the attention
_Q_BLOCK = 128


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _rounder(inputs):
    """Operands as a float of type ``inputs`` would hold them."""
    if inputs is None:
        return lambda a: a
    kind = jnp.finfo(inputs)
    return lambda a: jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


def _layer_norm(x, g, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g


def _rope_pairs(x, theta):
    """Interleaved RoPE on ``x`` [b, h, t, hd], positions 0..t-1: the
    pair ``(x[2i], x[2i+1])`` turned by ``t * theta ** (-2i / hd)``."""
    t, hd = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(q, k, v, window=None, rnd=lambda a: a):
    """Causal softmax attention of ``q`` [b, H, t, hd] over ``k`` / ``v``
    [b, Hkv, t, hd], query head ``j`` on KV head ``j // (H / Hkv)``;
    with ``window`` row ``t`` sees rows ``s``, ``0 <= t - s < window``.
    A block of queries at a time, the mask made from positions."""
    b, nh, t, hd = q.shape
    kv = k.shape[1]
    blocks = -(-t // _Q_BLOCK)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, blocks * _Q_BLOCK - t), (0, 0)))
    # [blocks, b, kv, group, block, hd]: head j = (j // group, j % group)
    q = q.reshape(b, kv, nh // kv, blocks, _Q_BLOCK, hd).transpose(
        3, 0, 1, 2, 4, 5)
    k, v = rnd(k), rnd(v)
    s_pos = jnp.arange(t)[None, :]

    def one(args):
        i, qb = args
        t_pos = i * _Q_BLOCK + jnp.arange(_Q_BLOCK)[:, None]
        seen = s_pos <= t_pos
        if window is not None:
            seen = jnp.logical_and(seen, t_pos - s_pos < window)
        att = jnp.einsum("bkgqd,bksd->bkgqs", rnd(qb), k, precision=_HI) \
            / math.sqrt(hd)
        att = jax.nn.softmax(jnp.where(seen, att, -jnp.inf), axis=-1)
        return jnp.einsum("bkgqs,bksd->bkgqd", rnd(att), v, precision=_HI)

    out = jax.lax.map(one, (jnp.arange(blocks), q))
    return out.transpose(1, 2, 3, 0, 4, 5).reshape(
        b, nh, blocks * _Q_BLOCK, hd)[:, :, :t]


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "head_dim", "top_k", "n_shared", "window", "held",
    "eps", "theta", "inputs"))
def block(p, x, *, n_head: int, n_kv: int, head_dim: int, top_k: int,
          n_shared: int, window, held, eps: float, theta: float,
          inputs=None):
    """One layer on ``x`` [b, t, d]: ``(y, the chosen experts [b, t,
    k])``.  ``window`` None is a full layer; ``held`` ``(lo, hi)`` the
    experts ``p`` holds (None: all)."""
    p = _f32(p)
    rnd = _rounder(inputs)

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w), precision=_HI)

    b, t, _ = x.shape
    h = _layer_norm(x, p["ln"]["scale"], eps)

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    q = heads(mm(h, p["q"]["w"]), n_head)
    k = heads(mm(h, p["k"]["w"]), n_kv)
    v = heads(mm(h, p["v"]["w"]), n_kv)
    if window is not None:
        q, k = _rope_pairs(q, theta), _rope_pairs(k, theta)
    y = attention(q, k, v, window, rnd)
    a = mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"])

    score = jax.nn.sigmoid(mm(h, p["router"]["w"]))            # [b, t, E]
    top_s, chosen = jax.lax.top_k(score, top_k)
    # s_e / (sum of the chosen) where expert e is chosen, 0 elsewhere
    weight = jnp.where(score >= top_s[..., -1:], score, 0.0) \
        / top_s.sum(-1, keepdims=True)
    ex = p["experts"]
    lo = 0 if held is None else held[0]

    def one(acc, e):
        f = mm(jax.nn.silu(mm(h, ex["gate"][e])) * mm(h, ex["up"][e]),
               ex["down"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            weight, lo + e, axis=2, keepdims=True) * f, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x),
                             jnp.arange(ex["gate"].shape[0]))
    width = p["shared_gate"]["w"].shape[1] // n_shared
    shared = jnp.zeros_like(x)
    for j in range(n_shared):
        cols = slice(j * width, (j + 1) * width)
        shared = shared + mm(
            jax.nn.silu(mm(h, p["shared_gate"]["w"][:, cols]))
            * mm(h, p["shared_up"]["w"][:, cols]),
            p["shared_down"]["w"][cols])
    return x + a + routed + shared / n_shared, chosen


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "scale"))
def _head(p_ln, p_embed, x, *, eps: float, lo: int, scale: float):
    h = _layer_norm(x[:, lo:], _f32(p_ln)["scale"], eps)
    return scale * jnp.matmul(h, _f32(p_embed)["wte"].T, precision=_HI)


def logits(params, ids, *, n_layer: int, n_head: int, n_kv: int,
           head_dim: int, top_k: int, n_shared: int, layer_types,
           window: int, held=None, eps: float = 1e-5,
           theta: float = 50000.0, logit_scale: float = 1.0, lo: int = 0,
           experts: bool = False, inputs=None):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [b, t] (``lo`` only spares the head the positions nobody
    reads).  ``layer_types`` is the pattern's period and repeats.  With
    ``experts`` also each layer's chosen experts, [n_layer, b, t,
    top_k]."""
    held = None if held is None else tuple(held)
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    chosen = []
    for i in range(n_layer):
        kind = layer_types[i % len(layer_types)]
        x, ch = block(params[f"block_{i}"], x, n_head=n_head, n_kv=n_kv,
                      head_dim=head_dim, top_k=top_k, n_shared=n_shared,
                      window=window if kind == WINDOW_LAYER else None,
                      held=held, eps=eps, theta=theta, inputs=inputs)
        chosen.append(ch)
    out = _head(params["final_ln"], params["embeddings"], x, eps=eps, lo=lo,
                scale=logit_scale)
    return (out, jnp.stack(chosen)) if experts else out
