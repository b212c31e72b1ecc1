"""Plain ``mellum`` forward (Mellum2-12B-A2.5B-Instruct): float32
``jax.numpy``, no cache, no kernel, no sort.

Written from the published ``config.json`` as the catalog holds it
(``/opt/skills/guides/model-configs/architectures.jsonl``); what it does
not settle is an assumption the configuration file lists, and each
departure from the published description is a comment below.  For layer
``l`` on the stream ``x`` [T, d], row ``t`` of each sequence:

* ``u = x / sqrt(mean x^2 + eps) * g1`` (RMSNorm, ``rms_norm_eps``).
* ``q = u Wq`` (``n_head`` heads of ``head_dim``), ``k = u Wk``, ``v = u
  Wv`` (``n_kv`` heads); no bias (``attention_bias`` false); **no q/k
  norm** (assumed: ``config.json`` names none); query head ``j`` reads
  KV head ``j // (n_head / n_kv)``.
* ``q`` and ``k`` turned over the whole head, pair ``(j, j + hd / 2)``
  (**rotate-half**, assumed: the transformers library's default for
  ``rope_parameters``), by ``t * f_j``, ``cos`` and ``sin`` both times
  ``c``.  A window layer (``layer_types[l] == "sliding_attention"``;
  ``rope_type`` ``default``): ``f_j = e_j = theta ** (-2j / hd)``, ``c
  = 1``; row ``t`` attends rows ``s`` with ``0 <= t - s < window``.  A
  full layer (``rope_type`` ``yarn``): ``f_j = e_j (1 - r_j) + (e_j /
  factor) r_j``, ``r_j = clip((j - lo) / (hi - lo), 0, 1)``, ``lo =
  floor(P(beta_fast))``, ``hi = ceil(P(beta_slow))``, ``P(b) = hd
  ln(original / (2 pi b)) / (2 ln theta)``; ``c`` the config's
  ``attention_factor`` (``0.1 ln(factor) + 1``); rows ``s <= t``.
  Scores ``q . k / sqrt(head_dim)``, softmax.
* ``h = x + heads(softmax(.) v) Wo``.
* ``u2 = rms(h; g2)``; router ``P = softmax(u2 Wr)`` over all
  ``n_experts`` (no bias); the ``top_k`` largest, ``w_e = P_e / sum of
  the chosen P`` (``norm_topk_prob``).
* An expert: ``f_e(u) = (silu(u G_e) * (u U_e)) D_e``; ``y = h + sum_{e
  chosen} w_e f_e(u2)``.  No shared expert; ``intermediate_size`` 7168
  is not read (every ``mlp_layer_types`` entry is ``sparse``); no
  multi-token-prediction head (``config.json`` has no key for one).
* After the last layer RMSNorm, then ``logits = h W_head``: untied.

The routed experts are evaluated in a loop with a mask over expert ids,
one expert's matrices at a time on every row; the window is a mask;
attention runs over blocks of queries so that ``heads x T x T`` scores
are never held.  This module imports nothing from the program under
test: the YaRN table and the router below are its own.

Layout, taken from the program so that the same weights feed both: a
matrix is stored ``[in, out]``; the experts are stacked
``experts.gate/up/down`` ``[E, in, out]``; the tree is ``embeddings`` /
``block_i`` / ``final_ln`` / ``lm_head``.

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


def _rms(a, g, eps):
    return a / jnp.sqrt(jnp.mean(a * a, axis=-1, keepdims=True) + eps) * g


def plain_frequencies(hd: int, theta: float):
    """``e_j = theta ** (-2j / hd)``, ``[hd / 2]`` float32."""
    return jnp.float32(theta) ** (
        -2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)


def yarn_frequencies(hd: int, theta: float, factor: float, original: int,
                     beta_fast: float, beta_slow: float, shift: int = 0):
    """``f_j`` of a full layer (the module docstring), ``[hd / 2]``
    float32.  ``shift`` moves the ramp that many pairs up (the check's
    control: 0 is the published table)."""
    def pair(b):
        return hd * math.log(original / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    lo, hi = math.floor(pair(beta_fast)), math.ceil(pair(beta_slow))
    lo, hi = max(lo, 0) + shift, min(hi, hd // 2 - 1) + shift
    j = jnp.arange(hd // 2, dtype=jnp.float32)
    e = plain_frequencies(hd, theta)
    r = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return e * (1.0 - r) + (e / factor) * r


def rotate(x, pos, freqs, c: float = 1.0, pairing: str = "half"):
    """``x`` [..., t, hd] at positions ``pos`` [t] turned by ``pos *
    freqs[j]``, ``cos`` and ``sin`` times ``c``: rotate-half pairs ``(j,
    j + hd / 2)``; ``pairing="interleaved"`` turns the pairs ``(2j, 2j +
    1)`` instead (the check's control, never the model's)."""
    ang = jnp.asarray(pos, jnp.float32)[:, None] * freqs[None, :]
    cos, sin = c * jnp.cos(ang), c * jnp.sin(ang)
    if pairing == "interleaved":
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer_rotation(kind: str, *, head_dim: int, theta: float, yarn: dict):
    """``(freqs, c)`` of a layer of ``kind``: the plain table and 1 in a
    window layer, YaRN's table and its ``attention_factor`` in a full
    one.  ``yarn``: ``factor``, ``original``, ``beta_fast``,
    ``beta_slow``, and ``attention_factor`` (absent: ``0.1 ln(factor) +
    1``)."""
    if kind == WINDOW_LAYER:
        return plain_frequencies(head_dim, theta), 1.0
    c = yarn.get("attention_factor")
    if c is None:
        c = 0.1 * math.log(yarn["factor"]) + 1.0 if yarn["factor"] > 1 \
            else 1.0
    return yarn_frequencies(head_dim, theta, yarn["factor"],
                            yarn["original"], yarn["beta_fast"],
                            yarn["beta_slow"]), float(c)


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


def route(logits, top_k: int):
    """``(chosen [.., k], weight [.., E])``: a softmax over all experts,
    the ``top_k`` largest, each chosen expert's probability over the
    chosen ones' sum and 0 elsewhere."""
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, chosen = jax.lax.top_k(probs, top_k)
    weight = jnp.where(probs >= top_p[..., -1:], probs, 0.0) \
        / top_p.sum(-1, keepdims=True)
    return chosen, weight


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "head_dim", "top_k", "window", "eps", "c", "inputs"))
def block(p, x, freqs, *, n_head: int, n_kv: int, head_dim: int, top_k: int,
          window, eps: float, c: float = 1.0, inputs=None):
    """One layer on ``x`` [b, t, d]: ``(y, the chosen experts [b, t,
    k])``.  ``window`` None is a full layer; ``freqs`` / ``c`` the
    layer's rotation (:func:`layer_rotation`)."""
    p = _f32(p)
    rnd = _rounder(inputs)

    def mm(a, w):
        return jnp.matmul(rnd(a), rnd(w), precision=_HI)

    b, t, _ = x.shape
    u = _rms(x, p["ln1"]["scale"], eps)

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    pos = jnp.arange(t)
    q = rotate(heads(mm(u, p["q"]["w"]), n_head), pos, freqs, c)
    k = rotate(heads(mm(u, p["k"]["w"]), n_kv), pos, freqs, c)
    v = heads(mm(u, p["v"]["w"]), n_kv)
    y = attention(q, k, v, window, rnd)
    h = x + mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"])

    u2 = _rms(h, p["ln2"]["scale"], eps)
    chosen, weight = route(mm(u2, p["router"]["w"]), top_k)
    ex = p["experts"]

    def one(acc, e):
        f = mm(jax.nn.silu(mm(u2, ex["gate"][e])) * mm(u2, ex["up"][e]),
               ex["down"][e])
        return acc + jax.lax.dynamic_index_in_dim(
            weight, e, axis=2, keepdims=True) * f, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             jnp.arange(ex["gate"].shape[0]))
    return h + routed, chosen


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
    return jnp.matmul(h, _f32(p_head)["w"], precision=_HI)


def logits(params, ids, *, n_layer: int, n_head: int, n_kv: int,
           head_dim: int, top_k: int, layer_types, window: int, yarn: dict,
           eps: float = 1e-6, theta: float = 500000.0, lo: int = 0,
           experts: bool = False, inputs=None):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [b, t] (``lo`` only spares the head the positions nobody
    reads).  ``layer_types`` is the pattern's period and repeats;
    ``yarn`` the full layers' scaling (:func:`layer_rotation`).  With
    ``experts`` also each layer's chosen experts, [n_layer, b, t,
    top_k]."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    chosen = []
    for i in range(n_layer):
        kind = layer_types[i % len(layer_types)]
        freqs, c = layer_rotation(kind, head_dim=head_dim, theta=theta,
                                  yarn=yarn)
        x, ch = block(params[f"block_{i}"], x, freqs, n_head=n_head,
                      n_kv=n_kv, head_dim=head_dim, top_k=top_k,
                      window=window if kind == WINDOW_LAYER else None,
                      eps=eps, c=c, inputs=inputs)
        chosen.append(ch)
    out = _head(params["final_ln"], params["lm_head"], x, eps=eps, lo=lo)
    return (out, jnp.stack(chosen)) if experts else out
