"""Plain LFM2-MoE forward: float32 ``jax.numpy``, the convolution as an
explicit sum over shifted copies, attention as a masked softmax, the
experts in a loop with a mask — no cache, no window buffer, no kernel,
nothing sorted or grouped.  It imports nothing of ``defer_tpu``.

Follows ``LFM2-24B-A2B``'s ``config.json`` (``model_type`` ``lfm2_moe``;
the family's published modelling code is ``transformers``' ``Lfm2Moe``).
With ``rms(a; g) = a / sqrt(mean(a^2) + eps) * g``, layer ``l`` of kind
``layer_types[l]`` on the stream ``x`` [t, d]:

    u = rms(x; g_op);   h = x + mixer_l(u)            (operator_norm)
    a = rms(h; g_ffn);  y = h + ffn_l(a)              (ffn_norm)

**conv mixer** (``conv_L_cache`` = ``k`` taps, ``conv_bias`` false):

    [B, C, X] = u W_in                  (three equal parts of 3 d columns)
    z_t = B_t * X_t
    c_t = sum_{j<k} w[j] * z_{t-k+1+j}  (z_{<0} = 0; depthwise: a tap a
                                         channel; no bias, no activation)
    mixer = (C_t * c_t) W_out

What a sequence keeps behind position ``t``: ``z_{t-k+2} .. z_t``
(:func:`states`: ``[b, k - 1, d]``, oldest first).

**full_attention mixer**: ``q = u Wq`` (``H`` heads of ``hd``), ``k = u
Wk``, ``v = u Wv`` (``K`` heads), no bias; ``q`` and ``k`` RMS-normed a
head over its ``hd`` columns (weights ``[hd]``); rotate-half RoPE over
the whole head at ``theta``; every query head of a group reads its KV
head; scores ``q.k / sqrt(hd)``, causal, softmax; ``mixer =
heads(softmax(.) v) Wo``.

**ffn**, the first ``dense_layers`` layers: ``(silu(a W1) * (a W3))
W2``.  Behind them: ``s = sigmoid(a Wr)``; the ``top_k`` largest of ``s +
expert_bias`` choose (the bias chooses and never weighs); ``g = s[chosen]
/ (sum(s[chosen]) + 1e-6) * routed_scale``; ``sum_e g_e (silu(a W1_e) *
(a W3_e)) W2_e``.  No shared expert.

After the last layer ``rms(x; g_f)`` (the family's ``embedding_norm``)
and ``logits = h Wte^T`` (tied).

Not in the catalog's ``config``, so assumed (the configuration file
lists each under ``assumed``): the tied head; RoPE's pairing by halves;
``hd = hidden / heads``.  Departures from the Hugging Face model, all of
layout, taken from the program's parameter tree: a matrix is ``[in,
out]``; ``conv/w`` is ``[k, d]`` (taps lead); an expert's three matrices
are stacked over experts as ``experts/gate`` (``w1``), ``up`` (``w3``),
``down`` (``w2``); ``router/bias`` is ``expert_bias``; ``lm_head/w`` is
the embedding's table.

Everything runs at ``highest`` matmul precision, true float32 on a TPU.
A layer is jitted and called layer by layer; attention runs a block of
query rows at a time.  The keyword controls (``window_shift``,
``b_gate``, ``conv_silu``, ``conv_dtype``, ``router_dtype``, ``inputs``,
``bias_weighs``) each break one thing: the comparisons' limits are set
against them and the tests must tell them apart.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: query rows of one block of the masked softmax
_Q_ROWS = 256
#: the divisor's term when the chosen scores are renormalised
ROUTE_EPS = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _held(a, dtype):
    """``a`` rounded to ``dtype`` (by ``reduce_precision``: a cast there
    and back is the compiler's to drop); None: as it is."""
    if dtype is None:
        return a
    kind = jnp.finfo(dtype)
    return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


def _mm(a, b, inputs=None):
    """Every matrix product of this file (with :func:`_ein`).  With
    ``inputs`` both operands are rounded to that float type first: what
    a precision below the configuration's would give."""
    return jnp.matmul(_held(a, inputs), _held(b, inputs))


def _ein(spec, a, b, inputs=None):
    return jnp.einsum(spec, _held(a, inputs), _held(b, inputs))


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


# -- the convolution mixer ------------------------------------------------------

def conv_sum(w, z, conv_dtype=None):
    """``c_t = sum_j w[j] * z_{t-k+1+j}`` (``z_{<0} = 0``): ``w`` [k, d]
    a tap a channel, ``z`` [b, t, d] -> ``(c [b, t, d], padded)``, the
    three shifted copies summed one by one, and ``z`` behind its ``k -
    1`` leading zeros.  ``conv_dtype`` rounds every term and every
    partial sum to that type: what a sum kept below float32 gives."""
    k, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    c = jnp.zeros_like(z)
    for j in range(k):                     # the shifted copies
        c = _held(c + _held(w[j] * padded[:, j:j + t], conv_dtype),
                  conv_dtype)
    return c, padded


def conv_mixer(p, u, *, inputs=None, b_gate: bool = True,
               conv_silu: bool = False, conv_dtype=None,
               window_shift: int = 0):
    """``(mixer(u) [b, t, d], window [b, k - 1, d])`` of the normed
    stream ``u`` [b, t, d] under a layer's float32 weights ``p``.  The
    controls: ``b_gate`` false feeds the convolution ``X`` alone;
    ``conv_silu`` passes its sum through a ``silu`` (a state-space
    mixer's convolution); ``conv_dtype`` is :func:`conv_sum`'s;
    ``window_shift`` hands back the window that many positions
    earlier."""
    k = p["conv"]["w"].shape[0]
    t = u.shape[1]
    bcx = _mm(u, p["in_proj"]["w"], inputs)
    b_in, c_gate, x_in = jnp.split(bcx, 3, axis=-1)
    c, padded = conv_sum(p["conv"]["w"], b_in * x_in if b_gate else x_in,
                         conv_dtype)
    if conv_silu:
        c = jax.nn.silu(c)
    window = padded[:, t - window_shift:t - window_shift + k - 1]
    return _mm(c_gate * c, p["out_proj"]["w"], inputs), window


# -- the attention mixer ----------------------------------------------------------

def _rope(x, theta):
    """Rotate-half RoPE on ``x`` [b, h, t, hd], positions 0..t-1."""
    t, hd = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def attention(q, k, v, inputs=None):
    """Causal softmax attention: ``q`` [b, H, t, hd] against ``k`` /
    ``v`` [b, K, t, hd] -> [b, H, t, hd], a block of query rows at a
    time."""
    b, n_head, t, hd = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(b, n_kv, n_head // n_kv, t, hd)
    outs = []
    for lo in range(0, t, _Q_ROWS):
        rows = qg[:, :, :, lo:lo + _Q_ROWS]
        score = _ein("bkgqd,bkud->bkgqu", rows, k, inputs) / math.sqrt(hd)
        live = (lo + jnp.arange(rows.shape[3]))[:, None] \
            >= jnp.arange(t)[None, :]
        w = jax.nn.softmax(jnp.where(live, score, -jnp.inf), axis=-1)
        outs.append(_ein("bkgqu,bkud->bkgqd", w, v, inputs))
    return jnp.concatenate(outs, axis=3).reshape(b, n_head, t, hd)


def attention_mixer(p, u, *, n_head: int, n_kv: int, head_dim: int,
                    theta: float, eps: float, inputs=None,
                    qk_norm: bool = True):
    """``mixer(u)`` [b, t, d]; ``qk_norm`` false leaves the two norms a
    head out (there for the tests, which must tell them apart)."""
    b, t, _ = u.shape

    def heads(a, n):
        return a.reshape(b, t, n, head_dim)

    q = heads(_mm(u, p["q"]["w"], inputs), n_head)
    k = heads(_mm(u, p["k"]["w"], inputs), n_kv)
    v = heads(_mm(u, p["v"]["w"], inputs), n_kv)
    if qk_norm:
        q = _rms(q, p["q_norm"]["scale"], eps)
        k = _rms(k, p["k_norm"]["scale"], eps)
    q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    y = attention(_rope(q, theta), _rope(k, theta), v, inputs)
    return _mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"],
               inputs)


# -- the second half --------------------------------------------------------------

def router(p, a, *, top_k: int, routed_scale: float = 1.0,
           route_eps: float = ROUTE_EPS, inputs=None, router_dtype=None,
           bias_weighs: bool = False):
    """``(chosen [..., top_k], weights [..., top_k])`` of the normed
    stream ``a`` [..., d] under the router's float32 leaves ``p``:
    sigmoid scores, the largest of score + bias, the chosen scores over
    their sum plus ``route_eps``, times ``routed_scale``.  The
    controls: ``router_dtype`` rounds the logits to that type;
    ``bias_weighs`` lets the bias into the weights."""
    s = jax.nn.sigmoid(_held(_mm(a, p["w"], inputs), router_dtype))
    biased = s + p["bias"]
    _, chosen = jax.lax.top_k(biased, top_k)
    g = jnp.take_along_axis(biased if bias_weighs else s, chosen, axis=-1)
    return chosen, g / (g.sum(-1, keepdims=True) + route_eps) * routed_scale


def routed_ffn(p, a, *, inputs=None, **route_args):
    """``(sum_e g_e SwiGLU_e(a) [b, t, d], chosen, weights)``: the
    experts in a loop with a mask, every one on every row."""
    chosen, g = router(p["router"], a, inputs=inputs, **route_args)
    ex = p["experts"]
    n_experts = ex["gate"].shape[0]
    # g_e where expert e is among the chosen, 0 elsewhere: [b, t, E]
    weight = (jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)
              * g[..., None]).sum(-2)

    def one(acc, e):
        out = _mm(jax.nn.silu(_mm(a, ex["gate"][e], inputs))
                  * _mm(a, ex["up"][e], inputs), ex["down"][e], inputs)
        return acc + weight[..., e, None] * out, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(a), jnp.arange(n_experts))
    return out, chosen, g


def dense_ffn(p, a, inputs=None):
    return _mm(jax.nn.silu(_mm(a, p["mlp_gate"]["w"], inputs))
               * _mm(a, p["mlp_up"]["w"], inputs), p["mlp_down"]["w"],
               inputs)


# -- the model ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "kind", "dense", "n_head", "n_kv", "head_dim", "top_k", "routed_scale",
    "theta", "eps", "route_eps", "inputs", "b_gate", "conv_silu",
    "conv_dtype", "window_shift", "router_dtype", "bias_weighs", "qk_norm"))
def block(p, x, *, kind: str, dense: bool, n_head: int, n_kv: int,
          head_dim: int, top_k: int, routed_scale: float = 1.0,
          theta: float = 1000000.0, eps: float = 1e-5,
          route_eps: float = ROUTE_EPS, inputs=None, b_gate: bool = True,
          conv_silu: bool = False, conv_dtype=None, window_shift: int = 0,
          router_dtype=None, bias_weighs: bool = False,
          qk_norm: bool = True):
    """One layer on ``x`` [b, t, d]: ``(x, extras)``, ``extras`` a dict
    with ``window`` (a conv layer's, after the last position) and
    ``chosen`` (a routed layer's choices); None where the layer has
    none."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        u = _rms(x, p["ln1"]["scale"], eps)
        window = None
        if kind == "conv":
            mixed, window = conv_mixer(
                p, u, inputs=inputs, b_gate=b_gate, conv_silu=conv_silu,
                conv_dtype=conv_dtype, window_shift=window_shift)
        else:
            mixed = attention_mixer(
                p, u, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
                theta=theta, eps=eps, inputs=inputs, qk_norm=qk_norm)
        x = x + mixed
        a = _rms(x, p["ln2"]["scale"], eps)
        chosen = None
        if dense:
            x = x + dense_ffn(p, a, inputs)
        else:
            out, chosen, _ = routed_ffn(
                p, a, inputs=inputs, top_k=top_k, routed_scale=routed_scale,
                route_eps=route_eps, router_dtype=router_dtype,
                bias_weighs=bias_weighs)
            x = x + out
        return x, {"window": window, "chosen": chosen}


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "inputs"))
def _head(p_ln, p_embed, x, *, eps: float, lo: int, inputs=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_embed)["wte"].T, inputs)


def forward(params, ids, *, layer_types, dense_layers: int, lo: int = 0,
            eps: float = 1e-5, inputs=None, **block_args):
    """``(logits [b, t - lo, vocab], extras)`` of ``ids`` [b, t]:
    next-token logits at positions ``lo..t-1`` (every position sees the
    whole of ``ids`` before it; ``lo`` only spares the head the
    positions nobody reads) and every layer's :func:`block` extras.
    ``layer_types`` names each layer (a shorter list repeats); the
    first ``dense_layers`` end in the dense SwiGLU.  The head is the
    embedding's table."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    n_layer = sum(1 for name in params if name.startswith("block_"))
    extras = []
    for i in range(n_layer):
        x, ex = block(params[f"block_{i}"], x,
                      kind=layer_types[i % len(layer_types)],
                      dense=i < dense_layers, eps=eps, inputs=inputs,
                      **block_args)
        extras.append(ex)
    return _head(params["final_ln"], params["embeddings"], x, eps=eps,
                 lo=lo, inputs=inputs), extras


def logits(params, ids, **args):
    """Next-token logits [b, t - lo, vocab] (:func:`forward`'s first)."""
    return forward(params, ids, **args)[0]


def states(params, ids, **args):
    """What every layer keeps after the last position of ``ids`` [b,
    t]: per layer the window ``[b, k - 1, d]`` (oldest input first),
    None for an attention layer."""
    last = jnp.shape(ids)[1] - 1          # spare the head all rows but one
    return [ex["window"] for ex in forward(params, ids, lo=last, **args)[1]]
