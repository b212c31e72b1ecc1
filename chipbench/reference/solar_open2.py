"""Plain Solar-Open2 forward: float32 ``jax.numpy``, the delta rule
**token by token**, the convolutions as explicit sums over shifted
copies, attention as a masked softmax, the experts in a loop with a
mask — no cache, no window buffer, no chunked form, no kernel, nothing
sorted or grouped.  It imports nothing of ``defer_tpu``.

Follows ``Solar-Open2-250B``'s ``config.json`` (``model_type``
``solar_open2``) and, for the KDA layer, Kimi Delta Attention
(arXiv:2510.26692).  With ``rms(a; g) = a / sqrt(mean(a^2) + eps) *
g``, layer ``l`` on the stream ``x`` [t, d]:

    u = rms(x; g_1);   h = x + mixer_l(u)
    a = rms(h; g_2);   y = h + moe_l(a)

**KDA mixer** (every layer outside ``gqa_layers``; ``H`` heads of
``D``, ``k`` taps):

    [q~, k~, v~] = u W_in                    (three parts of H D columns)
    c_t = silu(sum_{j<k} w[j] * [q~, k~, v~]_{t-k+1+j})   (depthwise, no bias)
    q = l2norm(c_q) / sqrt(D);  k = l2norm(c_k);  v = c_v       (a head)
    g_t = -exp(A_log[h]) * softplus(u W_f_down W_f_up + dt_bias)   [H, D]
    beta_t = 2 sigmoid(u W_beta)                                   [H]
    S' = Diag(exp g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t
    mixer = (rms(o_t; g_o) a head * sigmoid(u W_g_down W_g_up)) W_o

What a sequence keeps behind position ``t``: the last ``k - 1`` rows of
``[q~, k~, v~]`` and ``S_t`` ``[H, D, D]`` (:func:`states`).

**GQA mixer** (``gqa_layers``): ``q = u Wq`` (``n_head`` heads of
``hd``), ``k = u Wk``, ``v = u Wv`` (``n_kv`` heads), no bias, no norm
a head, no rotation; every query head of a group reads its KV head;
scores ``q.k / sqrt(hd)``, causal, softmax; ``mixer = (heads(softmax(.)
v) * sigmoid(u W_gate)) Wo``.

**moe**: ``s = sigmoid(a Wr)``; the ``top_k`` largest of ``s + bias``
choose (the bias chooses and never weighs); ``w = s[chosen] /
sum(s[chosen]) * routed_scale``; ``sum_e w_e SwiGLU_e(a)`` over the
experts the tree holds — all of them, or the range ``held`` (one chip's
share: what the absent experts would add is left out) — plus one shared
SwiGLU expert on every row.

After the last layer ``rms(x; g_f)`` and ``logits = h W_head^T``.

Not in the catalog's ``config``, so assumed (the configuration file
lists each under ``assumed``): see there.  Departures from the
published checkpoint, all of layout, taken from the program's parameter
tree: a matrix is ``[in, out]``; ``in_proj`` is ``q_proj | k_proj |
v_proj`` and ``conv/w`` ``[k, 3 H D]`` (taps lead); an expert's three
matrices are stacked over experts as ``experts/gate``, ``up``,
``down``; ``lm_head/w`` is ``[vocab, d]``.

Everything runs at ``highest`` matmul precision, true float32 on a TPU.
A layer is jitted and called layer by layer; attention runs a block of
query rows at a time, the delta rule a position at a time.  The keyword
controls (``state_dtype``, ``decay_a_head``, ``beta_scale``,
``window_shift``, ``delta_reads``, ``gqa_theta``, ``gqa_gate``,
``bias_weighs``, ``inputs``, ``router_dtype``, ``conv_silu``,
``qk_l2norm``, ``out_gate``) each break one thing: the comparisons'
limits are set against them and the tests must tell them apart.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: query rows of one block of the masked softmax
_Q_ROWS = 256
#: under the root of a head's squared sum
L2_EPS = 1e-6


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


def _l2(a):
    return a / jnp.sqrt((a * a).sum(-1, keepdims=True) + L2_EPS)


# -- the KDA mixer ----------------------------------------------------------------

def conv_sum(w, z):
    """``sum_j w[j] * z_{t-k+1+j}`` (``z_{<0} = 0``): ``w`` [k, W] a tap
    a channel, ``z`` [b, t, W] -> ``(the sum [b, t, W], padded)``, the
    shifted copies added one by one, and ``z`` behind its ``k - 1``
    leading zeros."""
    k, t = w.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (k - 1, 0), (0, 0)))
    c = jnp.zeros_like(z)
    for j in range(k):                     # the shifted copies
        c = c + w[j] * padded[:, j:j + t]
    return c, padded


def delta_rule(q, k, v, g, beta, *, delta_reads: bool = True,
               state_dtype=None):
    """The recurrence a position at a time from an empty state: ``q`` /
    ``k`` / ``v`` / ``g`` [b, t, H, D], ``beta`` [b, t, H] -> ``(o [b,
    t, H, D], S [b, H, D, D])``, ``S`` keyed ``[key channel, value
    channel]``.  The controls: ``delta_reads`` false writes ``beta k
    v^T`` without reading the state (a decay-and-add rule);
    ``state_dtype`` rounds the state to that type after every
    position."""
    def step(s, xs):
        qt, kt, vt, gt, bt = xs
        s = jnp.exp(gt)[..., None] * s
        seen = (s * kt[..., None]).sum(-2) if delta_reads else 0.0
        s = s + kt[..., None] * (bt[..., None] * (vt - seen))[..., None, :]
        s = _held(s, state_dtype)
        return s, (s * qt[..., None]).sum(-2)

    b, _, h, d = q.shape
    s, o = jax.lax.scan(step, jnp.zeros((b, h, d, d), jnp.float32), tuple(
        jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return jnp.swapaxes(o, 0, 1), s


def kda_mixer(p, u, *, heads: int, head_dim: int, eps: float, inputs=None,
              conv_silu: bool = True, qk_l2norm: bool = True,
              decay_a_head: bool = False, beta_scale: float = 2.0,
              out_gate: bool = True, window_shift: int = 0,
              delta_reads: bool = True, state_dtype=None):
    """``(mixer(u) [b, t, d], window [b, k - 1, 3 H D], S [b, H, D,
    D])`` of the normed stream ``u`` [b, t, d] under a layer's float32
    weights ``p``.  The controls: ``conv_silu`` false leaves the
    convolutions' sums as they are; ``qk_l2norm`` false leaves ``q`` and
    ``k`` unnormed; ``decay_a_head`` gives every channel of a head the
    head's mean log-decay; ``beta_scale`` 1 keeps ``beta`` in (0, 1);
    ``out_gate`` false drops the output gate; ``window_shift`` hands
    back the window that many positions earlier; ``delta_reads`` and
    ``state_dtype`` are :func:`delta_rule`'s."""
    b, t, _ = u.shape
    k_taps = p["conv"]["w"].shape[0]
    c, padded = conv_sum(p["conv"]["w"], _mm(u, p["in_proj"]["w"], inputs))
    if conv_silu:
        c = jax.nn.silu(c)
    q, k, v = (a.reshape(b, t, heads, head_dim)
               for a in jnp.split(c, 3, axis=-1))
    if qk_l2norm:
        q, k = _l2(q), _l2(k)
    f = _mm(_mm(u, p["f_down"]["w"], inputs), p["f_up"]["w"], inputs)
    g = -jnp.exp(p["decay"]["A_log"])[:, None] * jax.nn.softplus(
        (f + p["decay"]["dt_bias"]).reshape(b, t, heads, head_dim))
    if decay_a_head:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = beta_scale * jax.nn.sigmoid(_mm(u, p["beta"]["w"], inputs))
    o, s = delta_rule(q / math.sqrt(head_dim), k, v, g, beta,
                      delta_reads=delta_reads, state_dtype=state_dtype)
    o = _rms(o, p["o_norm"]["scale"], eps).reshape(b, t, -1)
    if out_gate:
        o = o * jax.nn.sigmoid(_mm(_mm(u, p["g_down"]["w"], inputs),
                                   p["g_up"]["w"], inputs))
    window = padded[:, t - window_shift:t - window_shift + k_taps - 1]
    return _mm(o, p["out_proj"]["w"], inputs), window, s


# -- the GQA mixer ------------------------------------------------------------------

def _rope(x, theta):
    """Rotate-half RoPE on ``x`` [b, h, t, hd], positions 0..t-1 (a
    control: the family rotates nothing)."""
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


def gqa_mixer(p, u, *, n_head: int, n_kv: int, head_dim: int, inputs=None,
              gqa_theta: float | None = None, gqa_gate: bool = True):
    """``mixer(u)`` [b, t, d].  The controls: ``gqa_theta`` lets a
    rotation at that base into queries and keys; ``gqa_gate`` false
    drops the gate on the merged heads."""
    b, t, _ = u.shape

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    q = heads(_mm(u, p["q"]["w"], inputs), n_head)
    k = heads(_mm(u, p["k"]["w"], inputs), n_kv)
    v = heads(_mm(u, p["v"]["w"], inputs), n_kv)
    if gqa_theta is not None:
        q, k = _rope(q, gqa_theta), _rope(k, gqa_theta)
    y = attention(q, k, v, inputs).transpose(0, 2, 1, 3).reshape(b, t, -1)
    if gqa_gate:
        y = y * jax.nn.sigmoid(_mm(u, p["gate"]["w"], inputs))
    return _mm(y, p["proj"]["w"], inputs)


# -- the second half --------------------------------------------------------------

def router(p, a, *, top_k: int, routed_scale: float = 1.0, inputs=None,
           router_dtype=None, bias_weighs: bool = False):
    """``(chosen [..., top_k], weights [..., top_k])`` of the normed
    stream ``a`` [..., d] under the router's float32 leaves ``p``:
    sigmoid scores, the largest of score + bias, the chosen scores over
    their sum, times ``routed_scale``.  The controls: ``router_dtype``
    rounds the logits to that type; ``bias_weighs`` lets the bias into
    the weights."""
    s = jax.nn.sigmoid(_held(_mm(a, p["w"], inputs), router_dtype))
    biased = s + p["bias"]
    _, chosen = jax.lax.top_k(biased, top_k)
    g = jnp.take_along_axis(biased if bias_weighs else s, chosen, axis=-1)
    return chosen, g / g.sum(-1, keepdims=True) * routed_scale


def moe(p, a, *, held=None, inputs=None, shared: bool = True, **route_args):
    """``(sum_e w_e SwiGLU_e(a) + the shared expert [b, t, d], chosen,
    weights)``: the experts the tree stacks in a loop with a mask, every
    one on every row.  With ``held`` = ``(lo, hi)`` the stack is that
    range of the router's columns and the other experts' pairs add
    nothing; ``shared`` false leaves the shared expert out."""
    chosen, g = router(p["router"], a, inputs=inputs, **route_args)
    ex = p["experts"]
    lo = 0 if held is None else held[0]
    columns = p["router"]["w"].shape[-1]
    # w_e where expert e is among the chosen, 0 elsewhere: [b, t, E]
    weight = (jax.nn.one_hot(chosen, columns, dtype=jnp.float32)
              * g[..., None]).sum(-2)

    def swiglu(gate, up, down):
        return _mm(jax.nn.silu(_mm(a, gate, inputs)) * _mm(a, up, inputs),
                   down, inputs)

    def one(acc, e):
        return acc + weight[..., lo + e, None] * swiglu(
            ex["gate"][e], ex["up"][e], ex["down"][e]), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(a),
                          jnp.arange(ex["gate"].shape[0]))
    if shared:
        out = out + swiglu(p["shared_gate"]["w"], p["shared_up"]["w"],
                           p["shared_down"]["w"])
    return out, chosen, g


# -- the model ----------------------------------------------------------------------

_STATIC = ("gqa", "n_head", "n_kv", "head_dim", "kda_heads", "kda_head_dim",
           "top_k", "routed_scale", "held", "eps", "inputs", "conv_silu",
           "qk_l2norm", "decay_a_head", "beta_scale", "out_gate",
           "window_shift", "delta_reads", "state_dtype", "gqa_theta",
           "gqa_gate", "router_dtype", "bias_weighs")


@functools.partial(jax.jit, static_argnames=_STATIC)
def block(p, x, *, gqa: bool, n_head: int, n_kv: int, head_dim: int,
          kda_heads: int, kda_head_dim: int, top_k: int,
          routed_scale: float = 1.0, held=None, eps: float = 1e-5,
          inputs=None, gqa_theta=None, gqa_gate: bool = True,
          router_dtype=None, bias_weighs: bool = False, **kda_controls):
    """One layer on ``x`` [b, t, d]: ``(x, extras)``, ``extras`` a dict
    with ``window`` and ``state`` (a KDA layer's, after the last
    position; None for a GQA layer), ``chosen`` (the router's choices)
    and ``normed`` (the stream the router read)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        u = _rms(x, p["ln1"]["scale"], eps)
        window = state = None
        if gqa:
            mixed = gqa_mixer(p, u, n_head=n_head, n_kv=n_kv,
                              head_dim=head_dim, inputs=inputs,
                              gqa_theta=gqa_theta, gqa_gate=gqa_gate)
        else:
            mixed, window, state = kda_mixer(
                p, u, heads=kda_heads, head_dim=kda_head_dim, eps=eps,
                inputs=inputs, **kda_controls)
        x = x + mixed
        a = _rms(x, p["ln2"]["scale"], eps)
        out, chosen, _ = moe(p, a, held=held, inputs=inputs, top_k=top_k,
                             routed_scale=routed_scale,
                             router_dtype=router_dtype,
                             bias_weighs=bias_weighs)
        return x + out, {"window": window, "state": state,
                         "chosen": chosen, "normed": a}


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "inputs"))
def _head(p_ln, p_head, x, *, eps: float, lo: int, inputs=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_head)["w"].T, inputs)


def forward(params, ids, *, gqa_layers, lo: int = 0, eps: float = 1e-5,
            inputs=None, held=None, **block_args):
    """``(logits [b, t - lo, vocab], extras)`` of ``ids`` [b, t]:
    next-token logits at positions ``lo..t-1`` (every position sees the
    whole of ``ids`` before it; ``lo`` only spares the head the
    positions nobody reads) and every layer's :func:`block` extras.
    ``gqa_layers`` lists the attention layers, every other is KDA;
    ``held`` is the range of routed experts the tree's stacks hold
    (None: all)."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    n_layer = sum(1 for name in params if name.startswith("block_"))
    held = None if held is None else tuple(held)
    extras = []
    for i in range(n_layer):
        x, ex = block(params[f"block_{i}"], x, gqa=i in tuple(gqa_layers),
                      held=held, eps=eps, inputs=inputs, **block_args)
        extras.append(ex)
    return _head(params["final_ln"], params["lm_head"], x, eps=eps, lo=lo,
                 inputs=inputs), extras


def logits(params, ids, **args):
    """Next-token logits [b, t - lo, vocab] (:func:`forward`'s first)."""
    return forward(params, ids, **args)[0]


def states(params, ids, **args):
    """What every layer keeps after the last position of ``ids`` [b,
    t]: per KDA layer ``(S [b, H, D, D], window [b, k - 1, 3 H D])``
    (``S`` keyed ``[key, value]``, the window oldest input first), None
    for a GQA layer."""
    last = jnp.shape(ids)[1] - 1          # spare the head all rows but one
    return [None if ex["state"] is None else (ex["state"], ex["window"])
            for ex in forward(params, ids, lo=last, **args)[1]]
