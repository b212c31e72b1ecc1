"""Plain Granite 4.0-H forward: float32 ``jax.numpy``, the Mamba-2
recurrence position by position, the convolution as shifted products,
attention as a masked softmax, the experts in a masked loop — no cache,
no kernel, no chunking.

Follows ``granite-4.0-h-small``'s ``config.json`` (``model_type``
``granitemoehybrid``) and Mamba-2 (Dao & Gu, arXiv:2405.21060).  With
``rms(a; g) = a / sqrt(mean(a^2) + eps) * g``, the stream ``x`` [t, D]
starts as ``embedding_multiplier * Wte[id]``, and for layer ``l``:

    h = rms(x; g_ln1);   x <- x + residual_multiplier * mixer_l(h)
    h' = rms(x; g_ln2);  x <- x + residual_multiplier * (routed(h') + shared(h'))

**Attention mixer** (where ``layer_types`` says ``attention``): ``q = h
Wq`` (``H`` heads of ``d``), ``k = h Wk``, ``v = h Wv`` (``K`` heads of
``d``), no bias, no QK-norm, no rotation and no position of any kind;
every query head of a group reads its KV head; scores ``q.k *
attention_multiplier``, causal, softmax; ``mixer = heads(softmax(.) v)
Wo``.

**Mamba-2 mixer** (every other layer): ``heads`` heads of ``P``
channels (``E = heads x P``), ``N`` states, ``k`` taps, per position
``t``:

    [z_t, u_t, r_t] = h_t W_in            z [E], u [E + 2N], r [heads]
    c_t = silu(b_conv + sum_{j<k} w_conv[j] * u_{t-k+1+j})     (u_{<0} = 0)
    [x_t, B_t, C_t] = c_t                 x [heads, P], B, C [N]
    dt_t = softplus(r_t + b_dt);  a = -exp(A_log)               [heads]
    H_t[h] = exp(dt_t[h] a[h]) * H_{t-1}[h] + dt_t[h] * x_t[h] (x) B_t
    y_t[h] = H_t[h] C_t + D[h] * x_t[h]
    mixer = rms(y_t * silu(z_t); g_norm) W_out       (the gate, then the norm)

**Experts** (every layer): ``s = h' W_r`` over all experts; the ``k``
largest ``s``; weights ``softmax`` over those ``k`` values; expert
``e``: ``(silu(h' G_e) * (h' U_e)) W_e``; ``routed`` sums the chosen
experts *of the range the layer holds* (``held``; the rest of the sum
is other chips' and is left out, here as in the program); ``shared =
(silu(h' G_s) * (h' U_s)) W_s`` for every token, weight 1.

After the last layer ``rms(x; g_f)`` and ``logits = (h Wte^T) /
logits_scaling`` (tied).

Not in ``config.json``, so assumed (the configuration file lists each
under ``assumed``): a head's width ``hidden / heads``; an expert's
width; which half of the fused input matrix passes the ``silu``; the
gate before the norm.  Departures from the Hugging Face model, all of
layout, taken from the program's parameter tree: one node a layer;
``conv/w`` is ``[k, E + 2N]``; an expert's input matrix is ``gate`` and
``up``; ``lm_head/w`` is the embedding's table.  Here the state is ``H
[b, heads, P, N]`` and the window ``[b, k - 1, E + 2N]`` (oldest input
first): the forms that know no layout.

:func:`states` gives what every Mamba layer's recurrence holds after the
last position; :func:`selective_scan` and :func:`explicit_state` are the
recurrence and its closed form on given inputs, for the long-memory
probe.  Everything runs at ``highest`` matmul precision, true float32
on a TPU.  A layer is jitted and called layer by layer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: query rows of one block of the masked softmax
_Q_ROWS = 256


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
    a precision below the configuration's would give, the control the
    token limit is set against."""
    return jnp.matmul(_held(a, inputs), _held(b, inputs))


def _ein(spec, a, b, inputs=None):
    return jnp.einsum(spec, _held(a, inputs), _held(b, inputs))


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


# -- the state-space mixer ------------------------------------------------------

def selective_scan(dt, x, b, c, a, *, state_dtype=None):
    """The recurrence position by position from an empty state: ``dt``
    [B, t, heads], ``x`` [B, t, heads, P], ``b`` / ``c`` [B, t, N], ``a``
    [heads] -> ``(y [B, t, heads, P], H [B, heads, P, N])``, ``y_t =
    H_t c_t`` and ``H`` after the last position.  With ``state_dtype``
    the state is rounded to that type after every position and read
    rounded (what a memory kept below float32 would give: the control
    the limits are set against)."""
    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs
        h = _held(jnp.exp(dt_t * a)[:, :, None, None] * h
                  + (dt_t[:, :, None] * x_t)[..., None]
                  * b_t[:, None, None, :], state_dtype)
        return h, (h * c_t[:, None, None, :]).sum(-1)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    last, ys = jax.lax.scan(step, start, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), last


def explicit_state(dt, x, b, a):
    """What the recurrence holds after the last position, as the
    explicit sum ``H[h, p, n] = sum_u exp(a[h] sum_{r > u} dt_r[h])
    dt_u[h] x_u[h, p] b_u[n]``: shapes as :func:`selective_scan`, a
    sequence at a time, one product over the positions."""
    def one(args):
        dt_s, x_s, b_s = args                  # [t, H], [t, H, P], [t, N]
        cum = jnp.cumsum(dt_s, axis=0)
        w = jnp.exp((cum[-1:] - cum) * a) * dt_s           # [t, H]
        return jnp.einsum("thp,tn->hpn", w[..., None] * x_s, b_s)

    return jax.lax.map(one, (dt, x, b))


def conv_taps(u, k: int):
    """The ``k`` shifted copies of ``u`` [B, t, W] a causal convolution
    reads, oldest first; zero before the sequence's start."""
    t = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return [padded[:, j:j + t] for j in range(k)]


def mamba_mixer(p, h, *, mamba_heads: int, d_state: int, eps: float,
                state_dtype=None, window_shift: int = 0, inputs=None):
    """``(mixer(h) [B, t, D], (H [B, heads, P, N], window [B, k-1, E +
    2N]))`` of the normed stream ``h`` [B, t, D] under a layer's float32
    weights ``p``.  ``window_shift`` is a control's: the window handed
    back is the one that many positions earlier."""
    nh, n = mamba_heads, d_state
    k, w = p["conv"]["w"].shape
    e = w - 2 * n
    zur = _mm(h, p["in_proj"]["w"], inputs)
    z, u, r = zur[..., :e], zur[..., e:e + w], zur[..., e + w:]
    acc = p["conv"]["b"]
    for j, tap in enumerate(conv_taps(u, k)):
        acc = acc + p["conv"]["w"][j] * tap
    c = jax.nn.silu(acc)
    bsz, t = h.shape[:2]
    x = c[..., :e].reshape(bsz, t, nh, e // nh)
    b_in, c_read = c[..., e:e + n], c[..., e + n:]
    dt = jax.nn.softplus(r + p["ssm"]["dt_bias"])
    a = -jnp.exp(p["ssm"]["a_log"])
    y, last = selective_scan(dt, x, b_in, c_read, a, state_dtype=state_dtype)
    y = (y + p["ssm"]["d"][:, None] * x).reshape(bsz, t, e)
    g = _rms(y * jax.nn.silu(z), p["gate_norm"]["scale"], eps)
    end = u.shape[1] - window_shift
    window = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))[:, end:end + k - 1]
    return _mm(g, p["out_proj"]["w"], inputs), (last, window)


# -- the attention mixer ----------------------------------------------------------

def attention(q, k, v, scale: float, inputs=None):
    """Causal softmax attention without positions: ``q`` [B, H, t, d]
    against ``k`` / ``v`` [B, K, t, d] -> [B, H, t, d], scores times
    ``scale``, a block of query rows at a time."""
    b, n_head, t, hd = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(b, n_kv, n_head // n_kv, t, hd)
    outs = []
    for lo in range(0, t, _Q_ROWS):
        rows = qg[:, :, :, lo:lo + _Q_ROWS]
        score = _ein("bkgqd,bkud->bkgqu", rows, k, inputs) * scale
        live = (lo + jnp.arange(rows.shape[3]))[:, None] \
            >= jnp.arange(t)[None, :]
        w = jax.nn.softmax(jnp.where(live, score, -jnp.inf), axis=-1)
        outs.append(_ein("bkgqu,bkud->bkgqd", w, v, inputs))
    return jnp.concatenate(outs, axis=3).reshape(b, n_head, t, hd)


def attention_mixer(p, h, *, n_head: int, n_kv: int, head_dim: int,
                    attention_multiplier: float, inputs=None):
    b, t, _ = h.shape

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    y = attention(heads(_mm(h, p["q"]["w"], inputs), n_head),
                  heads(_mm(h, p["k"]["w"], inputs), n_kv),
                  heads(_mm(h, p["v"]["w"], inputs), n_kv),
                  attention_multiplier, inputs)
    return _mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"],
               inputs)


# -- the experts --------------------------------------------------------------------

def route(scores, k: int, renormalise_over_all: bool = False):
    """``(ids [..., k], weights [..., k])``: the ``k`` largest scores
    and a softmax over those ``k`` values.  ``renormalise_over_all`` is
    a control's: the softmax taken over every expert instead and used
    as it comes (the rule this family does *not* have)."""
    top, ids = jax.lax.top_k(scores, k)
    if renormalise_over_all:
        return ids, jnp.take_along_axis(jax.nn.softmax(scores, -1), ids, -1)
    return ids, jax.nn.softmax(top, axis=-1)


def experts(p, h, *, top_k: int, held: tuple, inputs=None,
            renormalise_over_all: bool = False):
    """``(routed(h) + shared(h) [B, t, D], ids [B, t, k])`` of the normed
    stream ``h``: the routed sum over the experts ``held[0] .. held[1] -
    1`` (whose matrices ``p["experts"]`` holds, in that order), a masked
    loop over them, the choice over every column of the router."""
    ids, w = route(_mm(h, p["router"]["w"], inputs), top_k,
                   renormalise_over_all)
    ex = p["experts"]

    def one(acc, args):
        e, gate, up, down = args
        weight = jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
        y = _mm(jax.nn.silu(_mm(h, gate, inputs)) * _mm(h, up, inputs),
                down, inputs)
        return acc + weight * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held[0], held[1]), ex["gate"], ex["up"], ex["down"]))
    shared = _mm(jax.nn.silu(_mm(h, p["shared_gate"]["w"], inputs))
                 * _mm(h, p["shared_up"]["w"], inputs),
                 p["shared_down"]["w"], inputs)
    return routed + shared, ids


@functools.partial(jax.jit, static_argnames=(
    "top_k", "held", "eps", "inputs", "renormalise_over_all"))
def expert_half(p, x, *, top_k: int, held: tuple, eps: float, inputs=None,
                renormalise_over_all: bool = False):
    """``(routed + shared, ids)`` of the stream ``x`` [B, t, D] as a
    layer's second half sees it: behind the layer's second norm, before
    the residual multiplier."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        return experts(p, _rms(x, p["ln2"]["scale"], eps), top_k=top_k,
                       held=held, inputs=inputs,
                       renormalise_over_all=renormalise_over_all)


# -- the model ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "head_dim", "mamba_heads", "d_state", "top_k",
    "held", "attention_multiplier", "residual_multiplier", "eps",
    "state_dtype", "window_shift", "inputs", "renormalise_over_all"))
def block(p, x, *, kind: str, n_head: int, n_kv: int, head_dim: int,
          mamba_heads: int, d_state: int, top_k: int, held: tuple,
          attention_multiplier: float, residual_multiplier: float,
          eps: float, state_dtype=None, window_shift: int = 0, inputs=None,
          renormalise_over_all: bool = False):
    """One layer on ``x`` [B, t, D]: ``(x, state, ids)``, ``state`` the
    Mamba layer's ``(H, window)`` after the last position (None for an
    attention layer), ``ids`` [B, t, k] the experts the router chose.
    ``inputs`` rounds every product's operands to that float type
    (:func:`_mm`)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        h = _rms(x, p["ln1"]["scale"], eps)
        if kind == "attention":
            mixed, state = attention_mixer(
                p, h, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
                attention_multiplier=attention_multiplier,
                inputs=inputs), None
        else:
            mixed, state = mamba_mixer(
                p, h, mamba_heads=mamba_heads, d_state=d_state, eps=eps,
                state_dtype=state_dtype, window_shift=window_shift,
                inputs=inputs)
        x = x + residual_multiplier * mixed
        out, ids = experts(p, _rms(x, p["ln2"]["scale"], eps), top_k=top_k,
                           held=held, inputs=inputs,
                           renormalise_over_all=renormalise_over_all)
        return x + residual_multiplier * out, state, ids


@functools.partial(jax.jit, static_argnames=("multiplier",))
def _embed(p, ids, *, multiplier: float):
    return multiplier * _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "scaling",
                                             "inputs"))
def _head(p_ln, p_embed, x, *, eps: float, lo: int, scaling: float,
          inputs=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_embed)["wte"].T, inputs) / scaling


def _layers(params, ids, *, layer_types, embedding_multiplier: float,
            **block_args):
    """The stream after the last layer, and per layer its state and its
    router's choices."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32),
               multiplier=embedding_multiplier)
    states, chosen = [], []
    for i, kind in enumerate(layer_types):
        x, state, picked = block(params[f"block_{i}"], x, kind=kind,
                                 **block_args)
        states.append(state)
        chosen.append(picked)
    return x, states, chosen


def _args(args: dict) -> dict:
    """The configuration's ``reference.args`` as :func:`block` takes
    them (lists from a JSON file made tuples)."""
    return dict(args, layer_types=tuple(args["layer_types"]),
                held=tuple(args["held"]))


def logits(params, ids, *, lo: int = 0, inputs=None, experts: bool = False,
           **args):
    """Next-token logits [B, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [B, t] (every position sees the whole of ``ids`` before it;
    ``lo`` only spares the head the positions nobody reads).  The head
    is the embedding's table.  With ``experts`` also every layer's
    chosen experts, ``[L, B, t, k]``."""
    args = _args(args)
    scaling = args.pop("logits_scaling")
    x, _, chosen = _layers(params, ids, inputs=inputs, **args)
    out = _head(params["final_ln"], params["embeddings"], x, eps=args["eps"],
                lo=lo, scaling=scaling, inputs=inputs)
    return (out, jnp.stack(chosen)) if experts else out


def states(params, ids, **args):
    """What every layer keeps after the last position of ``ids`` [B,
    t]: per layer ``(H [B, heads, P, N], window [B, k - 1, E + 2N])``,
    None for an attention layer."""
    args = _args(args)
    args.pop("logits_scaling")
    return _layers(params, ids, **args)[1]
