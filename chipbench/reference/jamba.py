"""Plain Jamba forward: float32 ``jax.numpy``, the state-space
recurrence position by position, the convolution as shifted products,
attention as a masked softmax — no cache, no kernel, no blocks of
channels.

Follows ``AI21-Jamba2-3B``'s ``config.json`` (``model_type`` ``jamba``)
and Mamba-1 (Gu & Dao, arXiv:2312.00752).  With ``rms(a; g) = a /
sqrt(mean(a^2) + eps) * g``, for layer ``l`` on the stream ``x`` [t,
D]:

    h = rms(x; g_ln1);  x <- x + mixer_l(h)
    h' = rms(x; g_ln2); x <- x + (silu(h' G) * (h' U)) W_down

**Attention mixer** (``l % period == offset``): ``q = h Wq`` (``H``
heads of ``d``), ``k = h Wk``, ``v = h Wv`` (``K`` heads of ``d``), no
bias, no QK-norm, no rotation and no position of any kind; every query
head of a group reads its KV head; scores ``q.k / sqrt(d)``, causal,
softmax; ``mixer = heads(softmax(.) v) Wo``.

**Mamba mixer** (every other layer), ``E`` channels, ``N`` states,
``k`` taps, per position ``t``:

    [u_t, z_t] = h_t W_in
    c_t = silu(b_conv + sum_{j<k} w_conv[j] * u_{t-k+1+j})     (u_{<0} = 0)
    [r_t, B_t, C_t] = c_t W_x;  each through an rms of its own
    dt_t = softplus(r_t W_dt + b_dt)
    H_t = exp(dt_t (x) A) * H_{t-1} + (dt_t * c_t) (x) B_t,   A = -exp(A_log)
    y_t = H_t C_t + D * c_t;  mixer = (y_t * silu(z_t)) W_out

After the last layer ``rms(x; g_f)`` and ``logits = h Wte^T`` (tied).

Not in ``config.json``, so assumed (the configuration file lists each
under ``assumed``): a head's width ``hidden / heads``; no positions; the
three small norms (the ``jamba`` model type's own addition to
Mamba-1).  Departures from the Hugging Face model, all of layout, taken
from the program's parameter tree: one node a layer; ``conv/w`` is
``[k, E]`` and ``ssm/a_log`` ``[N, E]``; ``lm_head/w`` is the
embedding's table.  Here the state is ``H [b, E, N]`` and the window
``[b, k - 1, E]`` (oldest input first): the forms that know no layout.

:func:`states` gives what every Mamba layer's recurrence holds after
the last position; :func:`selective_scan` and :func:`explicit_state`
are the recurrence and its closed form on given inputs, for the
long-memory probe.  Everything runs at ``highest`` matmul precision,
true float32 on a TPU.  A layer is jitted and called layer by layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: query rows of one block of the masked softmax
_Q_ROWS = 256


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


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


def _held(a, state_dtype):
    """``a`` rounded to ``state_dtype`` (by ``reduce_precision``: a cast
    there and back is the compiler's to drop); None: as it is."""
    if state_dtype is None:
        return a
    kind = jnp.finfo(state_dtype)
    return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)


# -- the state-space mixer ------------------------------------------------------

def selective_scan(dt, x, b, c, a, *, state_dtype=None):
    """The recurrence position by position from an empty state: ``dt``
    / ``x`` [B, t, E], ``b`` / ``c`` [B, t, N], ``a`` [E, N] -> ``(y [B,
    t, E], H [B, E, N])``, ``y_t = H_t c_t`` and ``H`` after the last
    position.  With ``state_dtype`` the state is rounded to that type
    after every position and read rounded (what a memory kept below
    float32 would give: the control the limits are set against)."""
    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs
        h = _held(jnp.exp(dt_t[..., None] * a) * h
                  + (dt_t * x_t)[..., None] * b_t[:, None, :], state_dtype)
        return h, (h * c_t[:, None, :]).sum(-1)

    start = jnp.zeros((dt.shape[0],) + a.shape, jnp.float32)
    last, ys = jax.lax.scan(step, start, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), last


def explicit_state(dt, x, b, a):
    """What the recurrence holds after the last position, as the
    explicit sum ``H[e, n] = sum_u exp(A[e, n] sum_{r > u} dt_r[e])
    dt_u[e] x_u[e] b_u[n]``: shapes as :func:`selective_scan`, a
    sequence at a time."""
    def one(args):
        dt_s, x_s, b_s = args                        # [t, E], [t, E], [t, N]
        cum = jnp.cumsum(dt_s, axis=0)
        to_end = (cum[-1:] - cum)[..., None] * a     # [t, E, N]
        return ((dt_s * x_s)[..., None] * b_s[:, None, :]
                * jnp.exp(to_end)).sum(0)

    return jax.lax.map(one, (dt, x, b))


def conv_taps(u, k: int):
    """The ``k`` shifted copies of ``u`` [B, t, E] a causal convolution
    reads, oldest first; zero before the sequence's start."""
    t = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return [padded[:, j:j + t] for j in range(k)]


def mamba_mixer(p, h, *, d_state: int, dt_rank: int, eps: float,
                state_dtype=None, small_norms: bool = True, inputs=None):
    """``(mixer(h) [B, t, D], (H [B, E, N], window [B, k-1, E]))`` of
    the normed stream ``h`` [B, t, D] under a layer's float32 weights
    ``p``; ``small_norms`` false leaves out the family's three norms
    (plain Mamba-1: there for the tests, which must tell them apart)."""
    e = p["in_proj"]["w"].shape[1] // 2
    k = p["conv"]["w"].shape[0]
    uz = _mm(h, p["in_proj"]["w"], inputs)
    u, z = uz[..., :e], uz[..., e:]
    acc = p["conv"]["b"]
    for j, tap in enumerate(conv_taps(u, k)):
        acc = acc + p["conv"]["w"][j] * tap
    c = jax.nn.silu(acc)
    sel = _mm(c, p["x_proj"]["w"], inputs)
    low, b, c_read = (sel[..., :dt_rank], sel[..., dt_rank:dt_rank + d_state],
                      sel[..., dt_rank + d_state:])
    if small_norms:
        low = _rms(low, p["dt_norm"]["scale"], eps)
        b = _rms(b, p["b_norm"]["scale"], eps)
        c_read = _rms(c_read, p["c_norm"]["scale"], eps)
    dt = jax.nn.softplus(_mm(low, p["dt_proj"]["w"], inputs)
                         + p["dt_proj"]["b"])
    a = -jnp.exp(p["ssm"]["a_log"]).T                      # [E, N]
    y, last = selective_scan(dt, c, b, c_read, a, state_dtype=state_dtype)
    y = (y + p["ssm"]["d"] * c) * jax.nn.silu(z)
    window = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))[:, u.shape[1]:]
    return _mm(y, p["out_proj"]["w"], inputs), (last, window)


# -- the attention mixer ----------------------------------------------------------

def attention(q, k, v, inputs=None):
    """Causal softmax attention without positions: ``q`` [B, H, t, d]
    against ``k`` / ``v`` [B, K, t, d] -> [B, H, t, d], a block of
    query rows at a time."""
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


def attention_mixer(p, h, *, n_head: int, n_kv: int, head_dim: int,
                    inputs=None):
    b, t, _ = h.shape

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    y = attention(heads(_mm(h, p["q"]["w"], inputs), n_head),
                  heads(_mm(h, p["k"]["w"], inputs), n_kv),
                  heads(_mm(h, p["v"]["w"], inputs), n_kv), inputs)
    return _mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"],
               inputs)


# -- the model ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "head_dim", "d_state", "dt_rank", "eps",
    "state_dtype", "small_norms", "inputs"))
def block(p, x, *, kind: str, n_head: int, n_kv: int, head_dim: int,
          d_state: int, dt_rank: int, eps: float, state_dtype=None,
          small_norms: bool = True, inputs=None):
    """One layer on ``x`` [B, t, D]: ``(x, state)``, ``state`` the Mamba
    layer's ``(H, window)`` after the last position, None for an
    attention layer.  ``inputs`` rounds every product's operands to
    that float type (:func:`_mm`)."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        h = _rms(x, p["ln1"]["scale"], eps)
        if kind == "attention":
            mixed, state = attention_mixer(
                p, h, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
                inputs=inputs), None
        else:
            mixed, state = mamba_mixer(
                p, h, d_state=d_state, dt_rank=dt_rank, eps=eps,
                state_dtype=state_dtype, small_norms=small_norms,
                inputs=inputs)
        x = x + mixed
        h = _rms(x, p["ln2"]["scale"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, p["mlp_gate"]["w"], inputs))
                    * _mm(h, p["mlp_up"]["w"], inputs), p["mlp_down"]["w"],
                    inputs)
        return x, state


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "inputs"))
def _head(p_ln, p_embed, x, *, eps: float, lo: int, inputs=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_embed)["wte"].T, inputs)


def _layers(params, ids, *, n_layer: int, attn_period: int,
            attn_offset: int, **block_args):
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    states = []
    for i in range(n_layer):
        kind = "attention" if i % attn_period == attn_offset else "mamba"
        x, state = block(params[f"block_{i}"], x, kind=kind, **block_args)
        states.append(state)
    return x, states


def logits(params, ids, *, eps: float = 1e-6, lo: int = 0, inputs=None,
           **args):
    """Next-token logits [B, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [B, t] (every position sees the whole of ``ids`` before it;
    ``lo`` only spares the head the positions nobody reads).  The head
    is the embedding's table."""
    x, _ = _layers(params, ids, eps=eps, inputs=inputs, **args)
    return _head(params["final_ln"], params["embeddings"], x, eps=eps, lo=lo,
                 inputs=inputs)


def states(params, ids, *, eps: float = 1e-6, **args):
    """What every layer keeps after the last position of ``ids`` [B,
    t]: per layer ``(H [B, E, N], window [B, k - 1, E])``, None for an
    attention layer."""
    return _layers(params, ids, eps=eps, **args)[1]
