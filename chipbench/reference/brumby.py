"""Plain Brumby forward: float32 ``jax.numpy``, the retention in its
**attention form** — no state, no cache, no kernel, no chunks.

Follows Manifest AI's power retention ("Scaling Context Requires
Rethinking Attention", arXiv:2507.04239; the ``retention`` package's
``power_retention``) on the Qwen3-shaped dense decoder that
``Brumby-14B-Base``'s ``config.json`` describes.  Per layer, with
``rms(x, w) = x / sqrt(mean(x^2) + eps) * w``, ``H`` query heads, ``K``
KV heads of ``d`` values, ``s = 1/sqrt(d)``: ``h = rms(x, w_ln1)``;
``q_i = rope(rms(h Wq [head i], w_qn))``, ``k_j = rope(rms(h Wk [head
j], w_kn))`` (the norms run over a head's ``d`` values, one scale vector
for all heads; rotate-half RoPE over the whole head), ``v_j = h Wv
[head j]``, ``lg_j = log sigmoid(h Wg [j])``.  Query head ``i`` of KV
head ``j``'s group weighs position ``u <= t`` by ``w_tu = (s q_i(t) .
k_j(u))^2 exp(sum_{r=u+1..t} lg_j(r))`` and ``y_i(t) = sum_u w_tu
v_j(u) / (sum_u w_tu + 1e-6)``; ``x += concat_i(y_i) Wo``.  Then ``h =
rms(x, w_ln2)``; ``x += (silu(h W_gate) * (h W_up)) W_down``.  Last
``rms(x, w_norm) W_head``.

:func:`recurrent` is the same layer in its recurrent form over the
plain symmetric power (``phi(a) = (a_m a_n sqrt(2)^[m<n])_{m<=n}``,
8256 values at ``d`` 128, no tiles): the tests hold the two forms to
each other inside this file.  :func:`states` is the state the
recurrence would hold after the last position, written as the explicit
sum ``sum_u exp(sum_{r>u} lg(r)) k(u) k(u)^T (x) v(u)`` in the full
``[d, d, d]`` form, which knows no layout.

Not in ``config.json``, so assumed, from the paper and the published
modelling code (the configuration file lists each under ``assumed``):
the power ``p = 2``; the per-head QK-norm; RoPE kept; the gate as one
bias-free linear map to the KV heads through ``log sigmoid``; the
normaliser and its ``1e-6``; the scale ``1/sqrt(d)`` inside the power.
Departures from the Hugging Face model, all of layout, taken from the
program under test so that the same weights feed both: a matrix is
stored ``[in, out]``; the parameter tree is the program's
(``embeddings``, ``block_i`` with ``q/k/v/decay/proj/mlp_gate/mlp_up/
mlp_down``, ``final_ln``, ``lm_head``).

Weights are upcast to float32 here, a layer at a time; every product
runs at ``highest`` matmul precision, true float32 on a TPU.  One block
is jitted and called layer by layer.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

EPS_NORMALISER = 1e-6


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), tree)


def _mm(a, b):
    """Every matrix product of this file (with :func:`_ein`): one place
    for whoever wants to see what a lower precision would give."""
    return jnp.matmul(a, b)


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b)


def _rms(x, w, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotate-half RoPE on ``x`` [b, h, t, hd], positions 0..t-1."""
    t, hd = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
    return x * cos + rot * sin


def qkvg(p, x, *, n_head: int, n_kv: int, eps: float, theta: float,
         qk_norm: bool = True, use_rope: bool = True,
         head_dim: int | None = None):
    """``q`` [b, H, t, d], ``k`` / ``v`` [b, K, t, d], ``lg`` [b, K, t]
    of ``x`` [b, t, D] under a layer's float32 weights ``p``; ``d`` is
    ``head_dim``, or ``D / H`` where none is given."""
    b, t, dm = x.shape
    hd = head_dim or dm // n_head
    h = _rms(x, p["ln1"]["scale"], eps)

    def heads(a, n):
        return a.reshape(b, t, n, hd).transpose(0, 2, 1, 3)

    q, k, v = (heads(_mm(h, p[nm]["w"]), n)
               for nm, n in (("q", n_head), ("k", n_kv), ("v", n_kv)))
    if qk_norm:
        q = _rms(q, p["q_norm"]["scale"], eps)
        k = _rms(k, p["k_norm"]["scale"], eps)
    if use_rope:
        q, k = _rope(q, theta), _rope(k, theta)
    lg = jax.nn.log_sigmoid(_mm(h, p["decay"]["w"])).transpose(0, 2, 1)
    return q, k, v, lg


def retention(q, k, v, lg, *, power: int = 2):
    """The attention form: ``q`` [b, H, t, d] against ``k`` / ``v``
    [b, K, t, d] under the log-decays ``lg`` [b, K, t] -> [b, H, t, d]."""
    b, n_head, t, hd = q.shape
    n_kv = k.shape[1]
    qg = q.reshape(b, n_kv, n_head // n_kv, t, hd)
    score = _ein("bkgqd,bkud->bkgqu", qg, k) / math.sqrt(hd)
    cum = jnp.cumsum(lg, axis=-1)                           # [b, K, t]
    gap = cum[:, :, None, :, None] - cum[:, :, None, None, :]
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = score ** power * jnp.exp(jnp.where(causal, gap, -jnp.inf))
    y = _ein("bkgqu,bkud->bkgqd", w, v) \
        / (w.sum(-1, keepdims=True) + EPS_NORMALISER)
    return y.reshape(b, n_head, t, hd)


def _phi(a):
    """The plain symmetric square of ``a`` [..., d] -> [..., d (d+1)/2]:
    ``a_m a_n`` for ``m <= n``, times ``sqrt 2`` off the diagonal."""
    d = a.shape[-1]
    m, n = jnp.triu_indices(d)
    return a[..., m] * a[..., n] * jnp.where(m < n, math.sqrt(2.0), 1.0)


def recurrent(q, k, v, lg, *, state_dtype=None, want_state: bool = False):
    """The same layer, position by position through the recurrent state
    ``S(t) = g(t) S(t-1) + phi(k(t)) v(t)^T``, ``z(t) = g(t) z(t-1) +
    phi(k(t))``: shapes as :func:`retention`.  With ``state_dtype`` the
    state is rounded to that type after every position and read rounded
    (what a memory kept below float32 would give: the control the
    limits of ``correct`` are set against); with ``want_state`` also the
    last position's ``(S [b, K, F, d], z [b, K, F])``."""
    b, n_head, t, hd = q.shape
    n_kv = k.shape[1]
    s2 = 1.0 / hd
    rows = hd * (hd + 1) // 2
    qg = q.reshape(b, n_kv, n_head // n_kv, t, hd)

    def held(a):
        # reduce_precision, not a cast there and back: the compiler may
        # drop a pair of casts (the v5e's did), and the control is lost
        if state_dtype is None:
            return a
        kind = jnp.finfo(state_dtype)
        return jax.lax.reduce_precision(a, kind.nexp, kind.nmant)

    def step(carry, at):
        s, z = carry
        decay = jnp.exp(lg[:, :, at])
        fk = _phi(k[:, :, at])                              # [b,K,F]
        s = held(decay[..., None, None] * s
                 + fk[..., :, None] * v[:, :, at, None, :])
        z = held(decay[..., None] * z + fk)
        read = _phi(qg[:, :, :, at])                        # [b,K,g,F]
        num = _ein("bkgf,bkfd->bkgd", read, s)
        den = _ein("bkgf,bkf->bkg", read, z)
        return (s, z), s2 * num / (s2 * den[..., None] + EPS_NORMALISER)

    start = (jnp.zeros((b, n_kv, rows, hd), jnp.float32),
             jnp.zeros((b, n_kv, rows), jnp.float32))
    last, ys = jax.lax.scan(step, start, jnp.arange(t))
    y = ys.transpose(1, 2, 3, 0, 4).reshape(b, n_head, t, hd)
    return (y, last) if want_state else y


def explicit_state(k, v, lg):
    """What the recurrence holds after the last position, as the
    explicit sums in the form that knows no layout: ``(S [b, K, d, d,
    d], z [b, K, d, d])`` with ``S[a, c, :] = sum_u exp(sum_{r>u}
    lg(r)) k_a(u) k_c(u) v(u)`` and ``z`` the same without ``v``;
    ``k`` / ``v`` [b, K, t, d], ``lg`` [b, K, t]."""
    cum = jnp.cumsum(lg, axis=-1)
    to_end = jnp.exp(cum[..., -1:] - cum)                   # [b, K, t]
    kk = k[..., :, None] * k[..., None, :] * to_end[..., None, None]
    return _ein("bkuac,bkud->bkacd", kk, v), kk.sum(2)


def unpacked(state):
    """:func:`recurrent`'s ``(S [b, K, F, d], z [b, K, F])`` in
    :func:`explicit_state`'s form: row ``(m <= n)`` of the plain power
    holds ``sqrt 2`` times the pair's product off the diagonal."""
    s, z = state
    d = s.shape[-1]
    m, n = jnp.triu_indices(d)
    scale = jnp.where(m < n, 1.0 / math.sqrt(2.0), 1.0)

    def full(a):                                            # F leading
        out = jnp.zeros((d, d) + a.shape[1:], a.dtype)
        a = a * scale.reshape((-1,) + (1,) * (a.ndim - 1))
        return out.at[m, n].set(a).at[n, m].set(a)

    return (jnp.moveaxis(full(jnp.moveaxis(s, -2, 0)), (0, 1), (2, 3)),
            jnp.moveaxis(full(jnp.moveaxis(z, -1, 0)), (0, 1), (2, 3)))


@functools.partial(jax.jit, static_argnames=(
    "n_head", "n_kv", "eps", "theta", "qk_norm", "use_rope", "power",
    "form", "want_state", "state_dtype", "head_dim"))
def block(p, x, *, n_head: int, n_kv: int, eps: float, theta: float,
          qk_norm: bool = True, use_rope: bool = True, power: int = 2,
          form: str = "attention", want_state: bool = False,
          state_dtype=None, head_dim: int | None = None):
    """One layer on ``x`` [b, t, D].  ``qk_norm``, ``use_rope`` and
    ``power`` are Brumby's as given; the other setting of each, and the
    recurrent ``form`` (with :func:`recurrent`'s ``state_dtype``), are
    there for the tests and the controls, which must tell them apart.
    With ``want_state`` also :func:`states`' pair for this layer: the
    explicit sum, or in the recurrent form what the recurrence itself
    held, unpacked."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        b, t, dm = x.shape
        q, k, v, lg = qkvg(p, x, n_head=n_head, n_kv=n_kv, eps=eps,
                           theta=theta, qk_norm=qk_norm, use_rope=use_rope,
                           head_dim=head_dim)
        if form == "attention":
            y, state = retention(q, k, v, lg, power=power), None
        else:
            y, state = recurrent(q, k, v, lg, state_dtype=state_dtype,
                                 want_state=True)
        x = x + _mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1),
                    p["proj"]["w"])
        h = _rms(x, p["ln2"]["scale"], eps)
        x = x + _mm(jax.nn.silu(_mm(h, p["mlp_gate"]["w"]))
                    * _mm(h, p["mlp_up"]["w"]), p["mlp_down"]["w"])
        if not want_state:
            return x
        return x, (explicit_state(k, v, lg) if state is None
                   else unpacked(state))


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_head)["w"])


def logits(params, ids, *, n_layer: int, n_head: int, n_kv: int,
           eps: float = 1e-6, theta: float = 1e6, lo: int = 0,
           **block_args):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [b, t] (every position sees the whole of ``ids`` before it;
    ``lo`` only spares the head the positions nobody reads)."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    for i in range(n_layer):
        x = block(params[f"block_{i}"], x, n_head=n_head, n_kv=n_kv,
                  eps=eps, theta=theta, **block_args)
    return _head(params["final_ln"], params["lm_head"], x, eps=eps, lo=lo)


def states(params, ids, *, n_layer: int, n_head: int, n_kv: int,
           eps: float = 1e-6, theta: float = 1e6, **block_args):
    """What every layer's recurrence would hold after the last position
    of ``ids`` [b, t]: per layer :func:`explicit_state`'s ``(S [b, K, d,
    d, d], z [b, K, d, d])``."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    out = []
    for i in range(n_layer):
        x, state = block(params[f"block_{i}"], x, n_head=n_head, n_kv=n_kv,
                         eps=eps, theta=theta, want_state=True,
                         **block_args)
        out.append(state)
    return out
