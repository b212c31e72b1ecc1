"""Plain Nemotron-3-Super forward: float32 ``jax.numpy``, the Mamba-2
recurrence position by position, the convolution as shifted products,
attention as a masked softmax, every expert by a loop — no cache, no
kernel, no chunking, nothing of ``defer_tpu``.

Follows ``NVIDIA-Nemotron-3-Super-120B-A12B-BF16``'s ``config.json``
(``model_type`` ``nemotron_h``) and Mamba-2 (Dao & Gu, arXiv:2405.21060).
With ``rms(a; g) = a / sqrt(mean(a^2) + eps) * g``, the stream ``x`` [t,
D] starts as ``Wte[id]``, and layer ``l`` is ``x <- x + f_l(rms(x;
g_l))`` with exactly one ``f`` by the layer's kind (``layer_pattern``):

**``M``, a Mamba-2 mixer with B/C groups**: ``heads`` heads of ``P``
channels (``E = heads x P``), ``N`` states, ``G`` groups of ``heads /
G`` consecutive heads, ``k`` taps, per position ``t``:

    [z_t, u_t, r_t] = h_t W_in         z [E], u [E + 2 G N], r [heads]
    c_t = silu(b_conv + sum_{j<k} w_conv[j] * u_{t-k+1+j})     (u_{<0} = 0)
    [x_t, B_t, C_t] = c_t              x [heads, P], B, C [G, N]
    dt_t = softplus(r_t + b_dt);  a = -exp(A_log)               [heads]
    H_t[n] = exp(dt_t[n] a[n]) * H_{t-1}[n] + dt_t[n] * x_t[n] (x) B_t[g(n)]
    y_t[n] = H_t[n] C_t[g(n)] + D[n] * x_t[n],       g(n) = n // (heads / G)
    f = rms_g(y_t * silu(z_t); g_norm) W_out

the gate first, then the norm **a group**: each group's ``E / G``
channels normalised by their own mean square, one weight of ``E``.

**``*``, grouped-query attention**: ``q = h Wq`` (``H`` heads of ``d``),
``k = h Wk``, ``v = h Wv`` (``K`` heads of ``d``), no bias, no QK-norm,
no rotation and no position of any kind; every query head of a group
reads its KV head; scores ``q.k / sqrt(d)``, causal, softmax; ``f =
heads(softmax(.) v) Wo``.

**``E``, a LatentMoE**: ``s = sigmoid(h W_r)`` over all routed experts;
the ``k`` largest of ``s + bias`` choose (the bias chooses and never
weighs); weights ``routed_scale * s_chosen / sum(s_chosen)``; ``u = h
W_down`` (the latent space, once a token); expert ``e``: ``relu(u
W1_e)^2 W2_e``; ``f = (sum_e w_e E_e(u)) W_up + relu(h S1)^2 S2``, the
sum over the chosen experts *of the range the layer holds* (``held``;
the rest of the sum is other chips' and is left out, here as in the
program), the shared expert for every token.

After the last layer ``rms(x; g_f)`` and ``logits = h W_head`` (untied).

Departures from the published model, each listed under the
configuration's ``assumed``: no multi-token-prediction module
(``no_mtp``); the attention carries no rotation (``no_positions``: the
``nemotron_h`` model type applies none, the catalog cannot confirm it);
the gate before the norm, a group at a time (``gate_then_norm``); ``dt``
is not clamped (``time_step_*`` are the initialiser's); the LatentMoE's
shape (router and shared expert on the stream, experts between
``W_down`` and ``W_up``, no bias anywhere: ``latent_moe``); only the
held share of the experts and the held rows of the vocabulary exist.
Departures of layout, taken from the program's parameter tree: one node
a layer; ``conv/w`` is ``[k, E + 2 G N]``; an expert's matrices are
stacked.  Here the state is ``H [b, heads, P, N]`` and the window ``[b,
k - 1, E + 2 G N]`` (oldest input first): the forms that know no
layout.

:func:`states` gives what every layer keeps after the last position
(a Mamba layer's ``(H, window)``, the attention layer's ``(k, v)``
rows); :func:`selective_scan` and :func:`explicit_state` are the
recurrence and its closed form on given inputs, for the long-memory
probe.  Everything runs at ``highest`` matmul precision, true float32
on a TPU.  A layer is jitted and called layer by layer.  The keyword
arguments named *control* are the controls' (``scripts/
ssd_latent_moe_controls.py``): each makes a model this family is *not*.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: query rows of one block of the masked softmax
_Q_ROWS = 256

ACTIVATIONS = {
    "relu2": lambda a: jnp.square(jax.nn.relu(a)),
    # the controls': what this family's experts do not compute
    "relu": jax.nn.relu,
    "silu": jax.nn.silu,
}


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
    [B, t, heads], ``x`` [B, t, heads, P], ``b`` / ``c`` [B, t, G, N],
    ``a`` [heads] -> ``(y [B, t, heads, P], H [B, heads, P, N])``, ``y_t
    = H_t c_t`` and ``H`` after the last position; head ``n`` reads
    group ``n // (heads / G)``.  With ``state_dtype`` the state is
    rounded to that type after every position and read rounded (a
    control: a memory kept below float32)."""
    per = x.shape[2] // b.shape[2]

    def step(h, xs):
        dt_t, x_t, b_t, c_t = xs
        b_h, c_h = (jnp.repeat(v, per, axis=1) for v in (b_t, c_t))
        h = _held(jnp.exp(dt_t * a)[:, :, None, None] * h
                  + (dt_t[:, :, None] * x_t)[..., None]
                  * b_h[:, :, None, :], state_dtype)
        return h, (h * c_h[:, :, None, :]).sum(-1)

    start = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    last, ys = jax.lax.scan(step, start, tuple(
        jnp.swapaxes(v, 0, 1) for v in (dt, x, b, c)))
    return jnp.swapaxes(ys, 0, 1), last


def explicit_state(dt, x, b, a):
    """What the recurrence holds after the last position, as the
    explicit sum ``H[n, p, s] = sum_u exp(a[n] sum_{r > u} dt_r[n])
    dt_u[n] x_u[n, p] b_u[g(n), s]``: shapes as :func:`selective_scan`,
    a sequence at a time, one product over the positions."""
    per = x.shape[2] // b.shape[2]

    def one(args):
        dt_s, x_s, b_s = args          # [t, H], [t, H, P], [t, G, N]
        cum = jnp.cumsum(dt_s, axis=0)
        w = jnp.exp((cum[-1:] - cum) * a) * dt_s           # [t, H]
        return jnp.einsum("thp,thn->hpn", w[..., None] * x_s,
                          jnp.repeat(b_s, per, axis=1))

    return jax.lax.map(one, (dt, x, b))


def conv_taps(u, k: int):
    """The ``k`` shifted copies of ``u`` [B, t, W] a causal convolution
    reads, oldest first; zero before the sequence's start."""
    t = u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return [padded[:, j:j + t] for j in range(k)]


def mamba_mixer(p, h, *, mamba_heads: int, d_state: int, groups: int,
                eps: float, state_dtype=None, window_shift: int = 0,
                inputs=None, one_bc_group: bool = False,
                norm_one_group: bool = False):
    """``(f(h) [B, t, D], (H [B, heads, P, N], window [B, k-1, E + 2 G
    N]))`` of the normed stream ``h`` [B, t, D] under a layer's float32
    weights ``p``.  Controls: ``window_shift`` hands back the window
    that many positions earlier; ``one_bc_group`` lets every head read
    group 0's ``B`` and ``C``; ``norm_one_group`` normalises all ``E``
    channels as one group."""
    nh, n, g = mamba_heads, d_state, groups
    k, w = p["conv"]["w"].shape
    e = w - 2 * g * n
    zur = _mm(h, p["in_proj"]["w"], inputs)
    z, u, r = zur[..., :e], zur[..., e:e + w], zur[..., e + w:]
    acc = p["conv"]["b"]
    for j, tap in enumerate(conv_taps(u, k)):
        acc = acc + p["conv"]["w"][j] * tap
    c = jax.nn.silu(acc)
    bsz, t = h.shape[:2]
    x = c[..., :e].reshape(bsz, t, nh, e // nh)
    b_in = c[..., e:e + g * n].reshape(bsz, t, g, n)
    c_read = c[..., e + g * n:].reshape(bsz, t, g, n)
    if one_bc_group:
        b_in, c_read = b_in[:, :, :1], c_read[:, :, :1]
    dt = jax.nn.softplus(r + p["ssm"]["dt_bias"])
    a = -jnp.exp(p["ssm"]["a_log"])
    y, last = selective_scan(dt, x, b_in, c_read, a, state_dtype=state_dtype)
    y = (y + p["ssm"]["d"][:, None] * x).reshape(bsz, t, e)
    gated = y * jax.nn.silu(z)
    if norm_one_group:
        normed = _rms(gated, 1.0, eps)
    else:
        normed = _rms(gated.reshape(bsz, t, g, e // g), 1.0, eps
                      ).reshape(bsz, t, e)
    end = u.shape[1] - window_shift
    window = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))[:, end:end + k - 1]
    return _mm(normed * p["gate_norm"]["scale"], p["out_proj"]["w"],
               inputs), (last, window)


# -- the attention mixer ----------------------------------------------------------

def _rotated(a, theta: float):
    """``a`` [B, heads, t, d] under a rotary embedding of its whole
    width (half-split pairs): a control's — this family has none."""
    t, d = a.shape[2:]
    freq = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    lo, hi = a[..., :d // 2], a[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


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
                    inputs=None, rotation_theta=None):
    """``(f(h), (k, v))``, the rows ``[B, K, t, d]`` as a cache would
    keep them.  ``rotation_theta`` is a control's: queries and keys
    rotated by their position."""
    b, t, _ = h.shape

    def heads(a, n):
        return a.reshape(b, t, n, head_dim).transpose(0, 2, 1, 3)

    q = heads(_mm(h, p["q"]["w"], inputs), n_head)
    k = heads(_mm(h, p["k"]["w"], inputs), n_kv)
    v = heads(_mm(h, p["v"]["w"], inputs), n_kv)
    if rotation_theta is not None:
        q, k = _rotated(q, rotation_theta), _rotated(k, rotation_theta)
    y = attention(q, k, v, head_dim ** -0.5, inputs)
    return _mm(y.transpose(0, 2, 1, 3).reshape(b, t, -1), p["proj"]["w"],
               inputs), (k, v)


# -- the experts --------------------------------------------------------------------

def route(logits, bias, k: int, scale: float, *, drop_last: bool = False,
          bias_in_weights: bool = False):
    """``(ids [..., k], weights [..., k])``: sigmoid scores, the ``k``
    largest of score + bias, the chosen scores over their sum times
    ``scale``.  Controls: ``drop_last`` gives the ``k``-th choice no
    weight (the others keep theirs); ``bias_in_weights`` weighs by score
    + bias."""
    s = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(s + bias, k)
    p = jnp.take_along_axis(s + bias if bias_in_weights else s, ids, -1)
    w = scale * p / p.sum(-1, keepdims=True)
    if drop_last:
        w = w.at[..., -1].set(0.0)
    return ids, w


def latent_moe(p, h, *, top_k: int, held: tuple, routed_scale: float,
               inputs=None, activation: str = "relu2",
               drop_last: bool = False, bias_in_weights: bool = False):
    """``(f(h) [B, t, D], ids [B, t, k], weights [B, t, k], latent [B,
    t, R])`` of the normed stream ``h``: the routed sum over the experts
    ``held[0] .. held[1] - 1`` (whose matrices ``p["experts"]`` holds,
    in that order), a loop over them, the choice over every column of
    the router; ``latent`` the held experts' weighted sum before
    ``W_up``."""
    act = ACTIVATIONS[activation]
    ids, w = route(_mm(h, p["router"]["w"], inputs), p["router"]["bias"],
                   top_k, routed_scale, drop_last=drop_last,
                   bias_in_weights=bias_in_weights)
    u = _mm(h, p["latent_down"]["w"], inputs)
    ex = p["experts"]

    def one(acc, args):
        e, up, down = args
        weight = jnp.where(ids == e, w, 0.0).sum(-1, keepdims=True)
        return acc + weight * _mm(act(_mm(u, up, inputs)), down, inputs), None

    latent, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(held[0], held[1]), ex["up"], ex["down"]))
    shared = _mm(act(_mm(h, p["shared_up"]["w"], inputs)),
                 p["shared_down"]["w"], inputs)
    return _mm(latent, p["latent_up"]["w"], inputs) + shared, ids, w, latent


_CONTROLS = ("state_dtype", "window_shift", "inputs", "one_bc_group",
             "norm_one_group", "rotation_theta", "activation", "drop_last",
             "bias_in_weights")


@functools.partial(jax.jit, static_argnames=(
    "top_k", "held", "routed_scale", "eps", "inputs", "activation",
    "drop_last", "bias_in_weights"))
def expert_branch(p, x, *, top_k: int, held: tuple, routed_scale: float,
                  eps: float, inputs=None, activation: str = "relu2",
                  drop_last: bool = False, bias_in_weights: bool = False):
    """``(f, ids, weights, latent)`` of the stream ``x`` [B, t, D] as an
    ``E`` layer sees it: behind the layer's norm, before the residual."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        return latent_moe(p, _rms(x, p["ln"]["scale"], eps), top_k=top_k,
                          held=held, routed_scale=routed_scale,
                          inputs=inputs, activation=activation,
                          drop_last=drop_last,
                          bias_in_weights=bias_in_weights)


# -- the model ----------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "kind", "n_head", "n_kv", "head_dim", "mamba_heads", "d_state",
    "groups", "top_k", "held", "routed_scale", "eps") + _CONTROLS)
def block(p, x, *, kind: str, n_head: int, n_kv: int, head_dim: int,
          mamba_heads: int, d_state: int, groups: int, top_k: int,
          held: tuple, routed_scale: float, eps: float, state_dtype=None,
          window_shift: int = 0, inputs=None, one_bc_group: bool = False,
          norm_one_group: bool = False, rotation_theta=None,
          activation: str = "relu2", drop_last: bool = False,
          bias_in_weights: bool = False):
    """One layer on ``x`` [B, t, D]: ``(x, state, ids)``, ``state`` an
    ``M`` layer's ``(H, window)`` after the last position, a ``*``
    layer's ``(k, v)`` rows, None for an ``E`` layer; ``ids`` [B, t, k]
    the experts an ``E`` layer's router chose, None for the others."""
    with jax.default_matmul_precision("highest"):
        p = _f32(p)
        h = _rms(x, p["ln"]["scale"], eps)
        ids = None
        if kind == "*":
            out, state = attention_mixer(
                p, h, n_head=n_head, n_kv=n_kv, head_dim=head_dim,
                inputs=inputs, rotation_theta=rotation_theta)
        elif kind == "M":
            out, state = mamba_mixer(
                p, h, mamba_heads=mamba_heads, d_state=d_state,
                groups=groups, eps=eps, state_dtype=state_dtype,
                window_shift=window_shift, inputs=inputs,
                one_bc_group=one_bc_group, norm_one_group=norm_one_group)
        else:
            out, ids, _, _ = latent_moe(
                p, h, top_k=top_k, held=held, routed_scale=routed_scale,
                inputs=inputs, activation=activation, drop_last=drop_last,
                bias_in_weights=bias_in_weights)
            state = None
        return x + out, state, ids


@jax.jit
def _embed(p, ids):
    return _f32(p)["wte"][ids]


@functools.partial(jax.jit, static_argnames=("eps", "lo", "inputs"))
def _head(p_ln, p_head, x, *, eps: float, lo: int, inputs=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x[:, lo:], _f32(p_ln)["scale"], eps)
        return _mm(h, _f32(p_head)["w"], inputs)


def _layers(params, ids, *, layer_pattern, **block_args):
    """The stream after the last layer, and per layer its state and its
    router's choices."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    states, chosen = [], []
    for i, kind in enumerate(layer_pattern):
        x, state, picked = block(params[f"block_{i}"], x, kind=kind,
                                 **block_args)
        states.append(state)
        chosen.append(picked)
    return x, states, chosen


def _args(args: dict) -> dict:
    """The configuration's ``reference.args`` as :func:`block` takes
    them (lists from a JSON file made tuples)."""
    return dict(args, held=tuple(args["held"]))


def logits(params, ids, *, lo: int = 0, inputs=None, experts: bool = False,
           **args):
    """Next-token logits [B, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [B, t] (every position sees the whole of ``ids`` before it;
    ``lo`` only spares the head the positions nobody reads).  With
    ``experts`` also the ``E`` layers' chosen experts, ``{layer: [B, t,
    k]}``."""
    args = _args(args)
    x, _, chosen = _layers(params, ids, inputs=inputs, **args)
    out = _head(params["final_ln"], params["lm_head"], x, eps=args["eps"],
                lo=lo, inputs=inputs)
    if experts:
        return out, {l: c for l, c in enumerate(chosen) if c is not None}
    return out


def states(params, ids, **args):
    """What every layer keeps after the last position of ``ids`` [B,
    t]: an ``M`` layer's ``(H [B, heads, P, N], window [B, k - 1, E + 2
    G N])``, a ``*`` layer's ``(k, v)`` rows ``[B, K, t, d]``, None for
    an ``E`` layer."""
    return _layers(params, ids, **_args(args))[1]
