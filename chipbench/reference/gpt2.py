"""Plain GPT-2 forward: float32 ``jax.numpy``, no cache, no kernel.

Follows Radford et al. 2019 / the Hugging Face ``GPT2LMHeadModel``:
learned token and position embeddings, pre-LN blocks (causal multi-head
attention, then a 4x MLP with the tanh GELU), a final LayerNorm and an
output head.  Departures, both taken from the program under test so the
same weights can be fed to both: the output head is a matrix of its own
with a bias (GPT-2 ties it to the token embedding), and the fused qkv
projection is laid out ``[q | k | v]`` by columns.

The weights come in as the program's parameter tree (``embeddings``,
``block_i``, ``final_ln``, ``lm_head``) and are upcast to float32 here;
every product runs at ``highest`` matmul precision, which on a TPU is
true float32.  One block is jitted and called layer by layer, so the
compile is one small program whatever the depth.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _ln(p, x, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def block(p, x, *, n_head: int, eps: float):
    """One pre-LN block on ``x`` [b, t, d]."""
    p = _f32(p)
    b, t, d = x.shape
    hd = d // n_head
    qkv = jnp.matmul(_ln(p["ln1"], x, eps), p["qkv"]["w"],
                     precision=_HI) + p["qkv"]["b"]
    q, k, v = (a.reshape(b, t, n_head, hd).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    att = jax.nn.softmax(jnp.where(causal, att, -jnp.inf), axis=-1)
    y = jnp.einsum("bhqk,bhkd->bhqd", att, v, precision=_HI)
    y = y.transpose(0, 2, 1, 3).reshape(b, t, d)
    x = x + jnp.matmul(y, p["proj"]["w"], precision=_HI) + p["proj"]["b"]
    h = jnp.matmul(_ln(p["ln2"], x, eps), p["fc1"]["w"],
                   precision=_HI) + p["fc1"]["b"]
    h = jax.nn.gelu(h, approximate=True)
    return x + jnp.matmul(h, p["fc2"]["w"], precision=_HI) + p["fc2"]["b"]


@jax.jit
def _embed(p, ids):
    p = _f32(p)
    return p["wte"][ids] + p["wpe"][: ids.shape[1]]


@functools.partial(jax.jit, static_argnames=("eps", "lo"))
def _head(p_ln, p_head, x, *, eps: float, lo: int):
    h = _ln(_f32(p_ln), x[:, lo:], eps)
    p_head = _f32(p_head)
    return jnp.matmul(h, p_head["w"], precision=_HI) + p_head["b"]


def logits(params, ids, *, n_layer: int, n_head: int, eps: float = 1e-5,
           lo: int = 0):
    """Next-token logits [b, t - lo, vocab] at positions ``lo..t-1`` of
    ``ids`` [b, t] (all positions attend causally over the whole of
    ``ids``; ``lo`` only spares the head the positions nobody reads)."""
    x = _embed(params["embeddings"], jnp.asarray(ids, jnp.int32))
    for i in range(n_layer):
        x = block(params[f"block_{i}"], x, n_head=n_head, eps=eps)
    return _head(params["final_ln"], params["lm_head"], x, eps=eps, lo=lo)
