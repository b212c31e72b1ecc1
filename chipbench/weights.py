"""Weights from the seed, made on the device in one jitted call.

``graph.init`` run eagerly makes every leaf with a dispatch of its own
(and on the host platform takes minutes for 1.5 B parameters); under
``jit`` it is one program, cached like any other, and the leaves come
out in the type the cell serves them in.
"""

from __future__ import annotations


def init_on_device(graph, seed: int, dtype=None):
    """``graph.init(key(seed))`` as one jitted call; floating leaves cast
    to ``dtype`` inside the same program when given."""
    import jax
    import jax.numpy as jnp

    def make(key):
        params = graph.init(key)
        if dtype is None:
            return params
        return jax.tree.map(
            lambda a: a.astype(dtype)
            if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    # seeds run to a little over 2**31: fold into the key's 32-bit range
    params = jax.jit(make)(jax.random.key(int(seed) % (2 ** 31 - 1)))
    return jax.block_until_ready(params)
