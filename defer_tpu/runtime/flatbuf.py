"""Flat per-stage weight buffers: the shared pack/unpack scheme.

The stream pipeline (``runtime/spmd.py``, whose layout its trainer
``runtime/training.py`` reads) ships each stage's parameter pytree as one flat
row of a ``[num_stages, Pmax]`` array sharded over the ``stage`` mesh axis — the
TPU-native replacement for the reference's runtime weight shipping
(reference src/dispatcher.py:67-80): placement is a sharding annotation, not
a socket protocol.  This module is the single definition of the row layout.
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import numpy as np

from jax import lax
import jax


#: per-leaf layout record: (offset, size, shape, dtype)
LeafMeta = tuple[int, int, tuple[int, ...], Any]


def leaf_meta(leaves: Sequence[np.ndarray]) -> list[LeafMeta]:
    """Offsets/shapes/dtypes of ``leaves`` laid out back-to-back."""
    meta, off = [], 0
    for leaf in leaves:
        leaf = np.asarray(leaf)
        meta.append((off, leaf.size, leaf.shape, leaf.dtype))
        off += leaf.size
    return meta


def check_layout(leaves: Sequence[np.ndarray], treedef,
                 want_meta: Sequence[LeafMeta], want_treedef,
                 what: str) -> None:
    """Validate PRE-cast leaves + treedef against a deployed row layout.

    The rule of ``SpmdPipeline.reweight``: the compiled
    programs unflatten with the init-recorded treedef/shapes, and a
    silent dtype change would blind-cast values — so structure, shapes,
    AND original dtypes must match or we raise before touching the
    deployed buffer.
    """
    if treedef != want_treedef:
        raise ValueError(
            f"{what}: param tree structure differs from the deployed one")
    want = [(m[2], np.dtype(m[3])) for m in want_meta]
    got = [(np.shape(l), np.asarray(l).dtype) for l in leaves]
    if want != got:
        raise ValueError(f"{what}: leaves {got} != deployed {want}")


def pack_leaves(leaves: Sequence[np.ndarray], wire_dtype,
                cast_fn: Callable[[np.ndarray], np.ndarray] | None = None,
                ) -> np.ndarray:
    """One flat row: each leaf cast (``cast_fn`` or plain astype), raveled,
    concatenated in order."""
    if not leaves:
        return np.zeros((0,), wire_dtype)
    cast = cast_fn if cast_fn is not None \
        else (lambda a: np.asarray(a).astype(wire_dtype))
    return np.concatenate([cast(np.asarray(l)).ravel() for l in leaves])


def stack_rows(rows: Sequence[np.ndarray], wire_dtype) -> np.ndarray:
    """[N, Pmax] buffer: rows right-padded with zeros to the longest."""
    pmax = max(max((r.size for r in rows), default=1), 1)
    buf = np.zeros((len(rows), pmax), wire_dtype)
    for i, r in enumerate(rows):
        buf[i, : r.size] = r
    return buf


def unpack_leaves(w_local: jax.Array, meta: Sequence[LeafMeta], treedef,
                  leaf_dtype: Callable[[Any], Any] | None = None):
    """Rebuild the stage pytree from its flat row (inside jit).

    ``leaf_dtype`` maps each stored dtype to the dtype the consumer wants
    (e.g. the compute-dtype cast of ``runtime/spmd.py``); ``None`` keeps
    the buffer dtype as-is.
    """
    leaves = []
    for off, size, shape, dtype in meta:
        leaf = lax.slice(w_local, (off,), (off + size,)).reshape(shape)
        if leaf_dtype is not None:
            leaf = leaf.astype(leaf_dtype(dtype))
        leaves.append(leaf)
    return jax.tree.unflatten(treedef, leaves)
