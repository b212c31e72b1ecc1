"""Tensor parallelism: intra-layer (Megatron-style) sharding over a
``"model"`` mesh axis.

The reference has no tensor parallelism — each partition lives wholly on one
node (reference src/dispatcher.py:44-65, one sub-model per IP) — but its
capability frame ("split a model across devices that each hold a piece")
extends naturally to the intra-layer axis on TPU: weight matrices are
sharded across devices, every device computes a partial product, and one
``lax.psum`` over ICI reconstitutes the activation.  This module provides

  * per-op sharding hooks (``Op.tp_shard`` / ``Op.tp_apply``) implemented by
    the matmul-bearing ops (``Dense``, ``TransformerBlock``);
  * :func:`shard_tp_params` — slice a parameter pytree into per-rank shards
    stacked on a leading ``[tp, ...]`` axis for sharded ``device_put``;
  * :func:`tensor_parallel_fn` — a ``shard_map``-wrapped graph forward where
    weights live sharded over the ``model`` axis and activations are
    replicated, XLA inserting the matching ICI collectives.

Sharding scheme (the standard column→row pairing, two psums per
transformer block):

  =============  ==========================  =====================
  parameter      split                       collective
  =============  ==========================  =====================
  Dense.w        rows (input dim)            psum after matmul
  qkv.w / .b     columns, per head group     none (local heads)
  proj.w         rows                        psum before residual
  fc1.w / .b     columns                     none
  fc2.w          rows                        psum before residual
  =============  ==========================  =====================
"""

from __future__ import annotations

from typing import Any

import numpy as np

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.ir import LayerGraph
from .mesh import MODEL_AXIS


def tensor_parallel_mesh(tp: int, devices=None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < tp:
        raise ValueError(f"need {tp} devices, have {len(devices)}")
    return Mesh(np.array(devices[:tp]), (MODEL_AXIS,))


def shard_tp_params(graph: LayerGraph, params: dict[str, Any], tp: int,
                    mesh: Mesh | None = None, axis: str = MODEL_AXIS):
    """Per-rank TP shards of ``params``, stacked on a leading [tp, ...] axis.

    Ops that don't implement ``tp_shard`` are replicated (each rank gets the
    full leaf).  If ``mesh`` is given the result is ``device_put`` with the
    leading axis sharded over ``axis`` so each device materializes only its
    own shard.
    """
    out: dict[str, Any] = {}
    for name, node in graph.nodes.items():
        p = params.get(name)
        if p is None:
            continue
        shards = [node.op.tp_shard(p, tp, r) for r in range(tp)]
        out[name] = jax.tree.map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *shards)
    if mesh is not None:
        out = jax.device_put(
            out, NamedSharding(mesh, P(axis)))
    return out


def tensor_parallel_fn(graph: LayerGraph, mesh: Mesh, axis: str = MODEL_AXIS):
    """Jitted TP forward: ``fn(stacked_params, x) -> y``.

    ``stacked_params`` comes from :func:`shard_tp_params`; ``x`` and ``y``
    are replicated across the ``model`` axis, weights stay sharded.
    """
    tp = mesh.shape[axis]

    def local_fn(pstk, x):
        params = jax.tree.map(lambda a: a[0], pstk)  # my rank's shard
        cache = {graph.input_name: x}
        for name in graph.topo_order:
            node = graph.nodes[name]
            xs = [cache[i] for i in node.inputs]
            cache[name] = node.op.tp_apply(params.get(name), *xs,
                                           axis_name=axis, tp=tp)
        return cache[graph.output_name]

    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=(P(axis), P()),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)
