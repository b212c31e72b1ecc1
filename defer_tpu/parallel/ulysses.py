"""Ulysses-style sequence parallelism: all_to_all head-scatter attention.

The second of the two standard SP schemes (ring attention being the other,
``ring_attention.py``): instead of rotating K/V shards around a ring, two
``lax.all_to_all`` exchanges re-shard the tensors from sequence-sharded
[B, H, T/N, D] to head-sharded [B, H/N, T, D], run ordinary full attention
locally over the complete sequence, and shard back.  Communication is
O(T·D·H/N) per device independent of N hops (vs the ring's N-1 neighbor
hops), so it wins when the head count comfortably exceeds the mesh size and
the fabric provides good all-to-all bandwidth; the ring wins at very long T
(smaller live buffers).  Both produce exact attention.

Requires num_heads % mesh_size == 0; the global sequence must be evenly
sharded.
"""

from __future__ import annotations

import functools

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .ring_attention import SEQ_AXIS, full_attention


def ulysses_attention(q, k, v, *, axis_name: str = SEQ_AXIS,
                      causal: bool = False):
    """Exact attention on sequence-sharded q/k/v via head scatter.

    Call inside ``shard_map``; q/k/v are local shards [B, H, T/N, D].
    Returns the local output shard [B, H, T/N, D].
    """
    n = lax.axis_size(axis_name)
    h = q.shape[1]
    if h % n:
        raise ValueError(f"num_heads={h} not divisible by mesh size {n}")

    def scatter_heads(x):
        # [b, h, tl, d] -> [b, h/n, T, d]: head chunk j goes to device j,
        # received sequence shards concatenate into the full sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def gather_heads(x):
        # inverse: [b, h/n, T, d] -> [b, h, tl, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = full_attention(qh, kh, vh, causal=causal)
    return gather_heads(out)


def sequence_parallel_attention_ulysses(q, k, v, mesh: Mesh, *,
                                        axis_name: str = SEQ_AXIS,
                                        causal: bool = False):
    """Convenience wrapper: global [B,H,T,D] in, attention out, sequence dim
    sharded over ``mesh[axis_name]`` with all_to_all head exchange."""
    spec = P(None, None, axis_name, None)
    fn = jax.shard_map(
        functools.partial(ulysses_attention, axis_name=axis_name,
                          causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
