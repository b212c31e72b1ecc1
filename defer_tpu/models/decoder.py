"""The decoder-model contract: what a graph and its blocks must offer for
the decode engines (``runtime/decode.py``, ``serve/engine.py``) to run
it, whatever its family.

* **Nodes**, by name: ``embeddings`` (an op with ``max_len``, the
  positions the model declares, and ``embed_at(params, ids, pos)``),
  ``block_0..`` in topological order (each a :class:`DecoderBlock`, all
  of one head geometry and sowing the same statistics), ``final_ln``,
  ``lm_head``.  :func:`decoder_parts` checks a graph against this and
  hands back its parts; both engines' constructors call it.
* **Blocks**: :class:`DecoderBlock`.  A block hands key and value
  *columns* to the cache's format (``ops/kv_cache.py``) and takes the
  attention's output back: it knows no axis order, key or type of the
  cache.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..graph.ir import LayerGraph


class DecoderBlock:
    """What every decoder block shares: causal attention over a whole
    sequence, and one token's step in two halves around the cache.  A
    block the decode engines can run has

    * ``num_heads`` / ``kv_heads`` / ``attn_impl``;
    * ``apply_with_kv(params, x [b, t, d]) -> (y, k, v)``: the
      full-sequence forward, with the key and value columns [b, t, kv*hd]
      as :meth:`decode_qkv` would have handed them over row by row;
    * ``decode_qkv(params, x [b, d], pos) -> (q, k_new, v_new)``: the
      query [b, nh*hd] and the new key and value columns [b, kv*hd] of
      the token at position ``pos`` (final when handed over: a family
      with rotary positions rotates its keys here);
    * ``decode_finish(params, x, y, sow=None) -> out``: the rest of the
      block after attention, ``y`` [b, nh*hd] the attention of ``q``
      over the cache with the heads merged.  A block that names
      ``decode_stats`` adds one scalar under each of those names to the
      dict ``sow``;
    * ``stage_arg_keys``: keys of its parameter dict whose leaves the
      ring passes as stage-sharded arguments of their own instead of
      slicing them out of the flat weight row.

    Between the halves the caller writes the columns into its cache and
    attends over it, through ``ops/kv_cache.py``; :meth:`decode` is that
    composition over one layer's buffers.
    """

    #: per-step scalars ``decode_finish`` sows (summed over a generation)
    decode_stats: tuple = ()
    #: parameter subtrees kept out of the flat weight row
    stage_arg_keys: tuple = ()

    def _attend(self, q, k, v):
        """Causal attention on [b, nh, t, hd] by ``attn_impl``: the
        flash kernel (bottom-right aligned) on a TPU, plain XLA elsewhere."""
        impl = self.attn_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "xla"
        if impl not in ("flash", "xla"):
            raise ValueError(
                f"attn_impl must be 'auto', 'flash' or 'xla', got {impl!r}")
        if impl == "flash":
            from ..ops import flash_attention
            return flash_attention(q, k, v, causal=True)
        hd = q.shape[-1]
        t_q, t_k = q.shape[2], k.shape[2]
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        q_pos = jnp.arange(t_q)[:, None] + (t_k - t_q)
        mask = q_pos >= jnp.arange(t_k)[None, :]
        att = jnp.where(mask, att, jnp.asarray(-jnp.inf, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    def decode(self, params, x, cache, pos, fmt):
        """One-token step: ``x`` [b, d] at position ``pos`` against
        ``cache``, one layer's buffers in the format ``fmt``
        (``ops/kv_cache.py::KVCacheFormat``, without groups).  The new
        row is written at ``pos`` and attention covers positions
        ``<= pos``.  Returns ``(out, cache)``.

        The composition of :meth:`decode_qkv`, the format's write and
        attention and :meth:`decode_finish`: the oracle the tests hold
        both engines to.  The pipelined decoder and the serving engine
        call the halves and write into their own buffers.
        """
        q, k_new, v_new = self.decode_qkv(params, x, pos)
        cache = fmt.write_position(cache, fmt.rows(k_new, v_new), pos)
        return self.decode_finish(params, x, fmt.attend(q, cache, pos)), cache


def split_blocks(num_blocks: int, num_stages: int) -> list[list[int]]:
    """Contiguous, balanced block assignment (stage i gets ~L/N blocks)."""
    bounds = [round(num_blocks * s / num_stages)
              for s in range(num_stages + 1)]
    out = [list(range(bounds[s], bounds[s + 1])) for s in range(num_stages)]
    if any(not b for b in out):
        raise ValueError(
            f"{num_blocks} blocks cannot fill {num_stages} stages")
    return out


@dataclasses.dataclass(frozen=True)
class DecoderParts:
    """A graph that met the contract, taken apart."""

    embed_op: Any
    block_names: tuple          #: ``block_*`` in topological order
    d_model: int
    num_heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    max_len: int                #: positions a cache is to hold
    stage_blocks: list          #: per stage, its blocks' names, balanced
    decode_stats: tuple         #: what every block sows each step


def decoder_parts(graph: LayerGraph, num_stages: int,
                  max_len: int | None = None) -> DecoderParts:
    """Check ``graph`` against the contract (module docstring) and
    return its parts; ``max_len`` defaults to the positions the model
    declares and may not exceed them."""
    nodes = graph.nodes
    for req in ("embeddings", "final_ln", "lm_head"):
        if req not in nodes:
            raise ValueError(
                "decoder graphs must follow the node contract of "
                f"models/decoder.py; missing {req!r}")
    embed_op = nodes["embeddings"].op
    if max_len is None:
        max_len = embed_op.max_len      # the positions' reach
    if max_len > embed_op.max_len:
        raise ValueError(
            f"max_len {max_len} exceeds the model's positional table "
            f"({embed_op.max_len})")
    block_names = tuple(nm for nm in graph.topo_order
                        if nm.startswith("block_"))
    for nm in block_names:
        if not isinstance(nodes[nm].op, DecoderBlock):
            raise TypeError(
                f"{nm} ({nodes[nm].op!r}) is not a DecoderBlock "
                "(models/decoder.py): the decode engines need its "
                "decode_qkv / decode_finish / apply_with_kv")
    # an empty block list is refused with split_blocks' message
    stage_blocks = [[block_names[i] for i in idxs]
                    for idxs in split_blocks(len(block_names), num_stages)]
    first = nodes[block_names[0]]
    heads = (first.op.num_heads, first.op.kv_heads)
    stats = tuple(first.op.decode_stats)
    for nm in block_names:
        op = nodes[nm].op
        if (op.num_heads, op.kv_heads) != heads:
            raise ValueError(
                f"{nm} has heads ({op.num_heads}, kv {op.kv_heads}) "
                f"!= block_0's ({heads[0]}, {heads[1]}); the "
                "homogeneous cache needs one head geometry")
        if tuple(op.decode_stats) != stats:
            raise ValueError(
                f"{nm} sows {op.decode_stats}, block_0 {stats}: one "
                "ledger serves every block")
    d_model = first.out_spec.shape[-1]
    return DecoderParts(
        embed_op=embed_op, block_names=block_names, d_model=d_model, num_heads=heads[0], kv_heads=heads[1],
        head_dim=d_model // heads[0],
        vocab=nodes["lm_head"].out_spec.shape[-1], max_len=max_len,
        stage_blocks=stage_blocks, decode_stats=stats)
