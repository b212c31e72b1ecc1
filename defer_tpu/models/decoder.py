"""The decoder-model contract: what a graph and its blocks must offer for
the decode engines (``runtime/decode.py``, ``serve/engine.py``) to run
it, whatever its family.

* **Nodes**, by name: ``embeddings`` (an op with ``max_len``, the
  positions the model declares, and ``embed_at(params, ids, pos)``),
  ``block_0..`` in topological order (each a :class:`DecoderBlock`, all
  of one stream width and sowing the same statistics; *what* a layer
  keeps a sequence, in which geometry and how much of it, is the
  layer's own: a block whose attention has a ``window`` keeps a ring
  buffer of that many rows beside a neighbour that keeps every
  position, a state-space block a state of fixed size beside a
  block that keeps a KV cache, and a block that is a feed-forward part
  alone keeps **nothing**), ``final_ln``, ``lm_head``.  A block is what
  the published model calls a layer: most families' is a mixer *and* a
  second half behind two residuals, but a family whose layers are a
  mixer **or** a feed-forward part alone (``models/nemotron_h.py``)
  has a block a layer, the mixer blocks without a second half.
  :func:`decoder_parts` checks a graph against this and hands back its
  parts; both engines' constructors call it.
* **Blocks**: :class:`DecoderBlock`, whose per-sequence memory is a KV
  cache: it hands key and value *columns* to the cache's format
  (``ops/kv_cache.py``) and takes the attention's output back.  Its
  siblings: :class:`RetentionBlock` keeps a recurrent state of fixed
  size (``q, k, v`` and a log-decay to ``ops/retention.py``, the
  layer's output back); :class:`StateSpaceBlock` a convolution window
  and a state-space state (``ops/ssm.py``, Mamba-1's or Mamba-2's —
  whose channels form heads under a decay each and, where the block
  names ``bc_groups``, groups of heads that share a ``B`` and a ``C``),
  no heads of a cache's kind; :class:`ConvWindowBlock` such a window *alone*
  (``ops/conv_window.py``: a gated short convolution, no position);
  :class:`DeltaRuleBlock` such a window and a square state a head whose
  write *reads it* (``ops/delta_rule.py``: ``q, k, v``, a log-decay a
  channel and the write's strength, the layer's output back);
  :class:`LatentBlock` a latent cache (``ops/latent_cache.py``): one
  row a position that every head shares, which a step attends over
  with queries absorbed into the latent space and a prompt over the
  expanded heads.  Six kinds of per-sequence memory, and
  :class:`MemorylessBlock`, which keeps **none**: ``memory`` is None
  and its format ``ops/layered.py::NoMemory``, which has no key — that
  is the one place "no memory" is said, and a holder reads ``memory``
  alone (it allocates, gauges, idles and re-parents nothing for such a
  layer).  None of the blocks knows an axis order, key or type of what
  its format holds; which kind a block keeps is the class it is
  (``memory``), and the holder asks every block
  (:meth:`DecoderBlock.memory_format`).
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ..graph.ir import LayerGraph


class DecoderBlock:
    """What every decoder block shares: causal attention over a whole
    sequence, and one token's step in two halves around the cache.  A
    block the decode engines can run has

    * ``num_heads`` / ``kv_heads`` / ``attn_impl``, and ``head_dim``
      where a head's width is not the stream's over ``num_heads``;
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
      dict ``sow``.

    Between the halves the caller writes the columns into its cache and
    attends over it, through ``ops/kv_cache.py``; :meth:`decode` is that
    composition over one layer's buffers, :meth:`prefill` a whole
    prompt's.
    """

    #: per-step scalars ``decode_finish`` sows (summed over a generation)
    decode_stats: tuple = ()

    def _attention_impl(self) -> str:
        """``attn_impl`` resolved: ``"flash"`` (the Pallas kernels; what
        ``"auto"`` means on a TPU) or ``"xla"``."""
        impl = self.attn_impl
        if impl == "auto":
            impl = "flash" if jax.default_backend() == "tpu" else "xla"
        if impl not in ("flash", "xla"):
            raise ValueError(
                f"attn_impl must be 'auto', 'flash' or 'xla', got {impl!r}")
        return impl

    def _attend(self, q, k, v, window: int | None = None):
        """Causal attention on [b, nh, t, hd] by ``attn_impl``: the
        flash kernel (bottom-right aligned) on a TPU, plain XLA
        elsewhere.  ``k`` / ``v`` may have fewer heads, each serving a
        group of queries; with ``window`` a row attends its ``window``
        newest keys, itself counted."""
        if self._attention_impl() == "flash":
            from ..ops import flash_attention
            return flash_attention(q, k, v, causal=True, window=window)
        hd = q.shape[-1]
        t_q, t_k = q.shape[2], k.shape[2]
        if k.shape[1] != q.shape[1]:
            k, v = (jnp.repeat(a, q.shape[1] // k.shape[1], axis=1)
                    for a in (k, v))
        att = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(hd)
        q_pos = jnp.arange(t_q)[:, None] + (t_k - t_q)
        mask = q_pos >= jnp.arange(t_k)[None, :]
        if window is not None:
            mask = jnp.logical_and(
                mask, q_pos - jnp.arange(t_k)[None, :] < window)
        att = jnp.where(mask, att, jnp.asarray(-jnp.inf, att.dtype))
        att = jax.nn.softmax(att, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", att, v)

    #: the kind of per-sequence memory the block keeps
    memory = "kv_cache"
    #: how far back the block's attention reaches, its own token
    #: counted; None: every position (a dataclass block may make it a
    #: field)
    window = None

    def geometry(self, d_model: int):
        """``(query heads, KV heads, a head's width)`` of this layer on
        a stream of width ``d_model`` (a block without a ``head_dim`` of
        its own splits the stream among its heads); None for a block
        that has no heads."""
        return (self.num_heads, self.kv_heads,
                getattr(self, "head_dim", None) or d_model // self.num_heads)

    def widest(self, d_model: int) -> int:
        """Columns of the widest activation a token has inside this
        layer: what a holder sizes a prefill's pieces by."""
        heads, _, head_dim = self.geometry(d_model)
        return max(d_model, heads * head_dim)

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """The format of one layer of this block's memory on a stream
        of width ``d_model``, for ``positions`` positions of rows of
        type ``dtype``: what a holder builds its buffers from and hands
        back to :meth:`decode` and :meth:`prefill`.  The format is
        *this layer's*, kind and geometry and length: a block with a
        ``window`` shorter than ``positions`` keeps a ring buffer of
        ``window`` rows, its neighbour without one a row a position,
        and a holder asks every block."""
        from ..ops import kv_cache   # the class as the module names it now
        window = self.window
        if window is not None and window >= positions:
            window = None       # never wraps: a row a position
        heads, kv_heads, head_dim = self.geometry(d_model)
        return kv_cache.KVCacheFormat(
            kv_heads, head_dim, positions, dtype, quantized=quantized,
            groups=groups, window=window, query_group=heads // kv_heads)

    def decode(self, params, x, cache, pos, fmt, slot=None, group=None,
               sow=None):
        """One-token step: ``x`` [b, d] at position ``pos`` against
        ``cache``, one layer's buffers in the format ``fmt``
        (:meth:`memory_format`'s; of group ``group`` where it has
        groups).  The new row is written at ``pos`` and attention covers
        positions ``<= pos``.  ``slot`` is the format's own word for
        where a step's memory goes, made by ``fmt.decode_slot(valid,
        pos)`` and read by the format alone: a holder with bubbles
        passes it, and to a block it is opaque (here None stands for
        "at ``pos``").  Returns ``(out, cache)``.

        The composition of :meth:`decode_qkv`, the format's write and
        attention and :meth:`decode_finish`: what the ring runs a layer
        a step, and the oracle the tests hold both engines to (the
        serving engine calls the halves and writes a position a slot).
        """
        slot = pos if slot is None else slot
        q, k_new, v_new = self.decode_qkv(params, x, pos)
        att, cache = fmt.step(q, cache, fmt.rows(k_new, v_new), slot,
                              group=group)
        return self.decode_finish(params, x, att, sow=sow), cache

    def prefill(self, params, x, cache, fmt, slot):
        """A whole prompt ``x`` [b, t, d] through the layer, its rows
        bulk-written where ``slot`` says (``fmt.prefill_slot(valid,
        group)``'s, opaque to the block like :meth:`decode`'s):
        ``(out, cache)``."""
        x, k, v = self.apply_with_kv(params, x)
        return x, fmt.write_prefix(cache, k, v, slot)


class RetentionBlock(DecoderBlock):
    """A decoder block whose per-sequence memory is a retention state
    (``ops/retention.py``): of fixed size, read *and rewritten whole*
    every step, where a KV cache gains a row.  In place of
    ``apply_with_kv`` / ``decode_qkv`` such a block has

    * ``qkvg(params, x [..., t, d], pos [t]) -> (q, k, v, lg)``: the
      queries [..., t, nh*hd], the keys and values [..., t, kv*hd]
      (final when handed over) and the log-decay [..., t, kv] of the
      tokens at positions ``pos``;
    * ``decode_finish(params, x [T, d], y [T, nh*hd], sow=None)``: the
      rest of the block after the retention's output ``y``.

    The head geometry and ``decode_stats`` are :class:`DecoderBlock`'s.
    No serving engine takes such a block yet (``serve/engine.py``
    refuses every block but GPT's).
    """

    memory = "retention"

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """A state's size does not depend on ``positions``, and it is
        float32 whatever ``dtype`` the block computes in."""
        del positions, dtype
        if quantized:
            raise ValueError(
                "kv_cache='int8' quantizes cached key and value rows; "
                "these blocks keep a retention state, which has none")
        from ..ops import retention
        _, kv_heads, head_dim = self.geometry(d_model)
        return retention.RetentionFormat(kv_heads, head_dim, groups=groups)

    def decode(self, params, x, state, pos, fmt, slot=True, group=None,
               sow=None):
        """One-token step: ``x`` [b, d] at position ``pos`` against one
        layer's ``state``; ``slot`` is ``fmt.decode_slot``'s, handed on
        to the format unread (for a state it says whether the step is
        real: a bubble leaves the state as it is)."""
        q, k, v, lg = self.qkvg(params, x[:, None], jnp.reshape(pos, (1,)))
        y, state = fmt.step(q[:, 0], k[:, 0], v[:, 0], lg[:, 0], state,
                            group=group, valid=slot)
        return self.decode_finish(params, x, y, sow=sow), state

    def prefill(self, params, x, state, fmt, slot=(None, True)):
        """A whole prompt ``x`` [b, t, d] through the layer from an
        empty memory; the state after its last position is left where
        ``slot`` (``fmt.prefill_slot``'s, handed on unread) says."""
        b, t, d = x.shape
        q, k, v, lg = self.qkvg(params, x, jnp.arange(t))
        y, state = fmt.prefill(q, k, v, lg, state, slot)
        out = self.decode_finish(params, x.reshape(b * t, d),
                                 y.reshape(b * t, -1))
        return out.reshape(b, t, d), state


class _WindowedMixerBlock(DecoderBlock):
    """What the blocks share whose memory is a convolution's window and
    a recurrent state behind it (:class:`StateSpaceBlock`,
    :class:`DeltaRuleBlock`): no heads of a cache's kind, the input
    projection as the widest activation, and the walk of a step and of
    a prompt — the window's input, the taps, the convolution, what the
    recurrence takes (``mixer_selection``'s tuple, whatever the
    format's ``step`` / ``prefill`` take), the recurrence."""

    def geometry(self, d_model: int):
        del d_model
        return None

    def widest(self, d_model: int) -> int:
        """The input projection's columns."""
        return max(d_model, self.mixer_width)

    def _walk(self, params, x, state, shift, recur):
        """``(y, selection, rest, state)`` of ``x`` against ``state``:
        ``shift(u, state)`` the window's call, ``recur(*selection,
        state)`` the state's."""
        u, rest = self.mixer_inputs(params, x)
        taps, state = shift(u, state)
        sel = self.mixer_selection(params, self.mixer_conv(params, taps),
                                   rest)
        y, state = recur(*sel, state)
        return y, sel, rest, state

    def _step(self, params, x, state, fmt, slot, group):
        """:meth:`_walk` of one token a sequence."""
        return self._walk(
            params, x, state,
            lambda u, s: fmt.shift(u, s, group=group, valid=slot),
            lambda *a: fmt.step(*a, group=group, valid=slot))

    def _prompt(self, params, x, state, fmt, slot):
        """:meth:`_walk` of a whole prompt ``x`` [b, t, d], and ``rows``,
        which lays a tree's ``[b, t, ..]`` leaves as ``[b * t, ..]``."""
        b, t = x.shape[:2]

        def rows(tree):
            return jax.tree.map(
                lambda v: v.reshape((b * t,) + v.shape[2:]), tree)

        return self._walk(
            params, x, state,
            lambda u, s: fmt.prefill_shift(u, s, slot),
            lambda *a: fmt.prefill(*a, slot)) + (rows,)


class StateSpaceBlock(_WindowedMixerBlock):
    """A decoder block whose per-sequence memory is a state-space
    mixer's (``ops/ssm.py``): the last inputs of a causal convolution
    and a recurrent state ``H``, both of fixed size, the state read
    *and rewritten whole* every step.  One contract serves both mixers
    the package has — Mamba-1, a decay a channel and a state, and
    Mamba-2, heads of channels under one decay each, whose convolution
    also runs over ``B`` and ``C`` — and assumes neither's shapes: the
    block's fields say which it is, and what passes between the block
    and the format has whatever shape that format takes.  In place of
    ``apply_with_kv`` / ``decode_qkv`` such a block has

    * ``channels`` (``E``), ``states`` (``N``) and ``d_conv``, the
      state's sizes; ``heads``: absent or None where every channel and
      state has a decay of its own (``ops/ssm.py::SsmFormat``), else the number
      of heads the channels form, a scalar decay each, over a window
      of ``E + 2 G N`` columns (``SsdFormat``; such a block also names
      ``chunk``, the positions of one chunk of its prefill, and may
      name ``bc_groups``, ``G``: the groups of consecutive heads that
      share one ``B`` and one ``C``, 1 where absent);
      ``mixer_width``, the columns of the input projection, the widest
      activation a token has in the layer;
    * ``mixer_inputs(params, x [..., d]) -> (u, rest)``: the
      convolution's input [..., window's width] and whatever else the
      input projection gives (the gate; Mamba-2's raw step), which the
      block gets back untouched;
    * ``mixer_conv(params, taps) -> c``: the convolution over its
      ``d_conv`` taps (``ops/ssm.py::causal_conv`` under the block's
      weights), as wide as the window;
    * ``mixer_selection(params, c, rest) -> (dt, xs, b, c_read, a)``:
      what the recurrence takes, in the format's shapes — the step
      (``[..., E]``, or ``[..., heads]``), the channels ``xs [..., E]``
      it is fed, the two projections ``[..., N]`` (``[..., G N]``, a
      group after the other, under ``bc_groups``), and ``A`` (``[N,
      E]``, or ``[heads]``);
    * ``decode_finish(params, x [T, d], y [T, E], xs, rest,
      sow=None)``: the rest of the block after the recurrence's output
      ``y`` (the skip term, the gate, the output projection, the
      second half).

    ``decode_stats`` is :class:`DecoderBlock`'s.
    No serving engine takes such a block yet (``serve/engine.py``
    refuses every block but GPT's).
    """

    memory = "ssm"

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """A state's size depends neither on the stream's width nor on
        ``positions``; the window is of type ``dtype``, ``H`` float32.
        Which of ``ops/ssm.py``'s two shapes it has is the block's
        ``heads``."""
        del d_model, positions
        if quantized:
            raise ValueError(
                "kv_cache='int8' quantizes cached key and value rows; "
                "these blocks keep a state-space state, which has none")
        from ..ops import ssm
        heads = getattr(self, "heads", None)
        if heads is None:
            return ssm.SsmFormat(self.channels, self.states, self.d_conv,
                                 dtype, groups=groups)
        return ssm.SsdFormat(heads, self.channels // heads, self.states,
                             self.d_conv, self.chunk, dtype, groups=groups,
                             bc_groups=getattr(self, "bc_groups", 1))

    def decode(self, params, x, state, pos, fmt, slot=True, group=None,
               sow=None):
        """One-token step: ``x`` [b, d] against one layer's ``state``;
        ``slot`` is ``fmt.decode_slot``'s, handed on to the format
        unread (it says whether the step is real: a bubble leaves the
        window and ``H`` as they are).  The position is not read."""
        del pos
        y, sel, rest, state = self._step(params, x, state, fmt, slot, group)
        return self.decode_finish(params, x, y, sel[1], rest, sow=sow), state

    def prefill(self, params, x, state, fmt, slot=(None, True), sow=None):
        """A whole prompt ``x`` [b, t, d] through the layer from an
        empty memory; the window and the state after its last position
        are left where ``slot`` (``fmt.prefill_slot``'s, handed on
        unread) says.  A dict ``sow`` is filled as :meth:`decode`
        fills it, over all ``b * t`` rows."""
        y, sel, rest, state, rows = self._prompt(params, x, state, fmt, slot)
        out = self.decode_finish(params, rows(x), rows(y), rows(sel[1]),
                                 rows(rest), sow=sow)
        return out.reshape(x.shape), state


class DeltaRuleBlock(_WindowedMixerBlock):
    """A decoder block whose per-sequence memory is a delta rule's
    (``ops/delta_rule.py``): the last inputs of short causal
    convolutions over ``q``, ``k`` and ``v`` side by side, and a square
    float32 state a head, read *and rewritten whole* every step by a
    write that reads it — ``S <- (I - beta k k^T) Diag(alpha) S + beta
    k v^T``, ``alpha`` a decay a key channel.  No position is read.  In
    place of ``apply_with_kv`` / ``decode_qkv`` such a block has

    * ``heads`` and ``head_dim`` (a head's key and value channels),
      ``d_conv`` and ``chunk`` (the positions of one chunk of its
      prefill), the memory's sizes; ``mixer_width``, the columns of the
      convolutions' input (``3 heads head_dim``), the widest activation
      a token has in the layer;
    * ``mixer_inputs(params, x [..., d]) -> (u, rest)``: the
      convolutions' input ``[q, k, v]`` [..., mixer_width] and whatever
      else the layer makes of the normed stream (the decay's and the
      gate's projections, ``beta``), which the block gets back
      untouched;
    * ``mixer_conv(params, taps) -> c``: the convolutions over their
      ``d_conv`` taps (``ops/ssm.py::causal_conv`` under the block's
      weights), as wide as the window;
    * ``mixer_selection(params, c, rest) -> (q, k, v, g, beta)``: what
      the recurrence takes — ``q`` / ``k`` / ``v`` [..., heads *
      head_dim] final (normed, scaled), the log-decay ``g`` [..., heads
      * head_dim] and ``beta`` [..., heads], float32;
    * ``decode_finish(params, x [T, d], y [T, heads * head_dim], rest,
      sow=None)``: the rest of the block after the recurrence's output
      ``y`` (the norm a head, the gate, the output projection, the
      second half).

    ``decode_stats`` is :class:`DecoderBlock`'s.  No serving engine
    takes such a block yet (``serve/engine.py`` refuses every block but
    GPT's).
    """

    memory = "delta_rule"

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """A state's size depends neither on the stream's width nor on
        ``positions``; the window is of type ``dtype``, ``S`` float32."""
        del d_model, positions
        if quantized:
            raise ValueError(
                "kv_cache='int8' quantizes cached key and value rows; "
                "these blocks keep a delta-rule state, which has none")
        from ..ops import delta_rule
        return delta_rule.DeltaFormat(self.heads, self.head_dim, self.d_conv,
                                      self.chunk, dtype, groups=groups)

    def decode(self, params, x, state, pos, fmt, slot=True, group=None,
               sow=None):
        """One-token step: ``x`` [b, d] against one layer's ``state``;
        ``slot`` is ``fmt.decode_slot``'s, handed on to the format
        unread (it says whether the step is real: a bubble leaves the
        window and ``S`` as they are).  The position is not read."""
        del pos
        y, _, rest, state = self._step(params, x, state, fmt, slot, group)
        return self.decode_finish(params, x, y, rest, sow=sow), state

    def prefill(self, params, x, state, fmt, slot=(None, True), sow=None):
        """A whole prompt ``x`` [b, t, d] through the layer from an
        empty memory; the window and the state after its last position
        are left where ``slot`` (``fmt.prefill_slot``'s, handed on
        unread) says.  A dict ``sow`` is filled as :meth:`decode`
        fills it, over all ``b * t`` rows."""
        y, _, rest, state, rows = self._prompt(params, x, state, fmt, slot)
        out = self.decode_finish(params, rows(x), rows(y), rows(rest),
                                 sow=sow)
        return out.reshape(x.shape), state


class LatentBlock(DecoderBlock):
    """A decoder block whose per-sequence memory is a latent cache
    (``ops/latent_cache.py``): **one row a position** — the normalised
    latent and, behind it, the one rotated key every head shares —
    where a KV cache keeps a key and a value a head.  One layer, two
    attention paths that must agree: a step attends *in the latent
    space*, every head on the same row (the up-projections of keys and
    values absorbed into the query and the output), a prompt over the
    *expanded* heads.  In place of ``apply_with_kv`` / ``decode_qkv``
    such a block has

    * ``num_heads``, ``latent_dim`` (the latent's columns),
      ``rope_dim`` (the shared key's), ``nope_dim`` (a head's own key
      columns), ``v_dim`` (a head's value columns) and
      ``softmax_scale``, what a score is multiplied by;
    * ``decode_q_row(params, x [b, d], pos) -> (q [b, heads * (latent
      + rope)], row [b, latent + rope])``: every head's query *already
      absorbed* (``q_nope W_uk`` and the rotated ``q_rope``) and the
      token's one new row, final when handed over;
    * ``decode_finish(params, x, y [b, heads * latent], sow=None)``:
      the rest of the block after the attention's output in the latent
      space (``W_uv``, the output projection, the second half);
    * ``apply_with_rows(params, x [b, t, d], sow=None) -> (y, rows [b,
      t, latent + rope])``: the full-sequence forward over the
      expanded heads, with the rows as :meth:`decode_q_row` would have
      handed them over one by one.

    **Sublayers.**  A block may hold several latent-attention
    sublayers (``sublayers``; ``models/longcat_flash.py``'s double
    layer has two around one shortcut-connected MoE).  It then keeps a
    row buffer *a sublayer*, all of one format, and a step takes one
    turn around the cache a sublayer, *inside the block*: what lives
    across a turn (the shortcut's output) never leaves it, so a stage
    cut, which falls between blocks, never meets it.  Such a block
    overrides :meth:`round_q_row` and :meth:`round_finish`, which take
    the sublayer's index, and its ``apply_with_rows`` hands back a
    tuple of rows, one a sublayer; a block of one sublayer has the two
    halves above and nothing else.

    ``decode_stats`` is :class:`DecoderBlock`'s.  No serving engine
    takes such a block yet (``serve/engine.py`` refuses every block but
    GPT's).
    """

    memory = "latent_cache"
    #: latent-attention sublayers of the block: row buffers a layer
    #: keeps, turns around the cache a step
    sublayers = 1

    def geometry(self, d_model: int):
        """Every head has a key of its own once expanded: ``(heads,
        heads, nope + rope)``."""
        del d_model
        return (self.num_heads, self.num_heads,
                self.nope_dim + self.rope_dim)

    def widest(self, d_model: int) -> int:
        """The queries' columns, or the expanded keys and values'."""
        return max(d_model,
                   self.num_heads * (self.nope_dim + self.rope_dim),
                   self.num_heads * (self.nope_dim + self.v_dim))

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """A row's width is the block's own, whatever the stream's."""
        del d_model
        if quantized:
            raise ValueError(
                "kv_cache='int8' quantizes cached key and value rows; "
                "these blocks keep a latent cache, which has none")
        from ..ops import latent_cache
        return latent_cache.LatentCacheFormat(
            self.latent_dim, self.rope_dim, positions, dtype,
            self.softmax_scale, groups=groups, sublayers=self.sublayers)

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]:
        :meth:`apply_with_rows`' first result."""
        lead = x.shape[:-2]
        y = self.apply_with_rows(
            params, x.reshape((-1,) + x.shape[-2:]), sow)[0]
        return y.reshape(lead + y.shape[-2:])

    def round_q_row(self, params, x, pos, sublayer: int):
        """:meth:`decode_q_row` of sublayer ``sublayer``."""
        del sublayer
        return self.decode_q_row(params, x, pos)

    def round_finish(self, params, x, y, sublayer: int, carry, sow=None):
        """The rest of sublayer ``sublayer`` after its attention's
        output ``y``: ``(the stream, carry)``, ``carry`` whatever the
        block keeps alive for a later sublayer of the same step (None
        into the first)."""
        del sublayer, carry
        return self.decode_finish(params, x, y, sow=sow), None

    def decode(self, params, x, cache, pos, fmt, slot=None, group=None,
               sow=None):
        """One-token step, a round a sublayer: the new row written at
        ``pos`` (``slot``: ``fmt.decode_slot``'s, opaque here as in
        :meth:`DecoderBlock.decode`) of the sublayer's buffer, then
        every head's absorbed query over that buffer's rows ``<=
        pos``."""
        slot = pos if slot is None else slot
        carry = None
        for i in range(self.sublayers):
            q, row = self.round_q_row(params, x, pos, i)
            att, cache = fmt.step(q, cache, fmt.rows(row), slot,
                                  group=group, sublayer=i)
            x, carry = self.round_finish(params, x, att, i, carry, sow)
        return x, cache

    def prefill(self, params, x, cache, fmt, slot):
        """A whole prompt ``x`` [b, t, d] through the layer over the
        expanded heads, each sublayer's rows bulk-written where
        ``slot`` says."""
        x, rows = self.apply_with_rows(params, x)
        for i, r in enumerate(rows if isinstance(rows, tuple) else (rows,)):
            cache = fmt.write_prefix(cache, r, slot, sublayer=i)
        return x, cache


class ConvWindowBlock(DecoderBlock):
    """A decoder block whose per-sequence memory is the window of a
    short depthwise causal convolution and **nothing else**
    (``ops/conv_window.py``): the last ``d_conv - 1`` inputs, of fixed
    size whatever the text's length, moved on by one row a step.  The
    mixer is *gated on both sides* by projections of the same input —
    ``[B, C, X] = u W_in``, the convolution runs over ``z = B * X`` and
    ``C`` multiplies what it gives — and goes on to no selection, no
    recurrence and no attention: no position is read and there are no
    heads.  In place of ``apply_with_kv`` / ``decode_qkv`` such a block
    has

    * ``channels`` (the convolution's columns) and ``d_conv`` (its
      taps), the window's sizes; ``mixer_width``, the columns of the
      input projection, the widest activation a token has in the
      layer;
    * ``mixer_inputs(params, x [..., d]) -> (z, c_gate)``: the
      convolution's input ``B * X`` [..., channels] — what the window
      keeps — and the output gate ``C``, which the block gets back
      untouched;
    * ``mixer_conv(params, taps) -> c``: the convolution over its
      ``d_conv`` taps (``ops/ssm.py::causal_conv`` under the block's
      weights, without bias or activation);
    * ``decode_finish(params, x [T, d], c [T, channels], c_gate,
      sow=None)``: the rest of the block after the convolution (the
      gate, the output projection, the second half).

    ``decode_stats`` is :class:`DecoderBlock`'s.  No serving engine
    takes such a block yet (``serve/engine.py`` refuses every block but
    GPT's).
    """

    memory = "conv_window"

    def geometry(self, d_model: int):
        del d_model
        return None

    def widest(self, d_model: int) -> int:
        """The input projection's columns."""
        return max(d_model, self.mixer_width)

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """A window's size depends neither on the stream's width nor on
        ``positions``; it is of type ``dtype``."""
        del d_model, positions
        if quantized:
            raise ValueError(
                "kv_cache='int8' quantizes cached key and value rows; "
                "these blocks keep a convolution window, which has none")
        from ..ops import conv_window
        return conv_window.ConvWindowFormat(self.channels, self.d_conv,
                                            dtype, groups=groups)

    def decode(self, params, x, state, pos, fmt, slot=True, group=None,
               sow=None):
        """One-token step: ``x`` [b, d] against one layer's window;
        ``slot`` is ``fmt.decode_slot``'s, handed on to the format
        unread (it says whether the step is real: a bubble leaves the
        window as it is).  The position is not read."""
        del pos
        z, c_gate = self.mixer_inputs(params, x)
        taps, state = fmt.shift(z, state, group=group, valid=slot)
        c = self.mixer_conv(params, taps)
        return self.decode_finish(params, x, c, c_gate, sow=sow), state

    def prefill(self, params, x, state, fmt, slot=(None, True), sow=None):
        """A whole prompt ``x`` [b, t, d] through the layer from an
        empty window; the window after its last position is left where
        ``slot`` (``fmt.prefill_slot``'s, handed on unread) says.  A
        dict ``sow`` is filled as :meth:`decode` fills it, over all ``b
        * t`` rows."""
        b, t, d = x.shape
        z, c_gate = self.mixer_inputs(params, x)
        taps, state = fmt.prefill_shift(z, state, slot)
        c = self.mixer_conv(params, taps)
        out = self.decode_finish(params, x.reshape(b * t, d),
                                 c.reshape(b * t, -1),
                                 c_gate.reshape(b * t, -1), sow=sow)
        return out.reshape(b, t, d), state


class MemorylessBlock(DecoderBlock):
    """A decoder block that keeps **nothing** a sequence: a feed-forward
    part alone behind its one norm and residual — no mixer, no cache,
    no state, no position (``models/nemotron_h.py``'s ``E`` layers: a
    published layer there is a mixer *or* a feed-forward part, and a
    block is a layer).  ``memory`` is None and the format
    ``ops/layered.py::NoMemory``, which has no key: a holder gives such
    a layer no buffer, and what it hands :meth:`decode` and
    :meth:`prefill` as the layer's memory is an empty dict that comes
    back as it went.  In place of every other half such a block has

    * ``feed_forward(params, x [T, d], sow=None) -> out``: the whole
      layer on ``T`` rows of the stream, whatever sequence or position
      each is; it sows :attr:`decode_stats` as the other blocks do;
    * optionally ``mixer_width``, the columns of its widest activation
      a token (else the stream's).

    Beams and int8 rows are other layers' business: this one has
    nothing to re-parent and nothing to quantise, and refuses neither.
    ``decode_stats`` is :class:`DecoderBlock`'s.  No serving engine
    takes such a block yet (``serve/engine.py`` refuses every block but
    GPT's).
    """

    memory = None

    def geometry(self, d_model: int):
        del d_model
        return None

    def widest(self, d_model: int) -> int:
        return max(d_model, getattr(self, "mixer_width", d_model))

    def memory_format(self, d_model: int, positions: int, dtype, *,
                      quantized: bool = False, groups: int | None = None):
        """No memory: the format that has no key."""
        del d_model, positions, dtype, quantized
        from ..ops import layered
        return layered.NoMemory(groups=groups)

    def apply(self, params, x, sow=None):
        """Full-sequence forward on ``x`` [b, t, d] or [t, d]: every
        row alike."""
        return self.feed_forward(
            params, x.reshape(-1, x.shape[-1]), sow=sow).reshape(x.shape)

    def decode(self, params, x, state, pos, fmt, slot=None, group=None,
               sow=None):
        """One-token step: ``x`` [b, d]; ``state`` (an empty dict) is
        handed back untouched, and position, slot and group are not
        read — a bubble's rows are computed like any others and dropped
        by the holder."""
        del pos, fmt, slot, group
        return self.feed_forward(params, x, sow=sow), state

    def prefill(self, params, x, state, fmt, slot=None, sow=None):
        """A whole prompt ``x`` [b, t, d] through the layer: its ``b *
        t`` rows."""
        del fmt, slot
        return self.apply(params, x, sow=sow), state


def split_blocks(num_blocks: int, num_stages: int) -> list[list[int]]:
    """Contiguous, balanced block assignment (stage i gets ~L/N blocks):
    the even rule, which counts blocks and no ends.  The cut a holder's
    bytes choose (:func:`balanced_cut`) falls back on it at a tie."""
    bounds = [round(num_blocks * s / num_stages)
              for s in range(num_stages + 1)]
    out = [list(range(bounds[s], bounds[s + 1])) for s in range(num_stages)]
    if any(not b for b in out):
        raise ValueError(
            f"{num_blocks} blocks cannot fill {num_stages} stages")
    return out


def _off_pattern(cut, kinds):
    """The first layer of ``cut`` (blocks a stage) whose kind of memory
    is not that of the longest stage's layer at the same place: ``(its
    stage, its place there, its block, the longest stage's block)``, or
    None where every stage repeats the longest's ``kinds`` in order."""
    bounds = list(itertools.accumulate(cut, initial=0))
    at = bounds[cut.index(max(cut))]        # where the longest stage starts
    return next(((s, l, b + l, at + l)
                 for s, (b, count) in enumerate(zip(bounds, cut))
                 for l in range(count) if kinds[b + l] != kinds[at + l]),
                None)


def check_cut(cut, names, kinds) -> None:
    """Refuse ``cut`` (blocks a stage, over the blocks ``names`` in
    order) unless the ring can run it: a block or more on every stage,
    all of them laid out, and every stage's layers repeating the longest
    stage's ``kinds`` of memory (one a block: its format) in order."""
    if sum(cut) != len(names) or min(cut) < 1:
        raise ValueError(
            f"cut {list(cut)} does not lay {len(names)} blocks on "
            f"{len(cut)} stages, one or more a stage")
    off = _off_pattern(cut, kinds)
    if off is not None:
        s, l, mine, theirs = off
        raise ValueError(
            f"stage {s}'s layer {l} ({names[mine]}) keeps {kinds[mine]}, "
            f"{names[theirs]} at the same place of its stage "
            f"{kinds[theirs]}: the ring shards one buffer a local layer "
            "over the stages, so every stage's layers must repeat the "
            "same kinds of memory in the same order (cut the graph at a "
            "whole period of its layer pattern)")


def balanced_cut(costs, num_stages: int, kinds=None, *, first=0,
                 last=0) -> list[int]:
    """Blocks a stage: the contiguous cut of ``len(costs)`` blocks whose
    costliest stage costs least, among the cuts :func:`check_cut` lets
    pass.  A stage costs its blocks' ``costs`` and, on the first stage,
    ``first``, on the last ``last`` (the ends it holds besides; whole
    numbers — a holder's bytes — tie exactly).  Ties: :func:`split_blocks`'
    cut where it is among the best, else the fewest blocks on the
    longest stage (the fewest zero leaves), then the bounds nearest the
    even cut's.  Where no cut passes, the even one, for its refusal."""
    n = len(costs)
    kinds = list(kinds) if kinds is not None else [None] * n
    even = [len(b) for b in split_blocks(n, num_stages)]
    even_bounds = list(itertools.accumulate(even))
    cum = list(itertools.accumulate(costs, initial=0))
    ends = [0] * num_stages
    ends[0] += first
    ends[-1] += last

    def rank(cut):
        bounds = list(itertools.accumulate(cut, initial=0))
        return (max(cum[b + c] - cum[b] + e
                    for b, c, e in zip(bounds, cut, ends)),
                cut != even, max(cut),
                sum(abs(b - e) for b, e in zip(bounds[1:], even_bounds)))

    best = None     # (its rank, the cut)

    def offer(cut):
        nonlocal best
        if _off_pattern(cut, kinds) is None:
            ranked = rank(cut), cut
            if best is None or ranked < best:
                best = ranked

    offer(even)

    def grow(cut, at):
        stage, left = len(cut), num_stages - len(cut) - 1
        if not left:
            offer(cut + [n - at])
            return
        for count in range(1, n - at - left + 1):
            if best is not None:
                # no stage over the best cut's costliest; and what is
                # left has to fit under it on the stages that are left
                if cum[at + count] - cum[at] + ends[stage] > best[0][0]:
                    break
                if cum[n] - cum[at + count] + ends[-1] > left * best[0][0]:
                    continue
            grow(cut + [count], at + count)

    grow([], 0)
    return best[1] if best is not None else even


@dataclasses.dataclass(frozen=True)
class DecoderParts:
    """A graph that met the contract, taken apart."""

    embed_op: Any
    block_names: tuple          #: ``block_*`` in topological order
    d_model: int                #: the stream's width, every block's
    vocab: int
    max_len: int                #: positions a cache is to hold
    stage_blocks: list          #: per stage, its blocks' names
    decode_stats: tuple         #: what every block sows each step
    #: per block, the kind of memory it keeps (``DecoderBlock.memory``;
    #: None: none)
    memory: tuple
    #: per block, ``(query heads, KV heads, a head's width)``, or None
    #: for a block without heads (``DecoderBlock.geometry``)
    geometry: tuple


def decoder_parts(graph: LayerGraph, num_stages: int,
                  max_len: int | None = None, *, cut=None,
                  step_bytes=None) -> DecoderParts:
    """Check ``graph`` against the contract (module docstring) and
    return its parts; ``max_len`` defaults to the positions the model
    declares and may not exceed them.

    Which blocks a stage holds (``stage_blocks``): with one stage all
    of them, and nothing is reckoned.  With more, ``cut`` (blocks a
    stage, ``[8, 8, 8, 4]``) where a caller hands one in — a planner
    that timed the stages, a deployment whose layer pattern it knows —
    refused by :func:`check_cut` unless the ring can run it.  Else,
    where the holder says what one ring step reads of each node
    (``step_bytes``: ``{node: bytes}`` over the blocks and the three
    ends), :func:`balanced_cut` over those: a stage costs what it reads
    a step, the first the embedding's gathered rows and the last the
    final norm and the head on top of their blocks, so a large head
    takes layers off the last stage.  Else the even rule
    (:func:`split_blocks`), unchecked: the serving engine walks the
    blocks in order and its stages are the planner's structure alone."""
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
                "decode / prefill and the halves they are made of")
    # an empty block list is refused with split_blocks' message
    even = [len(b) for b in split_blocks(len(block_names), num_stages)]
    first = nodes[block_names[0]]
    d_model = first.out_spec.shape[-1]
    ops = [nodes[nm].op for nm in block_names]
    if cut is None and (num_stages == 1 or step_bytes is None):
        cut = even
    else:
        # a layer's kind of memory is its format; the type and the
        # quantisation are the holder's, alike on every layer, and
        # stand out of the comparison
        kinds = [op.memory_format(d_model, max_len, jnp.float32,
                                  groups=num_stages) for op in ops]
        if cut is None:
            cut = balanced_cut(
                [step_bytes[nm] for nm in block_names], num_stages, kinds,
                first=step_bytes["embeddings"],
                last=step_bytes["final_ln"] + step_bytes["lm_head"])
        if len(cut) != num_stages:
            raise ValueError(
                f"cut {list(cut)} names {len(cut)} stages, the ring has "
                f"{num_stages}")
        check_cut(list(cut), block_names, kinds)
    bounds = list(itertools.accumulate(cut, initial=0))
    stage_blocks = [list(block_names[b:b + count])
                    for b, count in zip(bounds, cut)]
    stats = tuple(first.op.decode_stats)
    for nm in block_names:
        op = nodes[nm].op
        # what the ring needs alike of every block: the stream it hands
        # from one to the next, and the ledger their sums share.  Kind,
        # geometry and length of a layer's memory are the layer's own.
        if nodes[nm].out_spec.shape[-1] != d_model:
            raise ValueError(
                f"{nm}'s stream is {nodes[nm].out_spec.shape[-1]} wide, "
                f"block_0's {d_model}: one activation crosses every block")
        if tuple(op.decode_stats) != stats:
            raise ValueError(
                f"{nm} sows {op.decode_stats}, block_0 {stats}: one "
                "ledger serves every block")
    return DecoderParts(
        embed_op=embed_op, block_names=block_names, d_model=d_model,
        vocab=nodes["lm_head"].out_spec.shape[-1], max_len=max_len,
        stage_blocks=stage_blocks, decode_stats=stats,
        memory=tuple(op.memory for op in ops),
        geometry=tuple(op.geometry(d_model) for op in ops))
