"""Continuous-batching autoregressive decode: per-request KV state rides
through the pipeline stages; requests join and leave between steps.

:class:`~defer_tpu.runtime.decode.PipelinedDecoder` decodes one CLOSED
batch: every sequence enters together, decodes in lockstep, and exits
together — a serving system driving it would pay head-of-line blocking
(a 512-token request holds a 5-token request's slot hostage) and refill
bubbles (the whole batch must drain before new prompts enter).  This
engine is continuous batching proper:

* The batch is ``width`` SLOTS.  Each slot holds one request's state —
  its prompt, its position, and its OWN row range of every layer's KV
  cache: one f32 buffer of ``width`` sequences a layer and key, in the
  cache's format (``ops/kv_cache.py``, which describes the layout; the
  ring's buffers without its groups), every one a donated argument of
  the step and its aliased output.
* Between any two decode steps, finished requests leave (slot freed,
  tokens delivered) and waiting requests join (slot claimed); the step
  program itself never changes — one compiled program per width serves
  every batch composition.
* A joining request's prompt is prefilled in ONE pass, between two
  steps: positions ``0 .. plen - 2`` (the first
  :data:`PREFILL_POSITIONS` of them; one compiled length, the prompt
  padded to it) through every block's ``prefill`` — one program a few
  blocks long, called once a group of them — their key and value rows
  written into the slot's own rows of every layer's buffer.  The slot
  enters the step loop at the position behind them, so its first step
  feeds the last prompt token and produces the first generated one.
  Fed a token a step instead, a prompt costs a step a token that nobody
  is paid for: half of a short chat's steps.  Causal attention hides
  the padding from the prompt's own rows, and the rows the padding
  writes are each rewritten by a decode step before any query can see
  them.
* A step is one token per active slot: teacher-forced from the prompt
  while ``pos < prompt_len`` (the last prompt token, and the tail of a
  prompt longer than one prefill takes), sampled past it — and past it
  the step reads the id it feeds from the DEVICE array the step before
  it returned, never from the host.  A
  layer is the block's own halves, as the ring calls them:
  ``decode_qkv`` on the whole ``[width, d]`` batch, each slot's new row
  written in place at that slot's OWN position (the format's
  ``write_slots``: slots sit at different positions, and a vmapped
  write would be a batched scatter over a re-laid-out item,
  docs/DECODE_CLIFF.md), attention with each slot's own live mask, then
  ``decode_finish``.  The cache's two kernels walk the list of LIVE
  slots the host sends with the step's other rows
  (``kv_cache.live_slots``: who has a step left is known before the
  step in flight is read) and touch no other slot: an idle slot's
  window is not moved, its keys and values are not read, its attention
  is zeros and its id is dropped at delivery — under the knee most
  slots are idle, and moving their rows was 40% of a step.  Every row's
  computation reads its own rows only, so a row's output bytes are
  INDEPENDENT of who shares the batch — per-request outputs are
  byte-identical to the request run alone, the correctness bar
  continuous batching must meet.
* Sampling keys are ``fold_in(request_seed, position)`` per row —
  deterministic per request regardless of batch composition or join
  step.
* The engine keeps ONE step launched ahead of the one whose tokens it
  reads: a steady round is ``launch(n+1)``, ``sync(n)``,
  ``delivery(n)``, the loop's join sweep, ``gather(n+2)`` — as the
  ring's is ``dispatch(n+1)``, ``sync(n)``, ``scatter(n)``, ``emit(n)``
  (``runtime/decode.py``).  Nothing step n+1 needs waits for step n's
  tokens on the host: its ids are step n's device output, positions
  advance by one, seeds and temperatures are the request's, and a slot
  finishes by ``max_new_tokens`` alone, so which slots step n+1 holds
  is known before a token of step n is.  The chip has its next program
  while the host wakes, delivers, joins, gathers, uploads and launches.

The stage structure mirrors the deployed chain's partition (same
``split_blocks`` assignment), so the planner's per-stage latency budget
(``plan.cost.stage_ms_at_batch``) prices this engine's step the same way
it prices a chain frame; it is the planner's structure only, the step
walks the blocks in order.  Execution here is in-process (one jitted
step); carrying the per-slot caches through OS-process stage nodes
needs stateful stage artifacts — the documented next step
(docs/SERVING.md), not this PR.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.ir import LayerGraph
from ..models.decoder import decoder_parts
from ..models.gpt import CausalTransformerBlock, GptEmbedding
from ..obs import REGISTRY, span, spanned_first_call
from ..obs.events import emit as emit_event
from ..ops import kv_cache
from ..runtime.decode import sample_ids
from .batcher import _stamp_popped

#: the positions one prefill takes: the one length its programs are
#: compiled for (an engine's is ``min(this, max_len - 1)``).  A shorter
#: prompt is padded to it, a longer one has its tail teacher-forced by
#: the step.  One length and no buckets: every length is more programs
#: in the door's set-up, and at 128 positions a prefill is still bound
#: by the weights it reads, as a step is.
PREFILL_POSITIONS = 128
#: blocks one prefill program walks.  The engine calls it once a group
#: of as many: long enough that a prompt's few launches run ahead of the
#: device, short enough that a door's set-up traces and loads little
PREFILL_LAYERS = 8


@dataclasses.dataclass(eq=False)
class DecodeRequest:
    """One admitted generation request.  Compared by identity: the door
    looks a finished request up among its client's live ones, and a
    field-wise ``==`` over two prompt arrays has no truth value."""

    prompt: np.ndarray                 #: [prompt_len] int token ids
    max_new_tokens: int
    tenant: str = "default"
    request_id: int = 0
    seed: int = 0
    temperature: float = 0.0
    #: called with the finished [prompt_len + new] int64 ids (or None on
    #: cancellation) from the engine's step thread
    on_done: Callable[[Any], None] | None = None
    queued_at: float = 0.0
    #: set by the front door when the client disconnects while this
    #: request is still queued — the engine loop must not join it
    cancelled: bool = False
    #: where the request's time in the engine went (:class:`Waypoints`):
    #: set when its last token is delivered, before ``on_done``; ``None``
    #: until then, and for ever on a cancelled request
    waypoints: "Waypoints | None" = None

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class Waypoints:
    """A finished request's life in the engine, on ``time.perf_counter``,
    each stamp a clock read the loop makes anyway, taken where the thing
    happens.  The door lays its own ends around them (``queued_pc``,
    ``popped_at``, its ``done`` behind the socket write) and the six
    tile the request's timeline (``obs/attrib.py::DECODE_BUCKETS``)."""

    __slots__ = ("prefill_at", "first_at", "last_at", "rounds",
                 "pass_rounds", "worst_gap", "first_step", "last_step",
                 "forced_steps")

    def __init__(self):
        #: the ``engine.prefill`` span's ``t0``: the prompt's pass begins
        #: to be launched (for a prompt of one token, which has no pass,
        #: the ``engine.launch`` of the step that first takes the slot in)
        self.prefill_at: float | None = None
        #: the ``engine.sync`` span's ``t1`` of the step whose delivery
        #: appended the first generated id, and of the one that delivered
        #: the last: the instant the ids reached host memory
        self.first_at: float | None = None
        self.last_at: float | None = None
        #: delivering steps from the first id to the last: the answer's
        #: length
        self.rounds = 0
        #: of the ``rounds - 1`` behind the first id — the gaps between
        #: two of the request's tokens — those whose flight had another
        #: slot's pass launched in front of it (its own pass rides in
        #: front of a round that is no gap), and the longest of them in
        #: seconds (what ``step_s`` recorded for that step)
        self.pass_rounds = 0
        self.worst_gap = 0.0
        #: ``engine.steps`` as the first and the last id were read: the
        #: ``step`` of the ``engine.step`` span that launched each
        self.first_step = self.last_step = -1
        #: steps that fed the slot a prompt token and produced no
        #: generated id: the tail of a prompt over ``PREFILL_POSITIONS``
        #: + 1.  They lie between ``prefill_at`` and ``first_at``
        self.forced_steps = 0


class _Slot:
    __slots__ = ("req", "pos", "prefill", "out", "cancelled", "way")

    def __init__(self, req: DecodeRequest, prefill: int = 0):
        self.req = req
        #: next position to feed to a step: it advances when a step is
        #: LAUNCHED, a step before that step's token is read
        self.pos = prefill
        #: prompt positions ``0 .. prefill - 1`` still to go through the
        #: prefill, which the next ``step()`` runs first
        self.prefill = prefill
        self.out: list[int] = []   #: generated ids, as they are read
        self.cancelled = False
        self.way = Waypoints()

    def steps_left(self) -> bool:
        """Whether a step is still to be launched for this slot: the
        last one feeds position ``plen + max_new_tokens - 2``.  Known
        without any token."""
        return self.pos < self.req.prompt.size + self.req.max_new_tokens - 1


@dataclasses.dataclass
class _Flight:
    """A launched step whose ids the host has not read."""

    ids: Any                    #: the step's ``[width]`` ids, on the device
    #: ``(slot index, slot, position fed)`` a row: the slot object, not
    #: only its index, which may belong to a later tenant by delivery
    rows: list
    #: where the round ``step_s`` records began: the launch, for a step
    #: launched onto an empty queue; the step before's ids reaching the
    #: host, for one launched ahead (set when they do)
    since: float
    #: prompts' passes launched in front of this step since the launch
    #: before it: the device runs them first, so the round is that much
    #: longer and the wait for its ids is no pause of the host
    passes: int = 0


class ContinuousBatchEngine:
    """Step-wise decoder over ``width`` request slots.

    The engine is PASSIVE: callers (the front door's decode loop, or a
    test) drive it with :meth:`join` / :meth:`cancel` between calls to
    :meth:`step`.  All three must be called from one scheduling thread
    (the slot table is not locked against concurrent mutation; the
    front door owns that thread).  Between two calls one step may be
    running on the device, launched and not yet read (depth exactly
    one, always: no option)."""

    def __init__(self, graph: LayerGraph, params: dict[str, Any], *,
                 num_stages: int, width: int,
                 max_len: int | None = None, top_k: int | None = None):
        if width < 1:
            raise ValueError(f"width must be >= 1, got {width}")
        parts = decoder_parts(graph, num_stages, max_len)
        nodes = graph.nodes
        for nm in parts.block_names:
            if not isinstance(nodes[nm].op, CausalTransformerBlock):
                # the step adds learned positions and hands the blocks
                # no position: another family would answer wrongly
                raise TypeError(
                    f"{nm} ({nodes[nm].op!r}) is not a "
                    "CausalTransformerBlock: the decode engine serves the "
                    "GPT family only (PipelinedDecoder runs the others)")
        self.graph = graph
        placed: dict = {}
        with span("setup", "place", placed):
            self.params = jax.tree.map(jnp.asarray, params)
            leaves = jax.tree.leaves(self.params)
            placed.update(leaves=len(leaves),
                          bytes=sum(a.nbytes for a in leaves))
        self.width = width
        self.num_stages = num_stages
        self.embed_op: GptEmbedding = parts.embed_op
        self.max_len = parts.max_len
        #: the chain-partition structure: stage s owns these blocks
        self.stage_blocks = parts.stage_blocks
        #: every block in order, as the programs walk them: (op, name)
        self._blocks = [(nodes[nm].op, nm) for nm in parts.block_names]
        self.top_k = top_k
        #: one layer's cache: ``width`` slots, f32 (the step computes in
        #: it); looked up on the module when the engine is built, so a
        #: test can put another format in its place
        if len(set(parts.geometry)) != 1:
            raise ValueError(
                f"the blocks' heads are {sorted(set(parts.geometry))} "
                "(query heads, KV heads, width): the engine's homogeneous "
                "cache needs one head geometry")
        _, kv_heads, head_dim = parts.geometry[0]
        self.kv_format = kv_cache.KVCacheFormat(
            kv_heads, head_dim, self.max_len, jnp.float32)

        self._slots: list[_Slot | None] = [None] * width
        with span("setup", "state"):
            #: every layer's buffers, each a donated argument of the step
            #: and its aliased output (docs/DECODE_CLIFF.md, "The engine")
            self._caches = self.kv_format.zeros(width,
                                                len(parts.block_names))
            #: the ids the last launched step returned, on the device:
            #: what the next step feeds every slot that is past its prompt
            self._prev_ids = jnp.zeros(width, jnp.int32)
        self._step_fns: dict[bool, Any] = {}
        #: the step launched and not yet read, if any
        self._flight: _Flight | None = None
        #: positions a prefill takes (0: a model of one position)
        self.prefill_len = min(PREFILL_POSITIONS, self.max_len - 1)
        self._prefill_fns = self._build_prefill()
        #: what ``_prefill`` calls: each program through
        #: ``setup.first_call`` once, itself from then on
        calls = self._prefill_calls = []
        calls.extend(
            spanned_first_call(fn, functools.partial(calls.__setitem__, i))
            for i, fn in enumerate(self._prefill_fns))
        self.steps = 0              #: steps whose ids the host has read
        self._step_hist = REGISTRY.histogram("serve.decode.step_s")
        self._tok_count = REGISTRY.counter("serve.decode.tokens")
        #: steps launched while an earlier one was still unread: over
        #: ``step_s``'s count, the share of steps the chip never waited
        #: for.  None is ever discarded: a finish is known before the
        #: launch
        self._ahead_count = REGISTRY.counter("serve.decode.ahead.launched")
        #: slots the launched steps' cache kernels visited: over steps
        #: times ``width``, the share of the slot-wise work a step does
        #: (1.0: every slot live, the list saves nothing)
        self._rows_count = REGISTRY.counter("serve.decode.rows.launched")
        #: how often the prefill engages: prompt tokens it took, and
        #: prompt tokens a step was fed (a request's last, a long tail)
        self._prefilled_count = REGISTRY.counter(
            "serve.decode.prompt_tokens_prefilled")
        self._forced_count = REGISTRY.counter(
            "serve.decode.prompt_tokens_forced")
        #: passes launched, and how many since the last step's launch;
        #: the rounds whose flight held one, beside ``step_s``, which
        #: keeps every round
        self._pass_count = REGISTRY.counter("serve.decode.passes")
        self._passes_ahead = 0
        self._pass_round_hist = REGISTRY.histogram(
            "serve.decode.pass_round_s")

    # -- state -------------------------------------------------------------

    def free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def active(self) -> int:
        return self.width - self.free_slots()

    def join(self, req: DecodeRequest) -> bool:
        """Claim a free slot for ``req``; False when the batch is full.
        The request's KV rows start clean by construction: position p's
        cache row is written before any later position reads it, so a
        recycled slot needs no cache zeroing — with a step in flight
        too: it may still carry the slot's last tenant's row (cancelled
        since; a finished one is in no step launched ahead), and the
        newcomer's prefill and first step are queued behind it, where
        they rewrite every row the newcomer will read.  The slot is
        marked for its prompt's prefill, which the next :meth:`step`
        runs before it launches the step that takes the slot in (a slot
        cancelled before then has run nothing); a prompt of one token
        has nothing to prefill."""
        if req.prompt.size + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt {req.prompt.size} + {req.max_new_tokens} new "
                f"tokens exceeds max_len={self.max_len}")
        for i, s in enumerate(self._slots):
            if s is None:
                self._slots[i] = _Slot(
                    req, min(req.prompt.size - 1, self.prefill_len))
                return True
        return False

    def cancel(self, req: DecodeRequest) -> bool:
        """Free ``req``'s slot immediately (client disconnected).  The
        slot is reusable at the next join; other slots' rows are
        untouched (row-independent step), so a mid-decode cancellation
        cannot perturb anyone else's output.  A step in flight still
        carries the slot's row: its token is dropped at delivery.  Where
        this was the last live slot that step is read here and now, so
        that an engine with no request has nothing in flight (a caller
        that parks would otherwise read it a park later, as one long
        ``step_s``)."""
        for i, s in enumerate(self._slots):
            if s is not None and s.req is req:
                s.cancelled = True
                self._slots[i] = None
                if req.on_done is not None:
                    req.on_done(None)
                if not self.active():
                    self.drain()
                return True
        return False

    # -- the step program --------------------------------------------------

    def _build_step(self, sample: bool):
        nodes = self.graph.nodes
        embed = self.embed_op
        blocks = self._blocks
        final_ln = nodes["final_ln"].op
        lm_head = nodes["lm_head"].op
        top_k = self.top_k
        fmt = self.kv_format

        def step(params, caches, prev_ids, host_ids, from_host, pos, seeds,
                 temps, live):
            # the id a slot feeds: the one the step before sampled for
            # it, still on the device — or the host's, where the host
            # owns it (a prompt token, a slot with no request)
            ids = jnp.where(from_host, host_ids, prev_ids)
            safe = jnp.clip(pos, 0, self.max_len - 1)
            x = embed.embed_rows(params["embeddings"], ids,
                                 safe).astype(jnp.float32)
            for l, (op, nm) in enumerate(blocks):
                q, k_new, v_new = op.decode_qkv(params[nm], x, safe)
                # the cache's kernels visit the slots on the list and
                # no other: an idle slot's window is not moved, its keys
                # and values are not read and its attention is zeros
                layer = fmt.write_slots(fmt.layer(caches, l),
                                        fmt.rows(k_new, v_new), safe, live)
                caches = fmt.with_layer(caches, l, layer)
                # every live slot attends over its own positions <= its
                # own
                x = op.decode_finish(params[nm], x,
                                     fmt.attend(q, layer, safe, live=live))
            h = final_ln.apply(params["final_ln"], x)
            logits = lm_head.apply(params["lm_head"],
                                   h).astype(jnp.float32)
            if sample:
                def row_sample(lg, seed_r, pos_r, temp_r):
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(seed_r), pos_r)
                    return sample_ids(lg[None], temp_r, top_k, key)[0]
                sampled = jax.vmap(row_sample)(logits, seeds, safe, temps)
                ids_out = jnp.where(temps > 0, sampled,
                                    jnp.argmax(logits, axis=-1))
            else:
                ids_out = jnp.argmax(logits, axis=-1)
            return ids_out.astype(jnp.int32), caches

        return jax.jit(step, donate_argnums=(1,))

    def _step_fn(self, sample: bool):
        fn = self._step_fns.get(sample)
        if fn is None:
            # the launch that follows is ``setup.first_call``
            fn = spanned_first_call(self._step_fns.setdefault(
                sample, self._build_step(sample)))
        return fn

    # -- the prefill programs ----------------------------------------------

    def _build_prefill(self):
        """``(embed, blocks)``: a prompt's rows ``ids [prefill_len] ->
        x [1, prefill_len, d]``, and a few blocks' prefill ``(ops, their
        params, x, their layers of the caches, slot) -> (x, the
        layers)``, the layers donated and aliased like the step's
        caches.  In float32 like the step, whose rows these stand in
        for; no head: the last prompt token goes through the step.

        A program :data:`PREFILL_LAYERS` blocks long, called once a
        group of them, not one program over every block: alike blocks
        share it, so the door's set-up traces, lowers and loads a sixth
        of gpt2-xl's 48 layers (all of them in one program cost what the
        step program does, 4.7 s of a 26 s set-up; a program a block
        0.17 ms a call, 8 ms a prompt: PERF.md section 6, PR 39)."""
        embed, fmt = self.embed_op, self.kv_format

        def engine_prefill_embed(params, ids):
            # rows cut out one by one, as the step's: a gather would
            # first copy the whole token table out of its layout
            return embed.embed_rows(
                params, ids,
                np.arange(ids.shape[0]))[None].astype(jnp.float32)

        def engine_prefill(ops, params, x, layers, slot):
            out = []
            for op, p, layer in zip(ops, params, layers):
                x, layer = op.prefill(p, x, layer, fmt, slot)
                out.append(layer)
            return x, out

        return jax.jit(engine_prefill_embed), \
            jax.jit(engine_prefill, static_argnums=(0,), donate_argnums=(3,))

    def _prefill(self, i: int, s: _Slot) -> None:
        """Slot ``i``'s prompt positions ``0 .. s.prefill - 1`` through
        the prefill programs, launched and NOT waited for: the donated
        cache buffers order the pass behind the step in flight and in
        front of the step launched next.  The span closes when the
        launches have returned; the pass's device time is the
        ``jit_engine_prefill`` runs of a profiler trace."""
        n, s.prefill = s.prefill, 0
        fmt = self.kv_format
        embed, blocks_prefill = self._prefill_calls
        with span("engine", "prefill", {"step": self.steps, "slot": i,
                                        "positions": n}) as launched:
            ids = np.zeros(self.prefill_len, np.int32)
            ids[:n] = s.req.prompt[:n]
            slot = jnp.int32(i)
            x = embed(self.params["embeddings"], jnp.asarray(ids))
            for l0 in range(0, len(self._blocks), PREFILL_LAYERS):
                ops, names = zip(*self._blocks[l0:l0 + PREFILL_LAYERS])
                x, layers = blocks_prefill(
                    ops, [self.params[nm] for nm in names], x,
                    [fmt.layer(self._caches, l0 + j)
                     for j in range(len(ops))], slot)
                for l, layer in enumerate(layers, l0):
                    self._caches = fmt.with_layer(self._caches, l, layer)
        s.way.prefill_at = launched.t0
        self._prefilled_count.n += n
        self._pass_count.n += 1
        self._passes_ahead += 1

    # -- one decode step ---------------------------------------------------

    def step(self) -> list[tuple[DecodeRequest, np.ndarray]]:
        """Launch the next step, then read the one launched by the call
        before; returns the requests that read FINISHED as ``(request,
        [plen + new] ids)`` (their slots are already free).

        In order: a slot that joined since the last call has its prompt
        prefilled (``engine.prefill``, a phase of its own in front of
        the step's; launched, not waited for); the next step is gathered
        and launched for every slot that has a step left — which is
        known without the tokens of the step in flight — and only then
        the step in flight is waited for and its ids delivered: the
        device runs the step just launched meanwhile.  ``on_done`` fires
        from the delivery of a request's last token.  With nothing in
        flight (the first call after a join into an idle engine) the
        launch is all, and the list is empty; with nothing to launch
        (every live slot's last step is the one in flight) the read is
        all.  No-op (empty list) with no active slot."""
        flight = self._flight
        live = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        for i, s in live:
            if s.prefill:       # joined since the last call
                self._prefill(i, s)
        rows = [(i, s) for i, s in live if s.steps_left()]
        if not rows:
            return self.drain()
        # one root a step, around the call that launches it
        with span("engine", "step", {"step": self.steps + (flight is not None),
                                     "rows": len(rows)}):
            self._launch(rows)
            return self._deliver(flight) if flight is not None else []

    def drain(self) -> list[tuple[DecodeRequest, np.ndarray]]:
        """Read the step in flight, if any, and launch nothing: what a
        busy period's last call comes to, a cancellation that empties
        the engine, a loop that stops."""
        flight, self._flight = self._flight, None
        return self._deliver(flight) if flight is not None else []

    def _blank_rows(self, live=(0,)) -> tuple:
        """A step's host rows ``(host_ids, from_host, pos, seeds,
        temps, live)`` with no request in any slot: id 0 at position 0,
        the host's.  The sixth row is the list of the slots ``live``
        (ascending) as the cache's kernels take it
        (``kv_cache.live_slots``): the slots they visit — never none, a
        step is launched for a live slot or not at all."""
        w = self.width
        return (np.zeros(w, np.int32), np.ones(w, np.bool_),
                np.zeros(w, np.int32), np.zeros(w, np.uint32),
                np.zeros(w, np.float32), kv_cache.live_slots(live, w))

    def _launch(self, rows) -> None:
        """The first two phases of ``obs/profile.py::ENGINE_PHASES`` for
        the step that takes ``rows``: gather (host build of the per-slot
        rows / teacher-forcing) and dispatch (``upload`` of those rows,
        then ``launch``: the jit step call returning;
        ``ENGINE_DISPATCH_PHASES``).  The step becomes the one in
        flight."""
        with span("engine", "gather"):
            # who is live is known before the step in flight is read
            host_ids, from_host, pos, seeds, temps, live = self._blank_rows(
                [i for i, _ in rows])
            sample = False
            fed = []
            passless = []   # slots this step takes in without a pass
            for i, s in rows:
                if s.pos < s.req.prompt.size:
                    host_ids[i] = s.req.prompt[s.pos]
                    self._forced_count.n += 1
                    if s.way.prefill_at is None:
                        passless.append(s.way)
                else:
                    # the step before this one sampled it: every launch
                    # since the slot's first has held the slot
                    from_host[i] = False
                pos[i] = s.pos
                seeds[i] = s.req.seed & 0xFFFFFFFF
                temps[i] = s.req.temperature
                sample = sample or s.req.temperature > 0
                fed.append((i, s, s.pos))
                s.pos += 1
        self._rows_count.n += len(rows)
        if self._flight is not None:    # launched ahead of its read
            self._ahead_count.n += 1
        with span("engine", "dispatch") as dispatched:
            with span("engine", "upload"):
                up = jax.device_put((host_ids, from_host, pos, seeds, temps,
                                     live))
            with span("engine", "launch") as launched:
                self._prev_ids, self._caches = self._step_fn(sample)(
                    self.params, self._caches, self._prev_ids, *up)
            del up      # while the device runs the step, not at the next
        for way in passless:
            way.prefill_at = launched.t0
        self._flight = _Flight(self._prev_ids, fed, dispatched.t0,
                               self._passes_ahead)
        self._passes_ahead = 0

    def _deliver(self, flight: _Flight
                 ) -> list[tuple[DecodeRequest, np.ndarray]]:
        """The last three phases for the step ``flight``: device
        (block_until_ready — the fused step program: blocks, lm_head,
        sampling AND the KV write all live here; splitting those needs
        jax.profiler), sync (np.asarray of the sampled ids; ``ahead``
        says whether a later step was running under the wait), delivery
        (per-slot bookkeeping + on_done).  ``step_s`` records the round:
        from the step before's ids reaching the host to this step's —
        what a token costs a live slot — and launch to ids for a step
        launched onto an empty queue; ``pass_round_s`` the rounds whose
        flight had a prompt's pass in front of it.  The instant the ids
        reached the host (``sync``'s end) is every live row's stamp for
        this step (:class:`Waypoints`): no clock is read a row."""
        later = self._flight    # the step launched since, if any
        step, passes = self.steps, flight.passes
        # the wait behind a pass is kept apart from the plain waits by
        # the pause watch: a join is no pause of the host
        with span("engine", "device", {"passes": passes, "step": step}):
            flight.ids.block_until_ready()
        with span("engine", "sync", {"ahead": int(later is not None)}) \
                as synced:
            next_ids = np.asarray(flight.ids)
        at = synced.t1
        gap = at - flight.since
        self._step_hist.record(gap)
        if passes:
            self._pass_round_hist.record(gap)
        if later is not None:
            later.since = at
        self.steps += 1
        done: list[tuple[DecodeRequest, np.ndarray]] = []
        with span("engine", "delivery"):
            for i, s, fed in flight.rows:
                if s.cancelled:     # left while the step ran
                    continue
                way = s.way
                # the step consumed position ``fed``; the token it
                # produced sits behind it, generated iff past the prompt
                if fed + 1 >= s.req.prompt.size:
                    s.out.append(int(next_ids[i]))
                    self._tok_count.n += 1
                    if way.rounds:      # a gap between two of its tokens
                        if passes:
                            way.pass_rounds += 1
                        if gap > way.worst_gap:
                            way.worst_gap = gap
                    else:
                        way.first_at, way.first_step = at, step
                    way.rounds += 1
                else:
                    way.forced_steps += 1
                if len(s.out) >= s.req.max_new_tokens:
                    way.last_at, way.last_step = at, step
                    s.req.waypoints = way
                    result = np.concatenate(
                        [s.req.prompt.astype(np.int64),
                         np.asarray(s.out, np.int64)])
                    self._slots[i] = None
                    done.append((s.req, result))
                    if s.req.on_done is not None:
                        s.req.on_done(result)
        return done

    # -- convenience (tests, sequential baselines) -------------------------

    def run_all(self, requests, *, joiner=None, max_steps: int = 100_000
                ) -> dict[int, np.ndarray]:
        """Drive the engine until every request finished: join waiting
        requests whenever slots free up (continuous batching), step
        until drained.  ``joiner(engine, pending)`` can override join
        order/timing (tests use it to stagger joins).  Returns
        ``{request_id: ids}``."""
        pending = list(requests)
        results: dict[int, np.ndarray] = {}

        def default_joiner(eng, queue):
            while queue and eng.free_slots():
                if not eng.join(queue[0]):
                    break
                queue.pop(0)

        join = joiner or default_joiner
        for _ in range(max_steps):
            join(self, pending)
            if not pending and self.active() == 0:
                return results
            for req, ids in self.step():
                results[req.request_id] = ids
        raise RuntimeError(f"run_all did not drain in {max_steps} steps")


class EngineLoop(threading.Thread):
    """The front door's decode scheduling thread: joins admitted
    requests from a :class:`~defer_tpu.serve.batcher.BatchFormer` into
    free slots between steps, steps while anything is active, parks on
    the queue otherwise."""

    def __init__(self, engine: ContinuousBatchEngine, former,
                 on_service=None):
        super().__init__(daemon=True, name="serve-decode-loop")
        self.engine = engine
        self.former = former
        self._halt = threading.Event()
        self.error: BaseException | None = None
        #: called with (per-unit seconds, units) after each call of
        #: ``step()`` that read a step — feeds the admission controller's
        #: live service EWMA.  The call's time is a round (it launched
        #: the next step first, then waited for the one it read); a
        #: busy period's first call only launches and is not a sample
        self._on_service = on_service
        #: cancellations queued from OTHER threads (client reader saw a
        #: disconnect); applied between steps on THIS thread — the slot
        #: table has exactly one mutating thread
        self._cancel_q: list = []
        self._cancel_lock = threading.Lock()

    def stop(self) -> None:
        self._halt.set()

    def request_cancel(self, req) -> None:
        """Thread-safe: free ``req``'s slot at the next step boundary."""
        with self._cancel_lock:
            self._cancel_q.append(req)

    def _apply_cancels(self) -> None:
        with self._cancel_lock:
            cancels, self._cancel_q = self._cancel_q, []
        for req in cancels:
            if self.engine.cancel(req):
                emit_event("decode_cancel", rid=req.request_id,
                           tenant=req.tenant)

    def _join(self, item) -> None:
        """Move one popped admission-queue item into a free slot."""
        # this loop pops the admission queue directly (no
        # BatchFormer.form), so the attribution boundary is stamped here
        _stamp_popped(item)
        req = item[1]
        if getattr(req, "cancelled", False):
            return  # client left while it queued
        if self.engine.join(req):
            emit_event("decode_join", rid=req.request_id,
                       tenant=req.tenant, step=self.engine.steps)

    def run(self) -> None:
        eng = self.engine
        queue = self.former.queue
        try:
            while not self._halt.is_set():
                item = None
                if eng.active() == 0:
                    # park on the queue only when idle; with work in
                    # flight just sweep whatever is already waiting
                    with span("engine", "park"):
                        item = queue.pop(timeout=0.05)
                with span("engine", "join"):
                    self._apply_cancels()
                    for _ in range(eng.free_slots()):
                        if item is None:
                            item = queue.pop(timeout=0.0)
                        if item is None:
                            break
                        self._join(item)
                        item = None
                if eng.active() == 0:
                    continue
                t0 = time.perf_counter()
                n, read = eng.active(), eng.steps
                eng.step()
                if self._on_service is not None and eng.steps > read:
                    self._on_service((time.perf_counter() - t0) / n, n)
            # a step launched ahead is read, not left: a request whose
            # last token it holds still gets its answer
            eng.drain()
        except BaseException as e:  # noqa: BLE001 — surfaced by the door
            self.error = e
