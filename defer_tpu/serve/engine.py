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
  prompt longer than one prefill takes), sampled past it.  A
  layer is the block's own halves, as the ring calls them:
  ``decode_qkv`` on the whole ``[width, d]`` batch, each slot's new row
  written in place at that slot's OWN position (the format's
  ``write_slots``: slots sit at different positions, and a vmapped
  write would be a batched scatter over a re-laid-out item,
  docs/DECODE_CLIFF.md), attention with each slot's own live mask, then
  ``decode_finish``.  Every row's
  computation reads its own rows only, so a row's output bytes are
  INDEPENDENT of who shares the batch — per-request outputs are
  byte-identical to the request run alone, the correctness bar
  continuous batching must meet.
* Sampling keys are ``fold_in(request_seed, position)`` per row —
  deterministic per request regardless of batch composition or join
  step.

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
import threading
import time
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp

from ..graph.ir import LayerGraph
from ..models.decoder import decoder_parts
from ..models.gpt import CausalTransformerBlock, GptEmbedding
from ..obs import REGISTRY, span
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

    def __post_init__(self):
        self.prompt = np.asarray(self.prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")


class _Slot:
    __slots__ = ("req", "pos", "prefill", "out", "last_id", "cancelled")

    def __init__(self, req: DecodeRequest, prefill: int = 0):
        self.req = req
        self.pos = prefill         #: next position to feed to a step
        #: prompt positions ``0 .. prefill - 1`` still to go through the
        #: prefill, which the next ``step()`` runs first
        self.prefill = prefill
        self.out: list[int] = []   #: generated ids
        self.last_id = 0           #: last sampled id (input past prompt)
        self.cancelled = False


class ContinuousBatchEngine:
    """Step-wise decoder over ``width`` request slots.

    The engine is PASSIVE: callers (the front door's decode loop, or a
    test) drive it with :meth:`join` / :meth:`cancel` between calls to
    :meth:`step`.  All three must be called from one scheduling thread
    (the slot table is not locked against concurrent mutation; the
    front door owns that thread)."""

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
        self.params = jax.tree.map(jnp.asarray, params)
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
        #: every layer's buffers, each a donated argument of the step and
        #: its aliased output (docs/DECODE_CLIFF.md, "The engine")
        self._caches = self.kv_format.zeros(width, len(parts.block_names))
        self._step_fns: dict[bool, Any] = {}
        #: positions a prefill takes (0: a model of one position)
        self.prefill_len = min(PREFILL_POSITIONS, self.max_len - 1)
        self._prefill_fns = self._build_prefill()
        self.steps = 0
        self._step_hist = REGISTRY.histogram("serve.decode.step_s")
        self._tok_count = REGISTRY.counter("serve.decode.tokens")
        #: how often the prefill engages: prompt tokens it took, and
        #: prompt tokens a step was fed (a request's last, a long tail)
        self._prefilled_count = REGISTRY.counter(
            "serve.decode.prompt_tokens_prefilled")
        self._forced_count = REGISTRY.counter(
            "serve.decode.prompt_tokens_forced")

    # -- state -------------------------------------------------------------

    def free_slots(self) -> int:
        return sum(1 for s in self._slots if s is None)

    def active(self) -> int:
        return self.width - self.free_slots()

    def join(self, req: DecodeRequest) -> bool:
        """Claim a free slot for ``req``; False when the batch is full.
        The request's KV rows start clean by construction: position p's
        cache row is written before any later position reads it, so a
        recycled slot needs no cache zeroing.  The slot is marked for
        its prompt's prefill, which the next :meth:`step` runs before
        the step itself (a slot cancelled before then has run nothing);
        a prompt of one token has nothing to prefill."""
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
        cannot perturb anyone else's output."""
        for i, s in enumerate(self._slots):
            if s is not None and s.req is req:
                s.cancelled = True
                self._slots[i] = None
                if req.on_done is not None:
                    req.on_done(None)
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

        def step(params, caches, ids, pos, seeds, temps):
            safe = jnp.clip(pos, 0, self.max_len - 1)
            x = embed.embed_rows(params["embeddings"], ids,
                                 safe).astype(jnp.float32)
            for l, (op, nm) in enumerate(blocks):
                q, k_new, v_new = op.decode_qkv(params[nm], x, safe)
                layer = fmt.write_slots(fmt.layer(caches, l),
                                        fmt.rows(k_new, v_new), safe)
                caches = fmt.with_layer(caches, l, layer)
                # every slot attends over its own positions <= its own
                x = op.decode_finish(params[nm], x,
                                     fmt.attend(q, layer, safe))
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
            fn = self._step_fns[sample] = self._build_step(sample)
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
        the prefill programs, waited for: the step behind them then
        starts on an empty queue, and ``step_s`` stays a step's own
        time."""
        n, s.prefill = s.prefill, 0
        fmt = self.kv_format
        embed, blocks_prefill = self._prefill_fns
        with span("engine", "prefill", {"step": self.steps, "slot": i,
                                        "positions": n}):
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
            jax.block_until_ready(x)
        self._prefilled_count.n += n

    # -- one decode step ---------------------------------------------------

    def step(self) -> list[tuple[DecodeRequest, np.ndarray]]:
        """Advance every active slot one token; returns requests that
        FINISHED this step as ``(request, [plen + new] ids)`` (their
        slots are already free).  A slot that joined since the last
        step first has its prompt prefilled (``engine.prefill``, a phase
        of its own in front of the step's).  No-op (empty list) with no
        active slots."""
        live = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        if not live:
            return []
        for i, s in live:
            if s.prefill:       # joined since the last step
                self._prefill(i, s)
        with span("engine", "step", {"step": self.steps,
                                     "rows": len(live)}):
            return self._step(live)

    def _step(self, live) -> list[tuple[DecodeRequest, np.ndarray]]:
        """One step in the phases of ``obs/profile.py::ENGINE_PHASES``:
        gather (host build of the per-slot rows / teacher-forcing),
        dispatch (``upload`` of those rows, then ``launch``: the jit step
        call returning; ``ENGINE_DISPATCH_PHASES``), device
        (block_until_ready — the fused step program: blocks, lm_head,
        sampling AND the KV write all live here; splitting those needs
        jax.profiler), sync (np.asarray of the sampled ids), delivery
        (per-slot bookkeeping + on_done).  ``step_s`` stays the
        dispatch→materialize total the serve stats already report."""
        with span("engine", "gather"):
            w = self.width
            ids = np.zeros(w, np.int32)
            pos = np.zeros(w, np.int32)
            seeds = np.zeros(w, np.uint32)
            temps = np.zeros(w, np.float32)
            sample = False
            for i, s in live:
                plen = s.req.prompt.size
                if s.pos < plen:
                    ids[i] = s.req.prompt[s.pos]
                    self._forced_count.n += 1
                else:
                    ids[i] = s.last_id
                pos[i] = s.pos
                seeds[i] = s.req.seed & 0xFFFFFFFF
                temps[i] = s.req.temperature
                sample = sample or s.req.temperature > 0
        with span("engine", "dispatch") as dispatched:
            with span("engine", "upload"):
                rows = (jnp.asarray(ids), jnp.asarray(pos),
                        jnp.asarray(seeds), jnp.asarray(temps))
            with span("engine", "launch"):
                next_ids, self._caches = self._step_fn(sample)(
                    self.params, self._caches, *rows)
            del rows    # while the device runs the step, not at the next
        with span("engine", "device"):
            sync = getattr(next_ids, "block_until_ready", None)
            if sync is not None:
                sync()
        with span("engine", "sync") as synced:
            next_ids = np.asarray(next_ids)
        self._step_hist.record(synced.t1 - dispatched.t0)
        self.steps += 1
        done: list[tuple[DecodeRequest, np.ndarray]] = []
        with span("engine", "delivery"):
            for i, s in live:
                plen = s.req.prompt.size
                tok = int(next_ids[i])
                # the step consumed position s.pos; the token it produced
                # sits at position s.pos + 1, generated iff past the prompt
                if s.pos + 1 >= plen:
                    s.out.append(tok)
                    s.last_id = tok
                    self._tok_count.n += 1
                s.pos += 1
                if len(s.out) >= s.req.max_new_tokens:
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
        #: called with (per-unit seconds, units) after each step — feeds
        #: the admission controller's live service EWMA.  A joined
        #: slot's prefill runs inside that ``step()`` and is timed with
        #: it: over a request's life its steps carry one prefill each
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
                n = eng.active()
                eng.step()
                if self._on_service is not None and n > 0:
                    self._on_service((time.perf_counter() - t0) / n, n)
        except BaseException as e:  # noqa: BLE001 — surfaced by the door
            self.error = e
