"""Pipelined autoregressive decoding with per-stage sequence memory.

The inference engine (:mod:`defer_tpu.runtime.spmd`) streams independent
inputs through the stage ring; generation is harder — token t+1 of a
sequence cannot enter stage 0 until token t has left the last stage.  A
single sequence would therefore keep only one of N stages busy.  The classic
fix, implemented here: interleave N independent *groups* of sequences
round-robin, so at every step stage k serves group ``(t - k) mod N`` — the
ring is full and every device computes every step, DEFER's "all stages busy
on different in-flight inputs" (SURVEY.md §0) transposed to token time.

TPU-native design, one SPMD program:

  * Weights: each device holds only its stage's parameters, stored in
    the compute dtype (int8 beside its scales under
    ``weight_dtype="int8"``), every leaf a stage-sharded argument of its
    own, in its own shape: local block ``l``'s leaves stacked over the
    stages, the leaves of the nodes outside the blocks by name.
  * The cut: a ring step lasts as long as its costliest stage, so the
    stages are cut where the costliest reads the fewest bytes a step —
    its blocks' leaves and the ends it holds, the head on the last
    (``models/decoder.py::balanced_cut``; ``split_blocks``' even cut
    wherever that is as good); ``cut=`` hands another in.
  * Sequence memory: per device, one resident buffer a local block and
    key, held and touched only through the format *that block* names
    (``DecoderBlock.memory_format``: kind, geometry and length are the
    layer's — a layer whose attention has a window keeps a ring buffer
    of the window's rows beside a layer that keeps every position, a
    state-space layer its window and state beside a layer that keeps a
    KV cache; a stage's layers must name the same formats in the same
    order on every stage, since a stage-sharded buffer has one shape).
    The state is a dict of tuples, an entry a local layer under each
    key, None where the layer's format has no such key
    (``ops/layered.py``).  A KV cache (``ops/kv_cache.py``,
    which describes the layout): a step writes one row a block in
    place, every sequence of the group at one position, and attends
    over the group's live rows where they lie; warmup bubbles write the
    format's scratch row and prefill bubbles its scratch group, so no
    masked read-modify-write of the cache is ever needed.  A latent
    cache (``ops/latent_cache.py``): one row a position that every head
    shares, written and attended over the same way.  A retention
    state (``ops/retention.py``) or a state-space state
    (``ops/ssm.py``): of fixed size, read and rewritten whole each
    step; a bubble is its identity update, so it has neither scratch.
  * The ring carry is one ``[mb, d]`` float32 buffer per device: stage
    activations in flight, and — on the wrap link from the last stage back
    to stage 0 (the reference's node->dispatcher link,
    src/dispatcher.py:51-55) — the greedily sampled token ids encoded in
    column 0 (f32 is exact for ids < 2^24).
  * ``lax.scan`` over decode steps fuses the token loop into chunked XLA
    dispatches (``token_chunk`` tokens per group per dispatch, whole
    generation in ONE dispatch by default); prompt teacher-forcing happens
    inside the scan (stage 0 substitutes the known prompt token while
    ``pos < prompt_len``), and the ring carry + caches flow between
    dispatches as donated device-resident shards — a chunk needs nothing
    of the host but where it starts, so the loop launches chunk n+1
    before it waits for chunk n's ids (streaming, the EOS check) and the
    device always has a program queued.
  * Sampling: greedy argmax, or temperature softmax sampling with optional
    top-k, keyed by ``fold_in(seed, step)`` so results are independent of
    the chunking.

Scope: stage-axis-only mesh and the decoder-model contract of
``models/decoder.py`` (``embeddings`` / ``block_i`` / ``final_ln`` /
``lm_head``; blocks with ``memory_format`` / ``decode`` / ``prefill``;
an embedding with ``embed_at``): the ring asks a block for nothing
else, whatever its family and whatever memory it keeps.  Prompts
are processed either at decode rate (teacher forcing inside the scan,
the default) or by the fused full-sequence
pipelined prefill (``generate(..., prefill=True)``): each group's whole
prompt crosses each stage in one causal-attention step and bulk-seeds the
caches, dropping prompt cost from ``plen * N`` ring steps to ``2N - 1``.
"""

from __future__ import annotations

import collections
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.layout import Format, Layout
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.ir import LayerGraph
from ..models.decoder import decoder_parts
from ..obs import REGISTRY, span, spanned_first_call
from ..ops import quant
from ..ops.layered import shapes_by_layer, totals, zeros_by_layer
from ..parallel.mesh import STAGE_AXIS, pipeline_mesh
from ..utils.xla_opts import ring_jit_kwargs


#: the most a piece of a group's prefill may hold in its widest
#: activation (a piece's rows x prompt x the widest any block names,
#: ``DecoderBlock.widest``, in the compute type): a group whose whole prompt
#: passes it crosses a stage a few sequences at a time
_PREFILL_PIECE_BYTES = 1 << 28


def sample_ids(logits, temp, top_k, step_key):
    """Temperature softmax sampling with optional top-k truncation.

    The single definition shared by the decode and prefill branches — both
    must draw from the identical distribution."""
    lg = logits / jnp.maximum(temp, 1e-6)
    if top_k is not None:
        kth = lax.top_k(lg, top_k)[0][:, -1:]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)
    return jax.random.categorical(step_key, lg, axis=-1)


def _leaf_layout(tree) -> dict:
    """``{path: (shape, dtype)}`` of a parameter tree's leaves as they
    were handed over, before any cast."""
    return {jax.tree_util.keystr(path): (np.shape(leaf), np.dtype(
        leaf.dtype if hasattr(leaf, "dtype") else np.asarray(leaf).dtype))
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def off_default_layout(leaf: jax.Array) -> bool:
    """Whether a placed array lies otherwise than its device lays out a
    shard of its shape and type when nobody says how (a backend that
    reports no layouts has only that one)."""
    held = leaf.format.layout
    if held is None:
        return False
    dev = next(iter(leaf.devices()))
    default = Layout.from_pjrt_layout(dev.client.get_default_layout(
        leaf.dtype, leaf.sharding.shard_shape(leaf.shape), dev))
    return held != default


def _as_is(x):
    return x


def relaid(leaf: jax.Array, want: Format) -> jax.Array:
    """``leaf`` (given up) laid out anew on its devices as ``want`` says.

    What ``jax.device_put(leaf, want, donate=True)`` does, but for the
    persistent compilation cache: a program read back from it hands out
    its results tagged with the device's *default* layout whatever it
    was compiled to produce (jax 0.9.0 on the v5e and on the CPU: the
    buffer lies as asked, ``.format`` says otherwise, and every program
    compiled for that array afterwards is compiled for a layout it does
    not have).  So this one program is compiled in every process
    (0.1-0.6 s a shape on the chip) and never written; programs that
    only *take* arrays in a named layout come back from the cache whole.
    """
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", math.inf)
    try:
        with span("setup", "relay"):
            out = jax.jit(_as_is, out_shardings=want,
                          donate_argnums=0)(leaf)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    if out.format.layout.major_to_minor != want.layout.major_to_minor:
        raise RuntimeError(
            f"a leaf {out.shape} asked for as {want.layout} came back as "
            f"{out.format.layout}: the program that lays it out anew must "
            "not come from the persistent compilation cache")
    return out


class PipelinedDecoder:
    """Greedy autoregressive generation over a ``stage``-axis mesh.

    Usage::

        graph = gpt_tiny()
        dec = PipelinedDecoder(graph, graph.init(key), num_stages=4,
                               microbatch=2, max_len=32)
        tokens = dec.generate(prompt_ids, max_new_tokens=16)

    ``prompt_ids`` is [B, prompt_len] with B <= num_stages * microbatch;
    returns [B, prompt_len + max_new_tokens].

    Which blocks a stage holds (``stage_blocks``) is chosen when the
    decoder is built, from the graph and ``params`` alone: a stage costs
    the bytes it reads in one ring step — its blocks' leaves as they are
    placed, ``final_ln`` and ``lm_head`` on the last stage, the
    embedding's gathered rows on the first — and the cut is the
    contiguous one whose costliest stage costs least among those the
    ring can run (every stage repeating the longest stage's kinds of
    memory in order); the even cut wherever it is among the best
    (``models/decoder.py::decoder_parts``).  ``cut`` (blocks a stage,
    ``[8, 8, 8, 4]``) hands one in instead, checked by the same rule:
    for a planner that timed the stages or a deployment that knows what
    its routed layers read, not a knob of the default.  With one stage
    there is nothing to choose.  The gauges ``decode.cut.blocks.<s>``,
    ``decode.cut.stage_bytes_max`` and ``.stage_bytes_mean`` say what
    was chosen.
    """

    def __init__(
        self,
        graph: LayerGraph,
        params: dict[str, Any],
        *,
        num_stages: int,
        max_len: int | None = None,
        mesh: Mesh | None = None,
        microbatch: int = 1,
        compute_dtype=None,
        kv_cache: str = "buffer",
        weight_dtype: str | None = None,
        beam_width: int = 1,
        cut=None,
    ):
        self.graph = graph
        self.num_stages = n = num_stages
        self.mesh = mesh if mesh is not None else pipeline_mesh(n)
        if self.mesh.shape[STAGE_AXIS] != n:
            raise ValueError(
                f"mesh stage axis {self.mesh.shape[STAGE_AXIS]} != {n}")
        self.microbatch = mb = microbatch
        self.compute_dtype = jnp.dtype(compute_dtype) if compute_dtype \
            else jnp.dtype(jnp.float32)
        if kv_cache not in ("buffer", "int8"):
            raise ValueError(
                f"kv_cache must be 'buffer' or 'int8', got {kv_cache!r}")
        self.kv_cache = kv_cache
        if weight_dtype not in (None, "int8"):
            raise ValueError(
                f"weight_dtype must be None or 'int8', got {weight_dtype!r}")
        #: W8A16: weights live int8 in HBM with channel-wise (last-axis)
        #: f32 scales (``ops/quant.py``), dequantized inside each stage
        #: branch.  Decode is HBM-bandwidth-bound (every step streams all
        #: weights), so int8 halves the dominant traffic vs bf16.
        self.weight_quant = weight_dtype == "int8"
        if beam_width < 1 or mb % beam_width:
            raise ValueError(
                f"beam_width={beam_width} must be >= 1 and divide "
                f"microbatch={mb} (each group's rows hold "
                "microbatch/beam_width sequences x beam_width beams)")
        self.beam_width = beam_width

        # weights live in the compute dtype (the runtime/spmd.py recipe):
        # bf16 deployments read 2 bytes/param from HBM per decode step with
        # no per-step downcast materialization
        self._wdt = np.dtype(jnp.bfloat16) \
            if self.compute_dtype == jnp.bfloat16 else np.dtype(np.float32)
        # what the deployment holds of each node, and what one ring step
        # reads of it: its leaves whole, but of the embedding's tables
        # the rows a group gathers — what a stage costs, by which the
        # stages are cut (with one stage nothing is; a graph that lacks
        # a node is ``decoder_parts``' to refuse)
        held = {nm: self._node_bytes(tree) for nm, tree in params.items()}
        reads = dict(held, embeddings=self._node_bytes(
            params.get("embeddings"), rows=mb))
        parts = decoder_parts(graph, n, max_len, cut=cut, step_bytes=reads)
        self.embed_op = parts.embed_op
        self.max_len = max_len = parts.max_len
        self.block_names = list(parts.block_names)
        self.d_model = parts.d_model
        self.vocab = parts.vocab
        #: per-step scalars the blocks sow (``DecoderBlock.decode_stats``);
        #: summed on the device over a chunk, fetched with the chunk's ids
        self._stat_names = parts.decode_stats
        self.stage_blocks = parts.stage_blocks
        self.l_max = max(len(b) for b in self.stage_blocks)
        nodes = graph.nodes
        #: each local block's memory (n groups of mb sequences), in the
        #: format that block names: one a local layer, the longest
        #: stage's, which ``decoder_parts`` saw every other stage repeat
        #: in order — a buffer is sharded over the stages, so local
        #: layer ``l`` has one shape on all of them
        self.state_formats = tuple(
            nodes[nm].op.memory_format(
                self.d_model, max_len, self.compute_dtype,
                quantized=kv_cache == "int8", groups=n)
            for nm in max(self.stage_blocks, key=len))

        #: the nodes outside the blocks, with the one stage that holds
        #: each
        self._ends = {"embeddings": 0, "final_ln": n - 1, "lm_head": n - 1}
        self._w = self._place_weights(params, init=True)
        # what the deployment holds: leaves as placed, before the
        # device's tiling pads them, a shorter stage's zeros and other
        # stages' ends not counted
        REGISTRY.gauge("decode.weights.own_bytes").set(
            sum(held[nm] for nm in self._layout))
        # the cut: blocks a stage, and what a stage reads in one ring
        # step (its blocks and the ends it holds) — the largest against
        # the mean says how far the longest stage holds the others back
        stage_bytes = []
        for s, names in enumerate(self.stage_blocks):
            REGISTRY.gauge(f"decode.cut.blocks.{s}").set(len(names))
            ends = [nm for nm, at in self._ends.items() if at == s]
            stage_bytes.append(sum(reads[nm] for nm in (*names, *ends)))
        REGISTRY.gauge("decode.cut.stage_bytes_max").set(max(stage_bytes))
        REGISTRY.gauge("decode.cut.stage_bytes_mean").set(
            sum(stage_bytes) / n)
        # the arrays (one a leaf, stage-sharded) that the device would
        # have laid out otherwise than row-major, and their bytes over
        # the stages: what every dispatch converted while they lay in
        # the device's default
        moved = [a for a in jax.tree.leaves(self._w)
                 if off_default_layout(a)]
        REGISTRY.gauge("decode.weights.relaid_leaves").set(len(moved))
        REGISTRY.gauge("decode.weights.relaid_bytes").set(
            sum(a.nbytes for a in moved))
        #: shard_map spec for the weight argument
        self._wspec_tree = jax.tree.map(
            lambda f: f.sharding.spec, self.weight_formats())

        #: the first local layer's (every layer's, where they are alike)
        self.state_format = self.state_formats[0]
        #: the kind of memory each local layer keeps
        #: (``DecoderBlock.memory``; None where it keeps none: such a
        #: layer has no buffer, no gauge, no bubble and no parent here)
        self.memory = tuple(nodes[nm].op.memory
                            for nm in max(self.stage_blocks, key=len))
        kinds = dict.fromkeys(k for k in self.memory if k is not None)
        REGISTRY.gauge("decode.memoryless_layers").set(sum(
            nodes[nm].op.memory is None for nm in self.block_names))
        states = [(kind, fmt) for kind, fmt in zip(self.memory,
                                                    self.state_formats)
                  if kind not in ("kv_cache", None)]
        #: the format beams re-parent rows through: the first KV layer's
        #: (it walks every layer's rows and passes by a layer without)
        self._rows_format = next(
            (fmt for kind, fmt in zip(self.memory, self.state_formats)
             if kind == "kv_cache"), None)
        if beam_width > 1 and states:
            kind, fmt = states[0]
            raise ValueError(
                f"beam_width={beam_width}: beam search re-parents a "
                f"sequence's memory at every expansion, and these blocks "
                f"keep a {kind} ({type(fmt).__name__}), which cannot "
                "hand one sequence's memory to another")

        # what the ring holds over its n stages (scratch row and group
        # included): a gauge a kind of memory, and under names of their
        # own what the layers' formats say of their kind's parts
        for kind in kinds:
            REGISTRY.gauge(f"decode.{kind}.state_bytes").set(sum(
                n * fmt.state_bytes(mb, 1)
                for k, fmt in zip(self.memory, self.state_formats)
                if k == kind))
        for name, value in totals(self.state_formats,
                                  lambda fmt: fmt.gauges(mb, n)).items():
            REGISTRY.gauge(name).set(value)
        # every block's format, over all stages (a shorter stage keeps
        # none at its missing places): those whose layers' reads a step
        # posts (:meth:`_post_rows_read`) — all, where they count them
        # under several names; where the rows a step reads are one
        # kind's, no gauge tells kinds apart
        blocks = tuple(self.state_formats[l] for names in self.stage_blocks
                       for l in range(len(names)))
        self._row_readers = blocks if len(totals(
            blocks, lambda fmt: fmt.rows_read(0, 0))) > 1 else ()
        #: the newest generation's state as it left it (device buffers;
        #: dropped when the next generation begins)
        self.state = None
        #: ring-buffer width: beam mode adds one column carrying each
        #: row's parent-beam index around the ring alongside the token id
        self._ring_width = self.d_model + (1 if beam_width > 1 else 0)
        #: compiled decode programs keyed by (chunk_steps, sample, top_k) —
        #: repeat ``generate`` calls of a matching shape are dispatch-only
        self._decode_fns: dict[tuple, Any] = {}
        #: compiled prefill programs keyed by (prompt_len, sample, top_k)
        self._prefill_fns: dict[tuple, Any] = {}
        self._init_fn = None  # cached jitted state initializer
        #: where a generation's host inputs go (prompt, scalars, where a
        #: chunk starts): whole on every stage's device, straight from
        #: the host.  Left on the default device they are copied from
        #: there to the other stages at every call, and that copy queues
        #: behind the chunk stage 0 is running
        self._everywhere = NamedSharding(self.mesh, P())
        #: the ring carry out of the newest chunk launched: a generation
        #: that was stopped may have left that chunk running
        self._tail = None

    # ------------------------------------------------------------------

    def _node_bytes(self, tree, rows: int | None = None) -> int:
        """Bytes of a node's leaves as :meth:`_place_weights` lays them
        (the compute type's; int8 values and a float32 scale a channel
        under W8A16), from their shapes; with ``rows``, of that many
        rows of each."""
        shapes = [np.shape(leaf) if rows is None else
                  (rows, *np.shape(leaf)[1:])
                  for leaf in jax.tree.leaves(tree)]
        if self.weight_quant:
            return sum(math.prod(sh) + 4 * math.prod(sh[-1:])
                       for sh in shapes)
        return self._wdt.itemsize * sum(map(math.prod, shapes))

    def _prefill_rows(self, plen: int) -> int:
        """Sequences of a group that cross a stage's prefill at once:
        all of them where their widest activation fits
        :data:`_PREFILL_PIECE_BYTES`, else the largest divisor of the
        group that does (at least one).  From shapes alone."""
        nodes = self.graph.nodes
        widest = max(nodes[nm].op.widest(self.d_model)
                     for nm in self.block_names)
        row = plen * widest * self.compute_dtype.itemsize
        mb = self.microbatch
        return max(r for r in range(1, mb + 1)
                   if mb % r == 0 and (r == 1 or r * row
                                       <= _PREFILL_PIECE_BYTES))

    def _place_weights(self, params, *, init: bool):
        """``params`` on the mesh as the compiled programs take them:
        ``{"blocks": (tree, ...), "ends": {name: tree}}``, every leaf
        ``[N, ...]`` and sharded over the stages.  Stage ``s``'s part of
        ``blocks[l]`` is its ``l``-th block's tree (zeros where it has
        fewer blocks), of ``ends[name]`` that node's tree on the one
        stage that holds it (zeros on the others).  Where the stages'
        ``l``-th blocks are of unlike kinds (a leading dense layer at
        the place of another stage's routed one), ``blocks[l]`` is a
        tuple of trees, one a kind, zeros on the stages whose block is
        of another (``self._variant[l]`` says which a stage reads).
        Under W8A16 a leaf is an ``Int8Weight`` of two such arrays.
        ``init=False`` (reweight): the new leaves must have the tree,
        the shapes and
        the types, before the cast to the compute type, of what was
        deployed — the compiled programs take that and nothing else.
        One leaf at a time goes host -> device, so the host never holds
        a second copy of all of them, and lies there row-major
        (:meth:`_leaf_format`): the compiled loops read a matrix with
        its last dimension on the lanes, and a device whose default for
        a shape is another order (the v5e's for ``[1, 6400, 1600]`` puts
        the 6400 there, 1600 being no multiple of 128) would have every
        dispatch convert the leaf on its way in.  Such a leaf is re-laid
        on the device, once, here; a program takes the layout its
        committed arguments have."""
        n, wdt = self.num_stages, self._wdt
        layout = {nm: _leaf_layout(params[nm])
                  for nm in (*self.block_names, *self._ends)}
        if init:
            self._layout = layout
        for nm, got in layout.items():
            if got != self._layout[nm]:
                raise ValueError(f"reweight: {nm}'s leaves are {got}, "
                                 f"deployed {self._layout[nm]}")

        def stacked(rows):
            rows = rows[0][None] if n == 1 else np.stack(rows)
            want = self._leaf_format(rows.ndim)
            leaf = jax.device_put(rows, want.sharding)
            held = leaf.format.layout
            if held is not None \
                    and held.major_to_minor != want.layout.major_to_minor:
                leaf = relaid(leaf, want)
            return leaf

        def placed(trees, real):
            """``trees``, one a stage, stacked leaf by leaf; a stage
            that is not ``real`` holds zeros of each leaf's shape."""
            def place(*per_stage):
                rows = [np.asarray(a) if ok else np.zeros(np.shape(a), wdt)
                        for ok, a in zip(real, per_stage)]
                if self.weight_quant:
                    return quant.Int8Weight(*map(stacked, zip(
                        *map(quant.quantize_weight, rows))))
                return stacked([r.astype(wdt, copy=False) for r in rows])

            return jax.tree.map(place, *trees)

        # one span a call, not one a leaf
        args: dict = {}
        with span("setup", "place", args):
            blocks, variant = [], []
            for l, longest in enumerate(max(self.stage_blocks, key=len)):
                # stage s's l-th block; where it has fewer, the longest
                # stage's stands in for the shapes
                real = [l < len(b) for b in self.stage_blocks]
                names = [b[l] if ok else longest
                         for ok, b in zip(real, self.stage_blocks)]
                kinds = [tuple(layout[nm].items()) for nm in names]
                distinct = list(dict.fromkeys(kinds))
                if len(distinct) == 1:
                    variant.append(None)
                    blocks.append(
                        placed([params[nm] for nm in names], real))
                    continue
                # unlike blocks at one place of their stages: a tree a
                # kind
                variant.append([distinct.index(kind) for kind in kinds])
                blocks.append(tuple(
                    placed([params[nm if kind == want else
                                   names[kinds.index(want)]]
                            for nm, kind in zip(names, kinds)],
                           [ok and kind == want
                            for ok, kind in zip(real, kinds)])
                    for want in distinct))
            #: per local layer, None where every stage's block has the
            #: same parameter tree, else the tree of ``blocks[l]`` each
            #: stage reads
            self._variant = variant
            ends = {nm: placed([params[nm]] * n, [s == at for s in range(n)])
                    for nm, at in self._ends.items()}
            w = {"blocks": tuple(blocks), "ends": ends}
            leaves = jax.tree.leaves(w)
            args.update(leaves=len(leaves),
                        bytes=sum(a.nbytes for a in leaves))
        return w

    def _leaf_format(self, ndim: int) -> Format:
        """Where a weight leaf of ``ndim`` dimensions lies: sharded over
        the stages by its first, and row-major — the tiling the
        device's own for the type."""
        return Format(Layout(tuple(range(ndim))), NamedSharding(
            self.mesh, P(STAGE_AXIS, *(None,) * (ndim - 1))))

    def weight_formats(self):
        """The ``Format`` of every leaf of the weights, in their tree: how
        a script that lowers a program from shapes
        (``jax.ShapeDtypeStruct(..., sharding=format)``) declares them
        as the decoder holds them."""
        return jax.tree.map(lambda a: self._leaf_format(a.ndim), self._w)

    def reweight(self, params) -> None:
        """Install fresh weights — no recompile, caches untouched.

        The decode analogue of ``SpmdPipeline.reweight``: compiled decode
        and prefill programs read the weights as arguments, so a swap
        redeploys (e.g. after further finetuning) without
        invalidating ``_decode_fns``/``_prefill_fns``.  Call between
        ``generate`` rounds — an in-flight generation keeps the weights
        it started with only up to its current dispatch boundary.
        """
        self._settle()
        self._w = self._place_weights(params, init=False)

    def _settle(self) -> None:
        """Wait for the chunk a stopped generation may have left running
        (it was launched before the chunk that showed the stop was read,
        and holds that generation's memory until it ends): whoever
        allocates next calls this first."""
        tail, self._tail = self._tail, None
        if tail is not None and not tail.is_deleted():
            tail.block_until_ready()

    def _stage_params(self, s: int, w_local):
        """Stage ``s``'s parameter trees by node, out of a device's part
        of the weights."""
        p = {nm: tree if which is None else tree[which[s]]
             for nm, tree, which in zip(self.stage_blocks[s],
                                        w_local["blocks"], self._variant)}
        p.update((nm, w_local["ends"][nm])
                 for nm, at in self._ends.items() if at == s)
        return quant.dequantize_weights(p, self.compute_dtype) \
            if self.weight_quant else p

    def _make_branch(self, s: int, sample: bool, top_k: int | None):
        """Stage ``s``'s step: consume the ring buffer, update caches.

        Uniform signature for ``lax.switch``:
        ``(w_local, a, caches, prompt, g, pos, plen, t, seed, temp,
        first_ids, first_pos) -> (a_out, caches)``.
        """
        n = self.num_stages
        nodes = self.graph.nodes
        cd = self.compute_dtype
        is_first, is_last = s == 0, s == n - 1
        block_ops = [nodes[nm].op for nm in self.stage_blocks[s]]
        embed_op = self.embed_op
        fmts = self.state_formats
        beam = self.beam_width
        mb = self.microbatch
        stats = self._stat_names

        def branch(w_local, a, caches, prompt, g, pos, plen, t, seed, temp,
                   first_ids, first_pos):
            p = self._stage_params(s, w_local)
            # bubble steps (pos < 0 during warmup skew, or pos >= max_len
            # on chunk-overshoot steps past the requested generation) go
            # where the format sends a bubble (a cache's scratch row; a
            # state's identity update); their outputs are never read
            # (host drops them by schedule index)
            valid = jnp.logical_and(pos >= 0, pos < self.max_len)
            safe_pos = jnp.clip(pos, 0, self.max_len - 1)
            # each format's own word for where this step's memory goes
            slots = {fmt: fmt.decode_slot(valid, safe_pos)
                     for fmt in dict.fromkeys(fmts)}

            if beam > 1:
                # re-parent this group's cache rows before appending the
                # incoming token: its activation was computed from the
                # CHOSEN beam's token, so history rows must match.  The
                # parent indices ride the ring in the extra column.  Only
                # beam-expansion arrivals (pos >= plen, non-bubble) carry
                # real parents — the cond skips the full-cache gather on
                # forced prompt steps and bubbles entirely.
                parents = jnp.clip(
                    jnp.round(a[:, self.d_model]).astype(jnp.int32),
                    0, mb - 1)
                applies = jnp.logical_and(valid, safe_pos >= plen)
                if self._rows_format is not None:
                    caches = lax.cond(
                        applies,
                        lambda cs: self._rows_format.reparent(cs, g, parents),
                        lambda cs: cs, caches)

            if is_first:
                recv_ids = jnp.round(a[:, 0]).astype(jnp.int32)
                prompt_ids = lax.dynamic_slice(
                    prompt, (g, 0, jnp.minimum(safe_pos, prompt.shape[2] - 1)),
                    (1, self.microbatch, 1))[0, :, 0]
                ids = jnp.where(safe_pos < plen, prompt_ids, recv_ids)
                # after a fused prefill the first generated token comes from
                # the prefill program, not the ring (first_pos = -1 disables)
                fi = lax.dynamic_slice(first_ids, (g, 0),
                                       (1, self.microbatch))[0]
                ids = jnp.where(safe_pos == first_pos, fi, ids)
                x = embed_op.embed_at(p["embeddings"], ids, safe_pos)
                x = x.astype(cd)
            else:
                x = a[:, : self.d_model].astype(cd)

            # counted anew a stage's trace: the layers whose format
            # writes the step's row inside its attention (kv_step), and
            # those that attend over joined rows (kv_attend_joined)
            REGISTRY.gauge("decode.kv.fused_layers").set(0)
            REGISTRY.gauge("decode.kv.joined_layers").set(0)
            for l, (nm, op) in enumerate(zip(self.stage_blocks[s],
                                             block_ops)):
                # the block's step against its layer's buffers where
                # they lie (a cache: one row written in place, then the
                # group's live rows attended over; a state: updated and
                # read in place): nothing the size of an item is cut out
                # of a buffer or written back
                sown = {} if stats else None
                fmt = fmts[l]
                x, layer = op.decode(p[nm], x, fmt.layer(caches, l),
                                     safe_pos, fmt, slots[fmt], g, sown)
                caches = fmt.with_layer(caches, l, layer)
                if stats:
                    step = jnp.stack([sown[k] for k in stats])
                    caches = dict(caches, stats=caches["stats"] + jnp.where(
                        valid, step.astype(jnp.int32), 0))
            caches = self._idle_layers(s, caches)

            if is_last:
                h = nodes["final_ln"].op.apply(p["final_ln"], x)
                logits = nodes["lm_head"].op.apply(
                    p["lm_head"], h).astype(jnp.float32)
                a_out = jnp.zeros((mb, self._ring_width), jnp.float32)
                if beam > 1:
                    # beam expansion: per sequence, the best `beam` of
                    # beam*V continuations by cumulative log-probability
                    nseq = mb // beam
                    vocab = logits.shape[-1]
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    cum = lax.dynamic_slice(caches["beam_cum"], (g, 0),
                                            (1, mb))[0]
                    sc = (cum.reshape(nseq, beam, 1)
                          + logp.reshape(nseq, beam, vocab))
                    # first expansion: every beam of a sequence is the
                    # same prompt — keep only beam 0's continuations
                    dup = jnp.logical_and(
                        safe_pos == plen - 1,
                        jnp.arange(beam)[None, :, None] > 0)
                    sc = jnp.where(dup, -jnp.inf, sc)
                    best, idx = lax.top_k(sc.reshape(nseq, beam * vocab),
                                          beam)
                    ids = (idx % vocab).reshape(mb)
                    par = (jnp.arange(nseq)[:, None] * beam
                           + idx // vocab).reshape(mb)
                    new_cum = best.reshape(mb)
                    # forced prompt steps keep identity/zero; bubbles keep
                    # the table untouched
                    forced = safe_pos < plen - 1
                    ids = jnp.where(forced, jnp.argmax(logits, -1), ids)
                    par = jnp.where(forced, jnp.arange(mb), par)
                    keep = jnp.logical_or(forced, jnp.logical_not(valid))
                    new_cum = jnp.where(keep, cum, new_cum)
                    caches = dict(caches, beam_cum=lax.dynamic_update_slice(
                        caches["beam_cum"], new_cum[None], (g, 0)))
                    a_out = a_out.at[:, self.d_model].set(
                        par.astype(jnp.float32))
                elif sample:
                    # keyed by the global step so results are identical
                    # under any dispatch chunking; rows draw independently
                    ids = sample_ids(
                        logits, temp, top_k,
                        jax.random.fold_in(jax.random.PRNGKey(seed), t))
                else:
                    ids = jnp.argmax(logits, axis=-1)
                a_out = a_out.at[:, 0].set(ids.astype(jnp.float32))
            else:
                a_out = x.astype(jnp.float32)
                if beam > 1:
                    # pass the incoming parent column onward unchanged —
                    # every stage re-derives applicability from pos
                    a_out = jnp.concatenate(
                        [a_out, a[:, self.d_model:]], axis=-1)
            return a_out, caches

        return branch

    def _idle_layers(self, s: int, caches):
        """``caches`` as stage ``s`` hands them on: the local layers it
        lacks (the longest stage's last) touched in place, so that no
        branch passes a buffer through (``LayeredState.idle``)."""
        for l in range(len(self.stage_blocks[s]), self.l_max):
            fmt = self.state_formats[l]
            caches = fmt.with_layer(caches, l, fmt.idle(fmt.layer(caches, l)))
        return caches

    def _make_prefill_branch(self, s: int, plen: int, sample: bool,
                             top_k: int | None):
        """Stage ``s``'s pipelined-prefill step: one whole prompt group.

        The group's full [mb, plen] prompt flows through the stages like
        one inference microbatch; each block runs full-sequence causal
        attention (``DecoderBlock.prefill``) and bulk-writes cache rows
        ``0..plen-1`` (or leaves the state after them); the last stage
        emits the first generated token (position ``plen``).  Bubble
        steps (g outside [0, n)) go where the format sends them: a
        cache's scratch group, a state's identity update.
        """
        n = self.num_stages
        nodes = self.graph.nodes
        cd = self.compute_dtype
        mb, d = self.microbatch, self.d_model
        is_first, is_last = s == 0, s == n - 1
        embed_op = self.embed_op
        fmts = self.state_formats
        rows = self._prefill_rows(plen)
        width = self._prefill_carry_width(plen)

        def slots(valid, group, row=None):
            """Each format's own word for where the prompts' memory goes."""
            return {fmt: fmt.prefill_slot(valid, group, row)
                    for fmt in dict.fromkeys(fmts)}

        def layers(p, x, caches, slot):
            for l, nm in enumerate(self.stage_blocks[s]):
                fmt = fmts[l]
                x, layer = nodes[nm].op.prefill(
                    p[nm], x, fmt.layer(caches, l), fmt, slot[fmt])
                caches = fmt.with_layer(caches, l, layer)
            return x, caches

        def last_logits(p, x):
            h = nodes["final_ln"].op.apply(p["final_ln"], x[:, -1])
            return nodes["lm_head"].op.apply(
                p["lm_head"], h).astype(jnp.float32)

        def branch(w_local, a, caches, prompt, g, seed, temp):
            p = self._stage_params(s, w_local)
            valid = jnp.logical_and(g >= 0, g < n)
            safe_g = jnp.clip(g, 0, n - 1)

            if rows == mb:
                slot = slots(valid, safe_g)
                if is_first:
                    ids = lax.dynamic_slice(prompt, (safe_g, 0, 0),
                                            (1, mb, plen))[0]
                    x = embed_op.apply(p["embeddings"], ids).astype(cd)
                else:
                    x = a.reshape(mb, plen, d).astype(cd)
                x, caches = layers(p, x, caches, slot)
                out = last_logits(p, x) if is_last else x
            else:
                # the group crosses the stage ``rows`` sequences at a
                # time: a piece is whole prompts, so each layer's
                # memory is written once a sequence, and the stage's
                # weights are read once a piece
                def piece(caches, i):
                    row = i * rows
                    if is_first:
                        ids = lax.dynamic_slice(prompt, (safe_g, row, 0),
                                                (1, rows, plen))[0]
                        x = embed_op.apply(p["embeddings"], ids).astype(cd)
                    else:
                        x = lax.dynamic_slice(
                            a.reshape(mb, plen, d), (row, 0, 0),
                            (rows, plen, d)).astype(cd)
                    x, caches = layers(p, x, caches,
                                       slots(valid, safe_g, row))
                    return caches, last_logits(p, x) if is_last \
                        else x.reshape(rows, plen * d).astype(jnp.float32)

                caches, out = lax.scan(piece, caches,
                                       jnp.arange(mb // rows))
                out = out.reshape((mb,) + out.shape[2:])

            caches = self._idle_layers(s, caches)
            if is_last:         # ``out`` the last position's logits
                if sample:
                    # key domain disjoint from decode's per-step keys
                    ids = sample_ids(
                        out, temp, top_k,
                        jax.random.fold_in(jax.random.PRNGKey(seed),
                                           (1 << 30) + safe_g))
                else:
                    ids = jnp.argmax(out, axis=-1)
                a_out = jnp.zeros((mb, width), jnp.float32)
                a_out = a_out.at[:, 0].set(ids.astype(jnp.float32))
            else:
                a_out = out.reshape(mb, plen * d).astype(jnp.float32)
            return a_out, caches

        return branch

    def _prefill_carry_width(self, plen: int) -> int:
        """Columns of the prefill's ring carry: a group's activations
        hop from stage to stage, ``plen * d`` a sequence.  One stage
        sends nothing but the first ids over its wrap link; where its
        prefill runs in pieces (the carry would be the size that
        forced them) it carries one column."""
        if self.num_stages == 1 and self._prefill_rows(plen) \
                < self.microbatch:
            return 1
        return plen * self.d_model

    def _state_specs(self):
        """shard_map spec pytree for the cache-state dict."""
        # one buffer a local block and key, never one array of the whole
        # stack: each of its own layer's shape, and None where the
        # layer's format has no such key
        specs = jax.tree.map(
            lambda buf: P(STAGE_AXIS, *(None,) * len(buf.shape)),
            shapes_by_layer(self.state_formats, self.microbatch))
        if self.beam_width > 1:
            # per-group cumulative beam scores; only the LAST stage's
            # device shard is meaningful (it runs the expansion)
            specs["beam_cum"] = P(STAGE_AXIS, None, None)
        return specs

    def _build_prefill_fn(self, plen: int, sample: bool, top_k: int | None):
        n = self.num_stages
        perm = [(k, (k + 1) % n) for k in range(n)]
        branches = [self._make_prefill_branch(s, plen, sample, top_k)
                    for s in range(n)]
        mb = self.microbatch
        width = self._prefill_carry_width(plen)
        num_steps = 2 * n - 1  # n groups through n stages, pipelined

        def device_prefill(w, prompt, seed, temp, caches):
            w_l = jax.tree.map(lambda x: x[0], w)
            idx = lax.axis_index(STAGE_AXIS)
            a0 = jnp.zeros((mb, width), jnp.float32)
            local = jax.tree.map(lambda c: c[0], caches)

            def body(carry, t):
                a, caches = carry
                g = t - idx  # stage idx prefills group t - idx
                a_out, caches = lax.switch(
                    idx, branches, w_l, a, caches, prompt, g, seed, temp)
                a_next = lax.ppermute(a_out, STAGE_AXIS, perm)
                return (a_next, caches), a_next[:, 0]

            (_, local), ids = lax.scan(
                body, (a0, local), jnp.arange(num_steps, dtype=jnp.int32))
            return jax.tree.map(lambda c: c[None], local), ids[None]

        state = self._state_specs()
        fn = jax.shard_map(
            device_prefill, mesh=self.mesh,
            in_specs=(self._wspec_tree, P(None, None, None), P(), P(),
                      state),
            out_specs=(state, P(STAGE_AXIS, None, None)),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(4,),
                       **ring_jit_kwargs(self.mesh.devices))

    def _init_state(self):
        """Fresh sharded pipeline state: ring carry + empty memory.

        The zero-fill programs are jitted ONCE and cached — a fresh lambda
        per call would recompile (~0.4 s each) on every ``generate``.
        """
        with span("setup", "state"):
            if self._init_fn is None:
                n, mb = self.num_stages, self.microbatch
                act_sh = NamedSharding(self.mesh, P(STAGE_AXIS, None, None))
                state_sh = jax.tree.map(
                    lambda spec: NamedSharding(self.mesh, spec),
                    self._state_specs())

                def zeros():
                    caches = zeros_by_layer(self.state_formats, mb,
                                            lead=(n,))
                    if self.beam_width > 1:
                        caches["beam_cum"] = jnp.zeros((n, n, mb),
                                                       jnp.float32)
                    return (jnp.zeros((n, mb, self._ring_width),
                                      jnp.float32), caches)

                self._init_fn = jax.jit(
                    zeros, out_shardings=(act_sh, state_sh))
            return self._init_fn()

    def _build_decode_fn(self, chunk_steps: int, sample: bool,
                         top_k: int | None):
        n = self.num_stages
        perm = [(k, (k + 1) % n) for k in range(n)]
        branches = [self._make_branch(s, sample, top_k) for s in range(n)]
        beam = self.beam_width > 1
        d = self.d_model
        stats = self._stat_names

        def device_decode(w, prompt, plen, t0, t_stop, seed, temp,
                          first_ids, first_pos, start, a, caches):
            w_l = jax.tree.map(lambda x: x[0], w)
            idx = lax.axis_index(STAGE_AXIS)
            local = jax.tree.map(lambda c: c[0], caches)
            if stats:
                # what this stage's blocks sow is summed over the chunk
                # and leaves as an output of its own: the host can read
                # a chunk's sums while the next chunk holds the state
                local = dict(local, stats=jnp.zeros(len(stats), jnp.int32))

            def body(carry, t):
                a, caches = carry
                # stage idx serves group (t - idx) mod n at token position
                # start + (t - idx)//n; negative skew = warmup bubble, and
                # chunk-overshoot steps (t >= t_stop) are bubbles too —
                # they must not touch caches or the beam ledger
                rel = t - idx
                live = jnp.logical_and(rel >= 0, t < t_stop)
                g = jnp.where(live, rel % n, 0)
                pos = jnp.where(live, start + rel // n, -1)
                a_out, caches = lax.switch(
                    idx, branches, w_l, a, caches, prompt, g, pos, plen,
                    t, seed, temp, first_ids, first_pos)
                a_next = lax.ppermute(a_out, STAGE_AXIS, perm)
                # emit what just arrived on the wrap link: ids (and, under
                # beam search, parent indices) chosen by the last stage,
                # readable on device 0 (runtime/spmd.py emits the same
                # slice for the inference pipeline)
                emit = (jnp.stack([a_next[:, 0], a_next[:, d]], axis=-1)
                        if beam else a_next[:, 0])
                return (a_next, caches), emit

            (a, local), ids = lax.scan(
                body, (a[0], local),
                t0 + jnp.arange(chunk_steps, dtype=jnp.int32))
            sown = (local.pop("stats")[None],) if stats else ()
            return (a[None], jax.tree.map(lambda c: c[None], local),
                    ids[None]) + sown

        state = self._state_specs()
        out_ids = P(STAGE_AXIS, None, None, None) if beam \
            else P(STAGE_AXIS, None, None)
        fn = jax.shard_map(
            device_decode, mesh=self.mesh,
            in_specs=(self._wspec_tree, P(None, None, None), P(), P(),
                      P(), P(), P(), P(None, None), P(), P(),
                      P(STAGE_AXIS, None, None), state),
            out_specs=(P(STAGE_AXIS, None, None), state, out_ids)
            + ((P(STAGE_AXIS, None),) if stats else ()),
            check_vma=False,
        )
        # donate the carried state so chunked dispatches update in place
        return jax.jit(fn, donate_argnums=(10, 11),
                       **ring_jit_kwargs(self.mesh.devices))

    # ------------------------------------------------------------------

    def _schedule(self, t_tok: int, start: int,
                  token_chunk: int | None) -> tuple[int, int]:
        """(num_steps, chunk_steps) for decoding positions (start, t_tok).

        The last needed step emits position t_tok-1 of the last group:
        ``(n-1) + n*(t_tok-2-start) + (n-1)``; one schedule shared by the
        greedy/sampling and beam paths."""
        n = self.num_stages
        num_steps = (n - 1) + n * (t_tok - 2 - start) + (n - 1) + 1 \
            if t_tok - 1 > start else 0
        chunk_steps = max(num_steps, n) if token_chunk is None \
            else max(n, n * int(token_chunk))
        return num_steps, chunk_steps

    def _get_decode_fn(self, chunk_steps: int, sample: bool,
                       top_k: int | None):
        key = (chunk_steps, sample, top_k)
        fn = self._decode_fns.get(key)
        if fn is None:
            # this generation calls it through ``setup.first_call``
            fn = spanned_first_call(self._decode_fns.setdefault(
                key, self._build_decode_fn(chunk_steps, sample, top_k)))
        return fn

    def _gather_init(self, prompt: np.ndarray, plen: int, t_tok: int,
                     start: int,
                     first_ids: np.ndarray | None) -> tuple[np.ndarray, int]:
        """Token output skeleton + the first position decode steps fill."""
        n, mb = self.num_stages, self.microbatch
        out = np.zeros((n, mb, t_tok), np.int64)
        out[:, :, :plen] = prompt[:, :, :plen]
        if first_ids is not None and start < t_tok:
            out[:, :, start] = first_ids.astype(np.int64)
            return out, start + 1
        return out, max(1, plen)

    def _gather_into(self, out: np.ndarray, ids_steps: np.ndarray,
                     t0: int, t_tok: int, start: int, p0: int) -> None:
        """Scatter one chunk of emitted wrap-link ids into ``out``.

        Each decode scan step t >= n-1 emits exactly one (group, position):
        ``g = (t - (n-1)) % n``, ``p = start + 1 + (t - (n-1) - g) // n``
        — the inverse of "token p of group g is sampled at step
        (n-1) + n*(p-1-start) + g".  O(chunk) per call, so chunked EOS
        checking stays linear in the total step count.
        """
        n = self.num_stages
        for i in range(ids_steps.shape[0]):
            t = t0 + i
            if t < n - 1:
                continue
            g = (t - (n - 1)) % n
            p = start + 1 + (t - (n - 1) - g) // n
            if p0 <= p < t_tok:
                out[g, :, p] = ids_steps[i].astype(np.int64)

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int, *,
                 temperature: float = 0.0, top_k: int | None = None,
                 seed: int = 0, eos_id: int | None = None,
                 token_chunk: int | None = None,
                 prefill: bool = False,
                 on_tokens=None) -> np.ndarray:
        """Decode ``max_new_tokens`` past each prompt.

        ``prompt_ids``: [B, prompt_len] ints, B % microbatch == 0; batches
        beyond one pipeline fill (num_stages * microbatch) are processed
        in successive full-pipe rounds.  All prompts share one length
        (pad/bucket upstream).  Returns [B, prompt_len + max_new_tokens].

        ``temperature=0`` is greedy argmax; ``temperature>0`` samples the
        softmax (optionally truncated to ``top_k``), keyed by
        ``(seed, step)`` so results do not depend on dispatch chunking.
        ``token_chunk`` splits the scan into dispatches of that many tokens
        per group (one compiled program serves every generation length);
        the default is the whole generation in one dispatch.  ``eos_id``
        stops early once every sequence has emitted it and fills the tail
        with ``eos_id``.  The stop is seen one chunk late: the loop
        launches chunk n+1 before it reads chunk n, so one chunk past
        the one that showed the stop has been launched by then.  It is
        discarded (counter ``decode.ahead.discarded``): its tokens reach
        neither ``on_tokens`` nor the result.

        ``prefill=True`` seeds the KV caches with a fused full-sequence
        pipelined pass (each group's whole prompt crosses each stage in
        ONE causal-attention step) instead of decode-rate teacher forcing:
        prompt cost drops from ``plen * n`` ring steps to ``2n - 1``.
        Greedy results are identical up to float reduction order; sampled
        results use a different key for the first generated token.

        ``on_tokens(lo, hi, tokens, rows=(r0, r1))`` streams newly
        decodable positions to the caller after each chunk dispatch:
        ``tokens`` is [r1-r0, hi-lo] for positions [lo, hi) of sequence
        rows [r0, r1) (generated region only; rows=(0, B) unless the
        batch spans several pipeline-fill rounds) — pair with
        ``token_chunk`` for incremental delivery.  With ``eos_id``,
        streamed tokens past a sequence's EOS are garbage the final
        result replaces with ``eos_id``.  A chunk's tokens are handed
        over while the next chunk (where there is one) already runs.
        ``on_tokens`` may raise to stop the generation: the exception
        passes through, and ``generate`` returns without waiting for
        the chunk it had launched ahead, which runs to its end on the
        device (the counters of what the blocks sowed hold the chunks
        handed over, not that one).

        After a generation ``self.state`` is the memory as its last
        chunk left it; after a stop (EOS, a raising callback) it is at
        the stop or one chunk past it.  The next ``generate`` (and
        ``reweight``) first waits for a chunk left running, then lets
        that memory go, then allocates.
        """
        prompt_ids = np.asarray(prompt_ids)
        if prompt_ids.ndim != 2:
            raise ValueError("prompt_ids must be [B, prompt_len]")
        b, plen = prompt_ids.shape
        if plen < 1:
            raise ValueError("prompt must contain at least one token "
                             "(position 0 has nothing to condition on)")
        n, mb = self.num_stages, self.microbatch
        if self.beam_width > 1:
            if prefill or eos_id is not None or float(temperature) > 0:
                raise ValueError(
                    "beam search currently composes with neither prefill, "
                    "eos_id, nor temperature sampling")
            if on_tokens is not None:
                raise ValueError(
                    "beam search cannot stream tokens (sequences are only "
                    "final after the last re-parenting)")
            return self._generate_beam(prompt_ids, max_new_tokens,
                                       token_chunk=token_chunk)
        if b % mb or b == 0:
            raise ValueError(
                f"B={b} must be a non-zero multiple of microbatch={mb}")
        if b > n * mb:
            # more sequences than one pipeline fill: successive rounds.
            # Each round derives its own seed — otherwise identical
            # prompts in different rounds would sample identical
            # continuations (the step keys restart at t=0 every round).
            # Streaming callers see each round's spans in turn; the
            # rows kwarg identifies the round's sequence range.
            outs = []
            for lo in range(0, b, n * mb):
                cb = None
                if on_tokens is not None:
                    def cb(a, c, t, rows, _lo=lo):  # noqa: E306
                        on_tokens(a, c, t,
                                  rows=(_lo + rows[0], _lo + rows[1]))
                outs.append(self.generate(
                    prompt_ids[lo: lo + n * mb], max_new_tokens,
                    temperature=temperature, top_k=top_k, seed=seed + lo,
                    eos_id=eos_id, token_chunk=token_chunk,
                    prefill=prefill, on_tokens=cb))
            return np.concatenate(outs, axis=0)
        t_tok = plen + max_new_tokens
        if t_tok > self.max_len:
            raise ValueError(
                f"prompt_len + max_new_tokens = {t_tok} exceeds "
                f"max_len={self.max_len}")
        with span("decode", "generate",
                  {"rows": b, "prompt_len": plen,
                   "new_tokens": max_new_tokens,
                   "chunk_steps": self._schedule(
                       t_tok, plen if prefill else 0, token_chunk)[1]}):
            return self._generate_fill(
                prompt_ids, t_tok, temperature=temperature, top_k=top_k,
                seed=seed, eos_id=eos_id, token_chunk=token_chunk,
                prefill=prefill, on_tokens=on_tokens)

    def _post_rows_read(self, rows: int, positions: int) -> None:
        """Set what the newest step read of the layers' memory for its
        ``rows`` sequences at ``positions`` positions each, by the
        names their formats count it under (a KV cache's rows, a
        window's or a full layer's).  Host integers, no device work;
        nothing where the formats tell no kinds apart."""
        for name, value in totals(
                self._row_readers,
                lambda fmt: fmt.rows_read(rows, positions)).items():
            REGISTRY.gauge(name).set(value)

    def _post_stats(self, sums: np.ndarray) -> None:
        """Add ``sums``, what the blocks sowed
        (``DecoderBlock.decode_stats``) in the chunks a generation read,
        to the ``decode.<name>`` counters.  Host numbers (each chunk's
        came with its ids): nothing here waits for the device, so a
        chunk still running behind a stop is not waited for."""
        if not self._stat_names:
            return
        with span("decode", "moe_stats"):
            for name, total in zip(self._stat_names, sums):
                REGISTRY.counter(f"decode.{name}").inc(int(total))

    def _generate_fill(self, prompt_ids: np.ndarray, t_tok: int, *,
                       temperature, top_k, seed, eos_id, token_chunk,
                       prefill, on_tokens) -> np.ndarray:
        """One pipeline fill of :meth:`generate` (arguments checked
        there), under its ``decode.generate`` span.  The phases of
        ``obs/profile.py::DECODE_PHASES`` tile the chunk loop: each
        starts where the last ended."""
        n, mb = self.num_stages, self.microbatch
        b, plen = prompt_ids.shape
        prompt = np.zeros((n, mb, plen), np.int32)
        prompt.reshape(n * mb, plen)[:b] = prompt_ids
        if t_tok == plen:
            return prompt.reshape(n * mb, plen)[:b].astype(np.int64)
        sample = float(temperature) > 0.0
        if not sample:
            top_k = None  # unused by argmax; keep the program caches keyed
            # identically so greedy calls never recompile over it
        with span("decode", "init"):
            # a chunk the last generation launched ahead of its stop may
            # still run and hold its memory: wait, then let that go
            self._settle()
            self.state = None
            prompt_dev, plen_s, seed_s, temp_s = jax.device_put(
                (prompt, np.int32(plen), np.uint32(seed),
                 np.float32(temperature)), self._everywhere)
            a, caches = self._init_state()

        if prefill:
            pkey = (plen, sample, top_k)
            pfn = self._prefill_fns.get(pkey)
            if pfn is None:
                pfn = spanned_first_call(self._prefill_fns.setdefault(
                    pkey, self._build_prefill_fn(plen, sample, top_k)))
            with span("decode", "prefill"):
                caches, pre_ids = pfn(self._w, prompt_dev, seed_s, temp_s,
                                      caches)
                pre_np = np.asarray(pre_ids[0])
            start = plen
        else:
            start = 0

        with span("decode", "init"):
            # group g's first generated token exits the wrap link at
            # prefill step g + (n-1)
            first_ids_np = np.stack(
                [pre_np[g + n - 1] for g in range(n)]).astype(np.int32) \
                if prefill else None
            # with prefill, position `start` is already known (first_ids)
            num_steps, chunk_steps = self._schedule(t_tok, start,
                                                    token_chunk)
            fn = self._get_decode_fn(chunk_steps, sample, top_k)
            fi_dev, fp_s, start_s = jax.device_put(
                (first_ids_np if first_ids_np is not None
                 else np.zeros((n, mb), np.int32),
                 np.int32(plen if prefill else -1), np.int32(start)),
                self._everywhere)
            out3, p0 = self._gather_init(prompt, plen, t_tok, start,
                                         first_ids_np)
        flat = out3.reshape(n * mb, t_tok)[:b]
        p_done = plen - 1  # last position already delivered to on_tokens
        if on_tokens is not None and prefill and t_tok > plen:
            # the prefill already produced position plen (first_ids)
            with span("decode", "emit"):
                on_tokens(plen, plen + 1, flat[:, plen: plen + 1].copy(),
                          rows=(0, b))
            p_done = plen
        # Chunk n+1 needs nothing of chunk n that the host reads (its
        # inputs are n's device outputs and where it starts), so a chunk
        # is launched before the one ahead of it is read: the device has
        # its next program while the host wakes, scatters and emits.
        # Tokens wanted as they come (streaming, the EOS check) keep one
        # chunk unread behind the newest; otherwise every chunk is
        # launched first and all are read at the end.
        unread = 1 if eos_id is not None or on_tokens is not None \
            else num_steps
        launched: collections.deque = collections.deque()
        ahead = REGISTRY.counter("decode.ahead.launched")
        steps_run = 0           # the next chunk to launch starts here
        all_eos = False
        # what the blocks sowed in the chunks read so far
        sums = np.zeros(len(self._stat_names), np.int64)
        try:
            while not all_eos and (steps_run < num_steps or launched):
                if steps_run < num_steps:
                    with span("decode", "dispatch",
                              {"steps_run": steps_run,
                               "chunk_steps": chunk_steps}):
                        with span("decode", "upload"):
                            at = jax.device_put(
                                (np.int32(steps_run), np.int32(num_steps)),
                                self._everywhere)
                        with span("decode", "launch"):
                            a, caches, ids, *sown = fn(
                                self._w, prompt_dev, plen_s, *at, seed_s,
                                temp_s, fi_dev, fp_s, start_s, a, caches)
                        # stage 0's shard, where the wrap link's ids
                        # arrive, and each stage's sums: buffers of this
                        # chunk, whose transfer waits for it alone
                        # (``ids[0]`` is a program of its own, queued
                        # behind the next chunk)
                        out = [ids.addressable_data(0), *sown]
                        # dropped while the device runs the chunk, as the
                        # call's own temporaries were: not when the next
                        # chunk waits
                        del at, ids, sown
                    if launched:
                        ahead.inc()
                    launched.append((steps_run, out))
                    steps_run += chunk_steps
                    if steps_run < num_steps and len(launched) <= unread:
                        continue
                t0, out = launched.popleft()
                with span("decode", "sync", {"ahead": int(bool(launched))}):
                    ids_np, *sown = [np.asarray(o) for o in out]
                    del out
                with span("decode", "scatter"):
                    # incremental scatter of just this chunk: linear host
                    # work
                    self._gather_into(out3, ids_np[0], t0, t_tok, start, p0)
                    if sown:
                        sums += sown[0].sum(axis=0)
                    # positions already decodable for EVERY group this far
                    p_avail = start + min(
                        (t0 + chunk_steps - 1 - (n - 1) - g) // n + 1
                        for g in range(n))
                    p_avail = min(p_avail, t_tok - 1)
                    # the step that made position p_avail read the
                    # p_avail rows before it
                    self._post_rows_read(b, p_avail)
                    new = None
                    if on_tokens is not None and p_avail > p_done \
                            and p_avail >= plen:
                        lo = max(p_done + 1, plen)
                        new = flat[:, lo: p_avail + 1].copy()
                    all_eos = eos_id is not None and p_avail >= plen \
                        and np.all((flat[:, plen: p_avail + 1]
                                    == eos_id).any(axis=1))
                if new is not None:
                    with span("decode", "emit"):
                        on_tokens(lo, p_avail + 1, new, rows=(0, b))
                    p_done = p_avail
        finally:
            # a stop (every sequence at its EOS, a callback that raised)
            # leaves the chunk launched ahead of it unread: its tokens
            # reach no one, and the next allocation waits for it
            REGISTRY.counter("decode.ahead.discarded").inc(len(launched))
            self.state, self._tail = caches, a
            # however the generation ended: what the chunks it read sowed
            self._post_stats(sums)
        if eos_id is not None:
            # freeze everything after each sequence's first generated EOS
            gen = flat[:, plen:]
            hit = gen == eos_id
            first = np.where(hit.any(1), hit.argmax(1), gen.shape[1])
            mask = np.arange(gen.shape[1])[None, :] > first[:, None]
            gen[mask] = eos_id
        return flat

    def _generate_beam(self, prompt_ids: np.ndarray, max_new_tokens: int,
                       *, token_chunk: int | None) -> np.ndarray:
        """Pipelined beam search; returns each prompt's best sequence.

        Each prompt occupies ``beam_width`` adjacent microbatch rows.  The
        last stage expands beams (top ``beam`` of beam*V continuations by
        cumulative log-probability, duplicate-masked on the first
        expansion) and the chosen parent indices ride the ring's extra
        column so every stage re-parents its cache rows before appending
        (see ``_make_branch``).  The host backtracks the recorded
        (token, parent) pairs and picks the best final beam per prompt.
        """
        n, mb, beam = self.num_stages, self.microbatch, self.beam_width
        b, plen = prompt_ids.shape
        nspg = mb // beam  # sequences per group
        if b % nspg or b == 0:
            raise ValueError(
                f"B={b} must be a non-zero multiple of "
                f"microbatch/beam_width = {nspg}")
        if b > n * nspg:
            return np.concatenate(
                [self._generate_beam(prompt_ids[lo: lo + n * nspg],
                                     max_new_tokens,
                                     token_chunk=token_chunk)
                 for lo in range(0, b, n * nspg)], axis=0)
        t_tok = plen + max_new_tokens
        if t_tok > self.max_len:
            raise ValueError(
                f"prompt_len + max_new_tokens = {t_tok} exceeds "
                f"max_len={self.max_len}")

        # each prompt duplicated over its beam rows
        rows = np.repeat(prompt_ids, beam, axis=0)
        prompt = np.zeros((n, mb, plen), np.int32)
        prompt.reshape(n * mb, plen)[: rows.shape[0]] = rows
        if t_tok == plen:
            return prompt_ids.astype(np.int64)

        num_steps, chunk_steps = self._schedule(t_tok, 0, token_chunk)
        fn = self._get_decode_fn(chunk_steps, False, None)

        prompt_dev = jnp.asarray(prompt)
        zero = jnp.int32(0)
        fi_dev = jnp.zeros((n, mb), jnp.int32)
        a, caches = self._init_state()
        chunks = []
        steps_run = 0
        while steps_run < num_steps:
            # (blocks that sow hand their sums out last; no one reads
            # them here)
            a, caches, ids = fn(self._w, prompt_dev, jnp.int32(plen),
                                jnp.int32(steps_run), jnp.int32(num_steps),
                                jnp.uint32(0), jnp.float32(0.0), fi_dev,
                                jnp.int32(-1), zero, a, caches)[:3]
            chunks.append(ids)
            steps_run += chunk_steps
        arr = np.concatenate([np.asarray(c[0]) for c in chunks], axis=0)
        toks = np.round(arr[..., 0]).astype(np.int64)   # [T, mb]
        pars = np.round(arr[..., 1]).astype(np.int64)
        # final cumulative scores live on the last stage's shard
        cum = np.asarray(caches["beam_cum"])[n - 1]      # [n_groups, mb]

        out = np.zeros((b, t_tok), np.int64)
        out[:, :plen] = prompt_ids
        for s in range(b):
            g, si = divmod(s, nspg)
            row_lo = si * beam
            r = row_lo + int(np.argmax(cum[g, row_lo: row_lo + beam]))
            for p in range(t_tok - 1, plen - 1, -1):
                t = (n - 1) + n * (p - 1) + g
                out[s, p] = toks[t, r]
                r = int(pars[t, r])
        return out
