"""SPMD pipeline engine: the TPU-native heart of the framework.

What the reference does with a chain of TCP-connected hosts — each node
receives an activation, runs ``model.predict`` on its partition, compresses
and relays to its successor (reference src/node.py:80-108), with the
dispatcher feeding node 0 and receiving from node N-1
(src/dispatcher.py:85-105) — this engine does inside a single jit-compiled
SPMD program over a ``stage`` mesh axis:

  * Each device holds exactly its stage's weights (sharded flat buffer, no
    runtime weight shipping — replaces the control plane of
    src/dispatcher.py:44-65).
  * Per pipeline step every device runs its stage via ``lax.switch`` on its
    stage index, then ``lax.ppermute``s its activation to its successor over
    ICI — the TPU-native "send to next node" (src/node.py:108).  The wrap
    link (stage N-1 → stage 0) is the reference's "last node points back at
    the dispatcher" (src/dispatcher.py:51-55).
  * ``lax.scan`` fuses many steps into one XLA program, so the whole
    streaming loop (recv → decompress → queue → predict → compress → send,
    reference §3.3) collapses to compute + collective with zero host-side
    tensor serialization.
  * Activations cross stages in one homogeneous padded buffer so the single
    program covers heterogeneous stage shapes; buffer dtype bfloat16 is the
    TPU-idiomatic analogue of the reference's lossy ZFP wire compression.

Schedule: inference (GPipe-style fill/drain-free streaming): at step t device
0 starts microbatch t, device k computes microbatch t-k, device N-1 emits
microbatch t-N+1.  After N-1 warmup steps every device is busy every step —
DEFER's "all stages process different in-flight inputs concurrently"
(SURVEY.md §0), with the in-flight window = pipeline depth.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..graph.ir import ShapeSpec
from ..obs import tracer
from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, STAGE_AXIS, pipeline_mesh
from ..partition.stage import StageSpec, buffer_footprint
from ..utils.metrics import PipelineMetrics
from ..utils.xla_opts import ring_jit_kwargs
from . import flatbuf


class SpmdPipeline:
    """Inference pipeline over the ``stage`` axis of a device mesh.

    Usage::

        stages = partition(graph, cut_points)
        pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(len(stages)))
        outputs = pipe.run(inputs)          # [M, B, ...] -> [M, B, ...]

    or streaming: ``reset()`` / ``push(chunk, n_real)`` / ``flush()``.
    """

    def __init__(
        self,
        stages: Sequence[StageSpec],
        params: dict[str, Any],
        *,
        mesh: Mesh | None = None,
        microbatch: int = 1,
        chunk: int = 16,
        buffer_dtype=jnp.float32,
        compute_dtype=None,
        wire: str = "buffer",
        master_weights: bool = False,
    ):
        self.stages = list(stages)
        self.num_stages = n = len(self.stages)
        self.mesh = mesh if mesh is not None else pipeline_mesh(n)
        if self.mesh.shape[STAGE_AXIS] != n:
            raise ValueError(
                f"mesh stage axis is {self.mesh.shape[STAGE_AXIS]} but "
                f"pipeline has {n} stages")
        self.data_parallel = self.mesh.shape.get(DATA_AXIS, 1)
        self.tensor_parallel = tp = self.mesh.shape.get(MODEL_AXIS, 1)
        if microbatch % self.data_parallel:
            raise ValueError("microbatch must divide by data_parallel")
        self.microbatch = microbatch
        self.chunk = chunk
        self.buffer_dtype = jnp.dtype(buffer_dtype)
        self.compute_dtype = jnp.dtype(compute_dtype) if compute_dtype else None

        # --- weights: one flat vector per stage (per TP rank when the
        # mesh has a "model" axis), padded & stacked to [N, (tp,) Pmax] and
        # sharded over (stage[, model]).  Each device materializes only its
        # own stage's — and, under TP, its own rank's — parameters.  The
        # buffer is stored in ``compute_dtype`` when set (bf16 deployments
        # hold bf16 weights in HBM — half the footprint, no per-step
        # recast inside the branch); float32 otherwise.
        # ``master_weights=True`` keeps the buffer f32 regardless and casts
        # to compute_dtype inside each stage branch — the mixed-precision
        # training recipe (optimizer updates land in full precision; XLA
        # fuses the per-step downcast into the stage program).
        self.master_weights = bool(master_weights)
        self.weight_dtype = wdt = np.dtype(
            self.compute_dtype
            if self.compute_dtype is not None and not self.master_weights
            else np.float32)
        self._wmeta: list[list[tuple[int, int, tuple[int, ...], Any]]] = []
        self._wtreedef = []
        #: per stage, per leaf: True when the leaf is REPLICATED across tp
        #: ranks (its shard shape equals the full leaf's shape) — the
        #: trainer needs this to sum tied-copy gradients across ranks
        self._wreplicated: list[list[bool]] = []
        self._wspec = P(STAGE_AXIS, MODEL_AXIS, None) if tp > 1 \
            else P(STAGE_AXIS, None)
        self._w = jax.device_put(self._pack_wbuf(params, init=True),
                                 NamedSharding(self.mesh, self._wspec))

        # --- homogeneous activation buffer sizing (shared geometry
        # helper: under wire="int8" the buffer pads to the quant block
        # size so hops block-quantize cleanly in HBM)
        if wire not in ("buffer", "int8"):
            raise ValueError(f"wire must be 'buffer' or 'int8', got {wire!r}")
        self.wire = wire
        self._in_sizes = [s.in_spec.size for s in self.stages]
        self._out_sizes = [s.out_spec.size for s in self.stages]
        self._footprint = buffer_footprint(
            self.stages, microbatch=microbatch,
            itemsize=self.buffer_dtype.itemsize, wire=wire)
        self.buf_elems = self._footprint["buf_elems"]
        self.in_spec: ShapeSpec = self.stages[0].in_spec
        self.out_spec: ShapeSpec = self.stages[-1].out_spec

        self._branches = [self._make_branch(k) for k in range(n)]
        self._chunk_fn = self._build_chunk_fn()

        self._act_sharding = NamedSharding(
            self.mesh, P(STAGE_AXIS, DATA_AXIS, None)
            if self.data_parallel > 1 else P(STAGE_AXIS, None, None))
        self._xs_sharding = NamedSharding(
            self.mesh, P(None, DATA_AXIS, None)
            if self.data_parallel > 1 else P(None, None, None))

        if (jnp.issubdtype(self.in_spec.dtype, jnp.integer)
                and self.buffer_dtype != jnp.float32):
            raise ValueError(
                "integer model inputs (e.g. token ids) require "
                "buffer_dtype=float32: ids above 256 are not exactly "
                f"representable in {self.buffer_dtype.name}")

        self.metrics = PipelineMetrics(
            num_stages=n, microbatch=microbatch, buffer_elems=self.buf_elems,
            buffer_bytes_per_hop=self._footprint["bytes_per_hop"])
        # telemetry: publish this deployment into the process registry
        # (scalar counters + push/stage histograms + derived per-hop
        # bytes-on-wire — the ICI-side wire accounting)
        self.metrics.bind()
        self._flush_zeros = None  # lazy device-resident bubble block
        self.reset()

    # ------------------------------------------------------------------
    # program construction
    # ------------------------------------------------------------------

    def _to_wire(self, leaf: np.ndarray, stage_name: str) -> np.ndarray:
        """Cast one param leaf into the flat weight buffer's dtype.

        Float leaves simply cast (lossy to bf16 is the deployment's choice).
        Integer/bool leaves are only accepted when they round-trip exactly
        through the buffer dtype — the reference ships raw per-dtype arrays
        (src/dispatcher.py:67-80) so it never has this hazard; the flat
        homogeneous buffer does, and silently corrupted int params (e.g.
        embedding ids) would be far worse than a loud error here.
        """
        wdt = self.weight_dtype
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf.astype(wdt)
        cast = leaf.astype(wdt)
        if not np.array_equal(cast.astype(leaf.dtype), leaf):
            raise ValueError(
                f"stage {stage_name!r} has a non-float param leaf "
                f"(dtype {leaf.dtype}) whose values do not survive the "
                f"{wdt} weight buffer; use compute_dtype=None (float32 "
                f"buffer, exact for |int| < 2**24) or keep such leaves "
                f"out of the flat buffer")
        return cast

    def _pack_wbuf(self, params, *, init: bool = False) -> np.ndarray:
        """Pack ``params`` into the [N, (tp,) Pmax] flat weight buffer.

        ``init=True`` (constructor) records per-stage leaf meta/treedefs;
        ``init=False`` (reweight) validates the new leaves against the
        recorded layout — same shapes or a loud error.
        """
        tp = self.tensor_parallel
        n = self.num_stages
        wdt = self.weight_dtype
        flats: list[list[np.ndarray]] = []  # [stage][tp_rank]
        for k, s in enumerate(self.stages):
            rank_flats = []
            full_shapes = None
            if tp > 1:
                full_shapes = [np.shape(l) for l in
                               jax.tree.flatten(s.select_params(params))[0]]
            for r in range(tp):
                shard = (s.tp_shard_params(params, tp, r) if tp > 1
                         else s.select_params(params))
                leaves, treedef = jax.tree.flatten(shard)
                if r == 0:
                    if init:
                        self._wmeta.append(flatbuf.leaf_meta(leaves))
                        self._wtreedef.append(treedef)
                        self._wreplicated.append(
                            [np.shape(l) == fs for l, fs
                             in zip(leaves, full_shapes)]
                            if full_shapes is not None
                            else [True] * len(leaves))
                    else:
                        # the compiled branches unflatten with the INIT-
                        # recorded treedef/shapes/dtypes: all three must
                        # match or the program would serve garbage
                        flatbuf.check_layout(
                            leaves, treedef, self._wmeta[k],
                            self._wtreedef[k], f"reweight: stage {s.name!r}")
                rank_flats.append(flatbuf.pack_leaves(
                    leaves, wdt,
                    cast_fn=lambda a, _nm=s.name: self._to_wire(a, _nm)))
            flats.append(rank_flats)
        if tp > 1:
            rows = [f for rf in flats for f in rf]
            return flatbuf.stack_rows(rows, wdt).reshape(n, tp, -1)
        return flatbuf.stack_rows([rf[0] for rf in flats], wdt)

    def reweight(self, params) -> None:
        """Install fresh weights into the live pipeline — no recompile.

        The SPMD analogue of the chain's weights-only re-push
        (``ChainDispatcher.reweight``): the new params (same graph, same
        leaf shapes) are packed into a fresh flat buffer and placed with
        the existing sharding; the compiled chunk program is reused as-is.
        Microbatches still inside the pipe run their REMAINING stages
        under the new weights (mixed-generation execution) — call
        ``flush()`` first when a clean cut matters.
        """
        wbuf = self._pack_wbuf(params, init=False)
        if wbuf.shape != self._w.shape:
            raise ValueError(
                f"reweight: packed buffer {wbuf.shape} != deployed "
                f"{self._w.shape} (stage boundaries changed?)")
        self._w = jax.device_put(
            wbuf, NamedSharding(self.mesh, self._wspec))

    def _make_branch(self, k: int):
        stage = self.stages[k]
        meta = self._wmeta[k]
        treedef = self._wtreedef[k]
        in_sz, out_sz = self._in_sizes[k], self._out_sizes[k]
        in_shape, in_dtype = stage.in_spec.shape, stage.in_spec.dtype
        pad = self.buf_elems - out_sz
        cd = self.compute_dtype
        x_dtype = (cd if cd is not None and jnp.issubdtype(in_dtype, jnp.floating)
                   else in_dtype)

        tp = self.tensor_parallel

        def leaf_dtype(dtype):
            # under compute_dtype, float leaves cast to the compute dtype
            # (a no-op when the buffer already stores it; the per-step
            # downcast under master_weights — fused by XLA); otherwise
            # every leaf restores its exact original dtype
            if cd is not None and jnp.issubdtype(dtype, jnp.floating):
                return cd
            return dtype

        def branch(w_local, a_local):
            p = flatbuf.unpack_leaves(w_local, meta, treedef, leaf_dtype)
            b = a_local.shape[0]
            x = a_local[:, :in_sz].reshape((b,) + in_shape).astype(x_dtype)
            y = stage.fn(p, x, tp_axis=MODEL_AXIS if tp > 1 else None, tp=tp)
            y = y.reshape(b, out_sz).astype(self.buffer_dtype)
            if pad:
                y = jnp.pad(y, ((0, 0), (0, pad)))
            return y

        return branch

    def _build_chunk_fn(self):
        n = self.num_stages
        perm = [(k, (k + 1) % n) for k in range(n)]
        branches = self._branches
        has_dp = self.data_parallel > 1
        has_tp = self.tensor_parallel > 1

        int8_wire = self.wire == "int8"
        if int8_wire:
            from ..ops.quant import quantized_ring_hop
        buffer_dtype = self.buffer_dtype
        out_sz_last = self._out_sizes[-1]

        def device_chunk(w, a0, xs):
            # local shapes: w [1, (1,) Pmax], a0 [1, Blocal, L],
            # xs [T, Blocal, L]
            w_l = w[0, 0] if has_tp else w[0]
            idx = lax.axis_index(STAGE_AXIS)

            def body(a, x):
                # inject fresh input at stage 0 (the dispatcher feeding node
                # 0, reference src/dispatcher.py:90-93), compute my stage,
                # relay to successor over ICI (src/node.py:103-108)
                a = jnp.where(idx == 0, x, a)
                y = lax.switch(idx, branches, w_l, a)
                if int8_wire:
                    # quantize the hop in HBM: ICI carries ~1 byte/value
                    # (the ZFP-wire analogue, SURVEY.md §2.2)
                    y_next = quantized_ring_hop(y, STAGE_AXIS, perm,
                                                buffer_dtype)
                else:
                    y_next = lax.ppermute(y, STAGE_AXIS, perm)
                # per-step output: only the slice the dispatcher reads —
                # what stage N-1 just delivered to device 0 (reference
                # src/dispatcher.py:102-105).  Emitting the whole buffer
                # here made XLA stack [T, B, buf_elems] per device (~100 MB
                # of dead stores per ResNet50 chunk) when only device 0's
                # first out_sz_last columns are ever read.
                return y_next, lax.slice_in_dim(y_next, 0, out_sz_last, axis=1)

            a_t, outs = lax.scan(body, a0[0], xs)
            return a_t[None], outs[None]

        bspec = P(STAGE_AXIS, DATA_AXIS, None) if has_dp \
            else P(STAGE_AXIS, None, None)
        xspec = P(None, DATA_AXIS, None) if has_dp else P(None, None, None)
        ospec = P(STAGE_AXIS, None, DATA_AXIS, None) if has_dp \
            else P(STAGE_AXIS, None, None, None)

        fn = jax.shard_map(
            device_chunk, mesh=self.mesh,
            in_specs=(self._wspec, bspec, xspec),
            out_specs=(bspec, ospec),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(1,),
                       **ring_jit_kwargs(self.mesh.devices))

    # ------------------------------------------------------------------
    # streaming interface
    # ------------------------------------------------------------------

    def reset(self):
        """Empty the pipe (all stages hold bubbles)."""
        self._a = jax.device_put(
            jnp.zeros((self.num_stages, self.microbatch, self.buf_elems),
                      self.buffer_dtype), self._act_sharding)
        self._step = 0
        self._fed = 0
        self._real: collections.deque[bool] = collections.deque()
        self._emitted = 0

    def _flatten_inputs(self, xs, staged: bool = False) -> jax.Array:
        if (isinstance(xs, jax.Array) and xs.ndim == 3
                and xs.shape[1:] == (self.microbatch, self.buf_elems)
                and xs.dtype == self.buffer_dtype):
            return xs  # already staged via stage_inputs()
        if staged:
            # host block already in transfer-buffer layout (e.g. drained
            # from the native staging ring): one straight device copy.
            # Opt-in only — a mis-shaped user input that coincidentally
            # matched [C, microbatch, buf_elems] must NOT skip validation.
            xs = np.asarray(xs)
            if xs.ndim != 3 or xs.shape[1:] != (self.microbatch,
                                                self.buf_elems):
                raise ValueError(
                    f"staged block must be [C, {self.microbatch}, "
                    f"{self.buf_elems}], got {xs.shape}")
            return jax.device_put(xs.astype(self.buffer_dtype, copy=False),
                                  self._xs_sharding)
        c = xs.shape[0]
        flat = np.asarray(xs, np.float32).reshape(c, self.microbatch, -1)
        if flat.shape[-1] != self._in_sizes[0]:
            raise ValueError(
                f"input sample size {flat.shape[-1]} != stage-0 input "
                f"size {self._in_sizes[0]}")
        buf = np.zeros((c, self.microbatch, self.buf_elems), np.float32)
        buf[..., : flat.shape[-1]] = flat
        return jax.device_put(buf.astype(self.buffer_dtype),
                              self._xs_sharding)

    def stage_inputs(self, xs: np.ndarray) -> jax.Array:
        """Pre-stage a [C, microbatch, *in_shape] host block on device.

        ``push`` accepts the result directly, skipping the host flatten +
        transfer on the hot path — the analogue of the single-device
        baseline keeping its input resident (reference test/local_infer.py
        reuses one device tensor per predict call)."""
        return self._flatten_inputs(np.asarray(xs))

    def push(self, xs: np.ndarray, n_real: int | None = None, *,
             staged: bool = False, raw: bool = False):
        """Advance the pipe by ``xs.shape[0]`` steps, feeding ``xs``.

        ``xs``: [C, microbatch, *in_shape] host array, or a device block
        from ``stage_inputs``.  ``n_real`` marks how many leading entries
        are real inputs (the rest are bubble padding).  ``staged=True``
        declares a host block already in transfer-buffer layout
        ``[C, microbatch, buf_elems]`` (e.g. drained from the native
        staging ring) — the explicit opt-in for skipping per-sample size
        validation.  Returns the list of completed output microbatches
        (jax arrays of shape [microbatch, *out_shape]), in feed order.

        ``raw=True`` returns ``(slab, real_mask)`` instead: one lazy device
        array ``[n_completed, microbatch, out_size]`` of every microbatch
        that completed this chunk (bubbles included) plus a bool mask of
        which entries are real.  One device slice per chunk instead of one
        per step — the hot-path drain for benchmarks and bulk serving.
        """
        c = xs.shape[0]
        if n_real is None:
            n_real = c
        xs_dev = self._flatten_inputs(xs, staged=staged)
        t0 = time.perf_counter()
        self._a, outs = self._chunk_fn(self._w, self._a, xs_dev)
        self.metrics.chunk_calls += 1
        self.metrics.steps += c
        self._real.extend([True] * n_real + [False] * (c - n_real))
        self._fed += c

        ready = self._collect(outs, c, raw=raw)
        dt = time.perf_counter() - t0
        self.metrics.wall_s += dt
        self.metrics.push_latency.record(dt)
        tr = tracer()
        if tr.enabled:
            tr.record("spmd.push", t0, dt, {"chunk": c, "n_real": n_real})
        return ready

    def _collect(self, outs, c: int, raw: bool = False):
        """Map step outputs back to microbatch indices and drop bubbles."""
        n = self.num_stages
        out_shape = (self.microbatch,) + self.out_spec.shape
        # outs[0] is device-0's [T, B, out_sz_last] slice: what arrived at
        # "the dispatcher" each step (reference src/dispatcher.py:102-105);
        # the scan body already cropped it to the final stage's output size
        outs0 = outs[0]
        # steps j in this chunk completing a microbatch m = _step+j-(n-1)
        # with 0 <= m < _fed form one contiguous local range [j0, j1)
        j0 = max(0, (n - 1) - self._step)
        j1 = min(c, self._fed + (n - 1) - self._step)
        cnt = max(0, j1 - j0)
        if cnt:  # outputs complete strictly in feed order
            assert self._step + j0 - (n - 1) == self._emitted, \
                (self._step, j0, n, self._emitted)
        self._step += c

        if raw:
            mask = np.empty(cnt, bool)
            for i in range(cnt):
                mask[i] = self._real.popleft()
            self._emitted += cnt
            self.metrics.inferences += int(mask.sum()) * self.microbatch
            slab = outs0[j0:j1] if cnt else None  # lazy: ONE device slice
            return slab, mask

        emitted = []
        for j in range(j0, j1):
            is_real = self._real.popleft()
            self._emitted += 1
            if is_real:
                self.metrics.inferences += self.microbatch
                emitted.append(outs0[j].reshape(out_shape))
        return emitted

    def _bubble_block(self) -> jax.Array:
        """Cached device-resident all-bubble [chunk, ...] input block."""
        if self._flush_zeros is None:
            self._flush_zeros = self.stage_inputs(
                np.zeros((self.chunk, self.microbatch) + self.in_spec.shape,
                         np.float32))
        return self._flush_zeros

    def warmup(self):
        """Compile-and-run the exact full-chunk program that will serve
        traffic, on bubbles, leaving the pipe empty.

        The one probe recipe shared by ``Defer.health_check`` and the
        dispatcher's preflight — and it seeds the same cached bubble block
        ``flush`` drains with, so no extra host transfer."""
        self.reset()
        self.push(self._bubble_block(), n_real=0)
        self.reset()

    def flush(self):
        """Drain the pipe: run bubble steps until every fed microbatch has
        emerged (the fill/drain of the classic pipeline schedule).

        Always pushes full-chunk bubble blocks (cached, device-resident) so
        draining reuses the already-compiled [chunk, ...] program — a
        partial-size push would trigger a fresh XLA compile."""
        emitted = []
        target = self._fed  # overshoot bubbles beyond this are just ignored
        block = self._bubble_block()
        while self._emitted < target:
            emitted.extend(self.push(block, n_real=0))
        return emitted

    # ------------------------------------------------------------------
    # batch convenience
    # ------------------------------------------------------------------

    def run(self, inputs: np.ndarray) -> np.ndarray:
        """Feed [M, microbatch, *in_shape]; return [M, microbatch, *out]."""
        inputs = np.asarray(inputs)
        m = inputs.shape[0]
        if inputs.shape[1] != self.microbatch:
            raise ValueError(
                f"inputs microbatch dim {inputs.shape[1]} != {self.microbatch}")
        self.reset()
        outs = []
        for lo in range(0, m, self.chunk):
            hi = min(lo + self.chunk, m)
            block = inputs[lo:hi]
            n_real = hi - lo
            if n_real < self.chunk:
                pad = np.zeros((self.chunk - n_real,) + block.shape[1:],
                               block.dtype)
                block = np.concatenate([block, pad], 0)
            outs.extend(self.push(block, n_real=n_real))
        outs.extend(self.flush())
        assert len(outs) == m, (len(outs), m)
        arr = jnp.stack(outs)
        return np.asarray(jax.device_get(arr), np.float32)

    def __call__(self, inputs: np.ndarray) -> np.ndarray:
        return self.run(inputs)

    # ------------------------------------------------------------------
    # diagnostics
    # ------------------------------------------------------------------

    @property
    def hop_utilization(self) -> list[float]:
        """Fraction of the homogeneous ``buf_elems`` hop buffer each
        stage->successor boundary actually carries (hop k = stage k's
        output; the last entry is the wrap link back to "the dispatcher").
        The padded-buffer waste diagnostic: every ``ppermute`` hop and
        every ``xs`` transfer pays ``buf_elems`` regardless."""
        return list(self._footprint["hop_utilization"])

    def stage_latencies(self, params: dict[str, Any] | None = None,
                        iters: int = 10):
        """Per-stage device latency (seconds) of the *deployed* program.

        Times each stage's compiled branch — the same function the pipeline
        scan dispatches — so the numbers reflect the deployment's compute
        dtype, weight-buffer storage dtype, and (under TP) the Megatron
        sharding, not a pristine f32 re-jit.  ``params`` is accepted for
        backward compatibility but unused: the branch reads the pipeline's
        own staged weight buffer.
        """
        del params  # weights come from the deployed buffer
        lats = []
        tp = self.tensor_parallel
        tp_mesh = None
        if tp > 1:
            # submesh of the model axis: the tp devices hosting stage 0's
            # ranks (any stage's rank group is equivalent for timing)
            ax = list(self.mesh.axis_names)
            devs = self.mesh.devices
            sl = tuple(slice(None) if a == MODEL_AXIS else slice(0, 1)
                       for a in ax)
            tp_devs = devs[sl].reshape((tp,))
            tp_mesh = Mesh(tp_devs, (MODEL_AXIS,))
        for k in range(self.num_stages):
            branch = self._branches[k]
            a = jnp.zeros((self.microbatch, self.buf_elems),
                          self.buffer_dtype)
            # slice this stage's row on device — no full-buffer host
            # round-trip (the buffer is the whole model's weights)
            if tp_mesh is not None:
                w_k = jax.device_put(
                    self._w[k], NamedSharding(tp_mesh, P(MODEL_AXIS, None)))
                fn = jax.jit(jax.shard_map(
                    lambda w, a: branch(w[0], a), mesh=tp_mesh,
                    in_specs=(P(MODEL_AXIS, None), P(None, None)),
                    out_specs=P(None, None), check_vma=False))
            else:
                w_k = self._w[k]  # [Pmax]
                fn = jax.jit(branch)
            fn(w_k, a).block_until_ready()  # compile
            t0 = time.perf_counter()
            for _ in range(iters):
                y = fn(w_k, a)
            y.block_until_ready()
            lat = (time.perf_counter() - t0) / iters
            lats.append(lat)
            self.metrics.record_stage_latency(k, lat)
            tr = tracer()
            if tr.enabled:
                tr.record(f"stage{k}:{self.stages[k].name}", t0,
                          time.perf_counter() - t0,
                          {"stage": k, "mean_latency_s": lat,
                           "iters": iters})
        self.metrics.stage_latency_s = lats
        return lats
