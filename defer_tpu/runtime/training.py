"""Pipeline-parallel training over the SPMD inference engine.

The reference is inference-only (SURVEY.md §5: nothing to checkpoint,
weights shipped once — reference src/dispatcher.py:57).  This module goes
beyond parity: the same ``shard_map`` + ``lax.switch`` + ``lax.ppermute``
+ ``lax.scan`` chunk program the inference pipeline runs is simply
*differentiated* — JAX transposes the ``ppermute`` ring into the reverse
ring for the backward pass, so one ``jax.value_and_grad`` yields GPipe-style
pipeline-parallel training with zero bespoke backward scheduling:

  * forward: microbatch t enters stage 0 at step t; stage k computes
    microbatch t-k; losses accrue on device 0 as completed microbatches
    arrive (steps n-1 .. n-1+M-1);
  * backward: the transposed scan runs the ring in reverse — exactly the
    1F1B wavefront, scheduled by XLA rather than by hand;
  * weights and their gradients live in the SAME [N, Pmax] stage-sharded
    flat buffer the inference engine uses, so any elementwise optax
    optimizer applies shard-local with no resharding.

Memory: the scan body is wrapped in ``jax.checkpoint`` so the backward
rematerializes each step's stage compute instead of storing every
intermediate — the standard TPU trade of FLOPs for HBM.
"""

from __future__ import annotations

from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, STAGE_AXIS
from .spmd import SpmdPipeline


class PipelineTrainer:
    """Train a model through an :class:`SpmdPipeline` deployment.

    ``loss_fn(logits, targets) -> scalar`` is applied per microbatch (it
    sees ``[microbatch/dp, *out_shape]`` logits per data-parallel shard),
    SUMMED over the chunk's completed microbatches, and AVERAGED across
    dp shards — so a mean-over-batch loss keeps per-sample scaling
    regardless of the dp factor.  ``optimizer`` is any optax-style
    gradient transformation; it runs directly on the stage-sharded flat
    weight buffer in one jitted fused update.

    Supports pp, pp x dp, and pp x tp meshes (the Megatron in-stage psums
    transpose correctly under autodiff).  ``wire="int8"`` pipelines train
    with a straight-through estimator on the quantized hop: the forward
    is exactly the deployment's quantized wire, the backward treats
    dequant∘quant as identity (cotangents still ride the reverse ring).
    """

    def __init__(self, pipe: SpmdPipeline, loss_fn: Callable,
                 optimizer=None):
        self.pipe = pipe
        self.loss_fn = loss_fn
        if optimizer is None:
            import optax
            optimizer = optax.sgd(1e-2)
        self.optimizer = optimizer
        #: compiled value_and_grad programs, keyed by the targets' rank
        #: (the target sharding spec must match ys's rank)
        self._loss_grad_cache: dict[int, Any] = {}
        self.opt_state = None  # lazily init'd on device from pipe._w
        self._fix_tp_grads = None
        if pipe.tensor_parallel > 1:
            # tied-copy gradient correction: a REPLICATED leaf exists once
            # per tp rank in the weight buffer, and value_and_grad hands
            # each copy only its own rank's partial (scaled 1/tp by the
            # loss pmean) — the correct tied-weight gradient is the SUM of
            # the copies' grads.  Sharded leaves are rank-owned: untouched.
            n, pmax = pipe._w.shape[0], pipe._w.shape[-1]
            rep = np.zeros((n, 1, pmax), bool)
            for k, (meta, flags) in enumerate(zip(pipe._wmeta,
                                                  pipe._wreplicated)):
                for (off, size, _shape, _dt), is_rep in zip(meta, flags):
                    if is_rep:
                        rep[k, 0, off: off + size] = True
            rep = jnp.asarray(rep)

            @jax.jit
            def fix(g):
                return jnp.where(rep, g.sum(axis=1, keepdims=True), g)

            self._fix_tp_grads = fix
        self._a0 = None        # cached sharded all-zeros activation block
        # one fused program per optimizer step instead of eager per-op
        # dispatches over the full weight buffer
        import optax

        @jax.jit
        def _apply(grads, opt_state, w):
            updates, opt_state = self.optimizer.update(grads, opt_state, w)
            return optax.apply_updates(w, updates), opt_state

        self._apply_updates = _apply

    # -- program construction ---------------------------------------------

    def _loss_grad(self, ys_ndim: int):
        if ys_ndim not in self._loss_grad_cache:
            self._loss_grad_cache[ys_ndim] = self._build_loss_grad(ys_ndim)
        return self._loss_grad_cache[ys_ndim]

    def _build_loss_grad(self, ys_ndim: int):
        pipe = self.pipe
        n = pipe.num_stages
        perm = [(k, (k + 1) % n) for k in range(n)]
        branches = pipe._branches
        has_dp = pipe.data_parallel > 1
        out_sz = pipe._out_sizes[-1]
        out_shape = pipe.out_spec.shape
        mb_local = pipe.microbatch // pipe.data_parallel
        loss_fn = self.loss_fn

        has_tp = pipe.tensor_parallel > 1

        if pipe.wire == "int8":
            # quantized hop with a straight-through estimator: forward
            # block-quantizes exactly like inference (the deployment being
            # trained IS the deployment that serves), backward treats
            # dequant∘quant as identity while still transposing the ring
            from ..ops.quant import quantized_ring_hop
            inv_perm = [(k, (k - 1) % n) for k in range(n)]
            buffer_dtype = pipe.buffer_dtype

            @jax.custom_vjp
            def hop(y):
                return quantized_ring_hop(y, STAGE_AXIS, perm,
                                          buffer_dtype)

            def _hop_fwd(y):
                return hop(y), None

            def _hop_bwd(_, g):
                return (lax.ppermute(g, STAGE_AXIS, inv_perm),)

            hop.defvjp(_hop_fwd, _hop_bwd)
        else:
            def hop(y):
                return lax.ppermute(y, STAGE_AXIS, perm)

        def device_chunk(w, a0, xs, ys, mask):
            # local: w [1, (1,) Pmax], a0 [1, B, L], xs [T, B, L],
            # ys [T, B, *target], mask [T].  Under tp each model rank runs
            # its own stage ring on its weight shard; in-stage psums make
            # activations (and hence the loss) replicated across ranks,
            # and their transposes route each rank's shard gradient — so
            # the same differentiation covers pp x tp x dp.
            w_l = w[0, 0] if has_tp else w[0]
            idx = lax.axis_index(STAGE_AXIS)

            @jax.checkpoint
            def body(a, xym):
                x, y, m = xym
                a = jnp.where(idx == 0, x, a)
                yhat = lax.switch(idx, branches, w_l, a)
                y_next = hop(yhat)
                # what arrived back at "the dispatcher" this step: a
                # completed microbatch (only device 0's copy is real).
                # Bubble steps are masked with where, not multiply: a
                # loss_fn that is non-finite on the zero padding must not
                # poison the chunk (nan * 0 == nan)
                out = lax.slice_in_dim(y_next, 0, out_sz, axis=1)
                step_loss = jnp.where(
                    m > 0,
                    loss_fn(out.reshape((mb_local,) + out_shape), y), 0.0)
                return y_next, step_loss

            a_init = a0[0]
            if has_tp:
                # the tp-rank rings produce replicated values the VMA
                # system types as model-varying; match the carry type
                a_init = lax.pcast(a_init, (MODEL_AXIS,), to="varying")
            _a_t, losses = lax.scan(body, a_init, (xs, ys, mask))
            total = jnp.where(idx == 0, losses.sum(), 0.0)
            # replicate the scalar so every shard returns the same loss;
            # pmean over dp so a mean-over-batch loss_fn keeps per-sample
            # scaling regardless of the dp factor (moving to a wider dp
            # mesh must not silently scale the effective learning rate)
            total = lax.psum(total, STAGE_AXIS)
            if has_dp:
                total = lax.pmean(total, DATA_AXIS)
            if has_tp:
                # numerically identity (ranks hold the same loss); types
                # the scalar back to model-invariant for out_specs P()
                total = lax.pmean(total, MODEL_AXIS)
            return total

        bspec = P(STAGE_AXIS, DATA_AXIS, None) if has_dp \
            else P(STAGE_AXIS, None, None)
        xspec = P(None, DATA_AXIS, None) if has_dp else P(None, None, None)
        # ys is [T, microbatch, *target...]: shard the microbatch axis
        # under dp, replicate everything else, matched to ys's rank
        yspec = P(None, DATA_AXIS if has_dp else None,
                  *([None] * (ys_ndim - 2)))
        # NOTE check_vma=True (unlike the inference engine): replication
        # tracking is what makes the TRANSPOSE of the in-stage Megatron
        # psums correct — with it off, a replicated cotangent re-enters
        # psum and every tp-rank gradient double-counts
        fn = jax.shard_map(
            device_chunk, mesh=pipe.mesh,
            in_specs=(pipe._wspec, bspec, xspec, yspec, P(None)),
            out_specs=P(),
            check_vma=True,
        )
        return jax.jit(jax.value_and_grad(fn))

    # -- stepping ----------------------------------------------------------

    def _schedule(self, xs: np.ndarray, ys: np.ndarray):
        """Lay out one self-contained chunk: M real inputs then n-1 bubble
        steps so every microbatch's loss lands inside the chunk."""
        pipe = self.pipe
        n = pipe.num_stages
        m = xs.shape[0]
        t = m + n - 1
        xs_full = np.zeros((t,) + xs.shape[1:], np.float32)
        xs_full[:m] = xs
        xs_dev = pipe._flatten_inputs(xs_full)
        ys_full = np.zeros((t,) + ys.shape[1:], ys.dtype)
        ys_full[n - 1: n - 1 + m] = ys  # target for mb j at step j+n-1
        mask = np.zeros((t,), np.float32)
        mask[n - 1: n - 1 + m] = 1.0
        return xs_dev, jnp.asarray(ys_full), jnp.asarray(mask)

    def loss_and_grad(self, xs: np.ndarray, ys: np.ndarray):
        """Summed loss + weight-buffer gradient for one chunk.

        ``xs``: [M, microbatch, *in_shape]; ``ys``: [M, microbatch, ...]
        targets (whatever ``loss_fn`` consumes).
        """
        pipe = self.pipe
        xs_dev, ys_dev, mask = self._schedule(np.asarray(xs),
                                              np.asarray(ys))
        if self._a0 is None:
            self._a0 = jax.device_put(
                jnp.zeros((pipe.num_stages, pipe.microbatch,
                           pipe.buf_elems), pipe.buffer_dtype),
                pipe._act_sharding)
        loss, grads = self._loss_grad(ys_dev.ndim)(pipe._w, self._a0,
                                                   xs_dev, ys_dev, mask)
        if self._fix_tp_grads is not None:
            grads = self._fix_tp_grads(grads)
        return loss, grads

    def step(self, xs: np.ndarray, ys: np.ndarray) -> float:
        """One optimizer step over a chunk; returns the summed loss."""
        loss, grads = self.loss_and_grad(xs, ys)
        if self.opt_state is None:
            self.opt_state = self.optimizer.init(self.pipe._w)
        self.pipe._w, self.opt_state = self._apply_updates(
            grads, self.opt_state, self.pipe._w)
        return float(loss)

    def accumulate_step(self, batches) -> float:
        """One optimizer step over SEVERAL chunks (gradient accumulation).

        ``batches`` iterates ``(xs, ys)`` chunk pairs; gradients stay in
        the stage-sharded buffer layout and sum on device (one lazy add
        per chunk, no host round trips), then a single optimizer update
        applies.  The effective batch is the sum of the chunks' — the
        standard recipe when the target batch exceeds what one chunk's
        in-flight window should hold.  Returns the summed loss.
        """
        total_loss = None  # device scalar until the end: no per-chunk sync
        acc = None
        for xs, ys in batches:
            loss, grads = self.loss_and_grad(xs, ys)
            total_loss = loss if total_loss is None else total_loss + loss
            acc = grads if acc is None else jax.tree.map(
                jnp.add, acc, grads)
        if acc is None:
            raise ValueError("accumulate_step needs at least one batch")
        if self.opt_state is None:
            self.opt_state = self.optimizer.init(self.pipe._w)
        self.pipe._w, self.opt_state = self._apply_updates(
            acc, self.opt_state, self.pipe._w)
        return float(total_loss)

    # -- interop ------------------------------------------------------------

    def trained_params(self) -> dict[str, Any]:
        """The deployment's CURRENT weights as a standard graph parameter
        pytree (the inverse of the buffer staging) — restore-anywhere
        interop with ``utils.checkpoint`` / fresh deployments.  Leaves
        come back in their original dtypes.  Under tensor parallelism the
        per-rank shards are reassembled op-by-op (``Op.tp_unshard``, the
        inverse of the Megatron column/row splits)."""
        pipe = self.pipe
        tp = pipe.tensor_parallel
        w = np.asarray(pipe._w)
        params: dict[str, Any] = {}
        for k, s in enumerate(pipe.stages):
            def unpack(row):
                return [row[off: off + size].reshape(shape).astype(dtype)
                        for off, size, shape, dtype in pipe._wmeta[k]]
            if tp > 1:
                rank_params = [
                    jax.tree.unflatten(pipe._wtreedef[k], unpack(w[k, r]))
                    for r in range(tp)]
                params.update(s.tp_unshard_params(rank_params))
            else:
                params.update(jax.tree.unflatten(pipe._wtreedef[k],
                                                 unpack(w[k])))
        return params

    def save_checkpoint(self, path: str):
        """Persist the training state (weight buffer + optimizer state)."""
        from ..utils.checkpoint import save_params
        if self.opt_state is None:
            # pre-first-step save must still restore: write the same
            # opt/s* keys load_checkpoint's template will demand
            self.opt_state = self.optimizer.init(self.pipe._w)
        flat, _ = jax.tree.flatten(self.opt_state)
        save_params(path, {
            "w": {"buffer": np.asarray(self.pipe._w)},
            "opt": {f"s{i}": np.asarray(l) for i, l in enumerate(flat)},
        })

    def load_checkpoint(self, path: str):
        """Restore training state saved by :meth:`save_checkpoint` into
        this deployment (same partition/mesh/optimizer)."""
        from ..utils.checkpoint import load_params
        pipe = self.pipe
        if self.opt_state is None:
            self.opt_state = self.optimizer.init(pipe._w)
        flat, treedef = jax.tree.flatten(self.opt_state)
        tpl = {"w": {"buffer": np.zeros(pipe._w.shape, pipe._w.dtype)},
               "opt": {f"s{i}": np.zeros(np.shape(l), np.asarray(l).dtype)
                       for i, l in enumerate(flat)}}
        state = load_params(path, tpl)
        sharding = NamedSharding(pipe.mesh, pipe._wspec)
        pipe._w = jax.device_put(state["w"]["buffer"], sharding)
        restored = []
        for i, l in enumerate(flat):
            arr = state["opt"][f"s{i}"]
            restored.append(
                jax.device_put(arr, sharding) if np.shape(arr) == pipe._w.shape
                else jnp.asarray(arr))
        self.opt_state = jax.tree.unflatten(treedef, restored)

    def stage_grads(self, grads) -> list[dict[str, Any]]:
        """Unflatten a weight-buffer gradient back into per-stage pytrees
        (host side; for inspection/tests/checkpointing).  Under tp the
        buffer holds per-rank shards whose reassembly is op-specific;
        inspect the raw [N, tp, Pmax] gradient directly instead."""
        pipe = self.pipe
        if pipe.tensor_parallel > 1:
            raise NotImplementedError(
                "stage_grads reassembly under tensor parallelism; "
                "read the sharded gradient buffer directly")
        out = []
        g = np.asarray(grads)
        for k, meta in enumerate(pipe._wmeta):
            leaves = [g[k, off: off + size].reshape(shape).astype(np.float32)
                      for off, size, shape, _dtype in meta]
            out.append(jax.tree.unflatten(pipe._wtreedef[k], leaves))
        return out
