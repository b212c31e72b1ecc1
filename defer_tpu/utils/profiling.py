"""Tracing / profiling hooks.

The reference's observability is throughput arithmetic and ad-hoc prints
(reference test/test.py:35-36, src/node.py:23); here profiling is a
first-class wrapper over ``jax.profiler`` plus a structured pipeline
breakdown that pairs with ``PipelineMetrics``.
"""

from __future__ import annotations

import contextlib
import time
from functools import partial
from typing import Any

import jax
import numpy as np


def timed_window(fn, *, min_iters=8, min_s=3.0, max_iters=512):
    """Warm call, then measure average seconds/iter over a timed window
    (the reference harness's measurement discipline, test/test.py:25-37)."""
    fn()  # warmup / compile
    t0 = time.perf_counter()
    n = 0
    while True:
        fn()
        n += 1
        dt = time.perf_counter() - t0
        if (n >= min_iters and dt >= min_s) or n >= max_iters:
            return dt / n


def amortized_forward_seconds(apply_fn, params, x0, k: int, *,
                              min_iters: int = 3, min_s: float = 2.0,
                              max_iters: int = 64) -> float:
    """Per-forward seconds with ``k`` forwards fused in ONE dispatch.

    Per-step dispatch+sync adds the host round trip to every forward
    (~0.6 ms per sync on the v5e host, chip_smoke.py's observation —
    small against a large batch, not against a small one); fusing K
    forwards into one on-device ``lax.scan`` leaves the chip's own
    time.  The per-step input perturbation ``x0 + t`` keeps
    every iteration's forward live — an invariant body would let XLA
    hoist the network out of the loop entirely and fake the number.
    """
    from jax import lax
    import jax.numpy as jnp

    from .xla_opts import jit_kwargs

    @partial(jax.jit, **jit_kwargs())
    def scan_fwd(p, x0, ts):
        def body(c, t):
            y = apply_fn(p, x0 + t)
            return c + y.astype(jnp.float32).sum(), None

        s, _ = lax.scan(body, jnp.float32(0), ts)
        return s

    if jnp.issubdtype(jnp.asarray(x0).dtype, jnp.integer):
        # integer inputs (token ids): alternate +0/+1 so ids stay valid
        # while the forward still depends on the step
        ts = (jnp.arange(k) % 2).astype(x0.dtype)
    else:
        ts = jnp.linspace(0, 1e-6, k).astype(x0.dtype)
    sec = timed_window(
        lambda: jax.block_until_ready(scan_fwd(params, x0, ts)),
        min_iters=min_iters, min_s=min_s, max_iters=max_iters)
    return sec / k


def pipeline_window_seconds(pipe, inputs, *, inflight: int = 2,
                            min_s: float = 2.5, max_chunks: int = 64):
    """Steady-state seconds per chunk with ``inflight`` chunk dispatches
    kept in flight (no per-chunk sync) and each completed chunk's result
    slab drained to the host.

    ``inputs`` must be a device block from ``pipe.stage_inputs`` — it is
    re-fed every chunk (the reference harness also re-feeds one image,
    test/test.py:20-23).  Warm-compiles with a bubble pass of the same
    resident block, so no extra chunk-sized buffer is staged."""
    import collections
    import math

    def run_window(m):
        pending = collections.deque()
        t0 = time.perf_counter()
        for _ in range(m):
            slab, _mask = pipe.push(inputs, raw=True)
            if slab is not None:
                pending.append(slab)
            while len(pending) > inflight:
                np.asarray(pending.popleft())
        while pending:
            np.asarray(pending.popleft())
        return time.perf_counter() - t0

    pipe.reset()
    slab, _ = pipe.push(inputs, n_real=0, raw=True)  # compile pass
    if slab is not None:
        np.asarray(slab)
    pipe.reset()
    run_window(2)  # post-compile warm pass
    t1 = max(run_window(1), 1e-4)
    m = max(2, min(max_chunks, math.ceil(min_s / t1)))
    # bill only the measured window to the deployment's metrics — the
    # compile/warm/calibration pushes above are harness artifacts that
    # would otherwise dominate bubble_fraction / throughput_per_s
    pipe.metrics.clear_counters()
    return run_window(m) / m


def measured_node_costs(graph, params, *, batch: int = 1,
                        compute_dtype=None, k: int = 32,
                        reps: int = 3) -> dict[str, float]:
    """Per-node measured seconds for every node of ``graph`` — the
    empirical cost map for latency-balanced partitioning
    (``graph.analysis.auto_cut_points(g, n, costs=...)``).

    Each op runs ``k`` iterations fused in ONE ``lax.scan`` dispatch
    (min over ``reps`` rounds, divided by ``k``) — per-call dispatch+sync
    timing would put the SAME host round-trip floor under every node,
    flattening the relative weights toward uniform and silently
    defeating the balancing.  Standalone per-op timing still
    ignores cross-op XLA fusion, so ABSOLUTE numbers overstate a fused
    stage; partitioning only needs the RELATIVE weights, where
    measurement beats the FLOP model for bandwidth-bound ops (pools,
    norms, elementwise) that the analytic model scores near zero.
    """
    import time as _time

    import jax
    import jax.numpy as jnp
    from jax import lax

    costs: dict[str, float] = {}
    for name in graph.topo_order:
        node = graph.nodes[name]
        in_specs = [graph.out_spec(i) for i in node.inputs]
        xs = []
        for s in in_specs:
            dt = s.dtype
            if compute_dtype is not None and jnp.issubdtype(
                    dt, jnp.floating):
                dt = compute_dtype
            xs.append(jnp.zeros((batch,) + s.shape, dt))
        p = params.get(name)
        if compute_dtype is not None and p is not None:
            p = jax.tree.map(
                lambda a: a.astype(compute_dtype)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, p)

        def scan_op(pp, xx, ts, _op=node.op):
            # perturb the first input per step so the op stays live in
            # the loop (an invariant body would be hoisted out entirely)
            def body(c, t):
                if jnp.issubdtype(xx[0].dtype, jnp.floating):
                    x0 = xx[0] + (t * 1e-7).astype(xx[0].dtype)
                else:  # int ids: alternate +0/+1, stays a valid index set
                    x0 = xx[0] + (t.astype(jnp.int32) % 2).astype(
                        xx[0].dtype)
                y = _op.apply(pp, x0, *xx[1:])
                return c + y.astype(jnp.float32).sum(), None

            s, _ = lax.scan(body, jnp.float32(0), ts)
            return s

        fn = jax.jit(scan_op)
        ts = jnp.arange(k, dtype=jnp.float32)
        jax.block_until_ready(fn(p, xs, ts))  # compile + warm
        best = float("inf")
        for _ in range(reps):
            t0 = _time.perf_counter()
            jax.block_until_ready(fn(p, xs, ts))
            best = min(best, _time.perf_counter() - t0)
        costs[name] = best / k
    return costs


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture an XLA/TPU profiler trace (view with tensorboard/xprof)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def profile_pipeline(pipe, params: dict[str, Any], *, iters: int = 20,
                     warmup: int = 2) -> dict:
    """Structured breakdown of a pipeline deployment.

    Returns per-stage compute latency, the steady-state step time of the
    fused pipeline program, the implied stage-imbalance factor (max stage /
    mean stage — the pipeline's efficiency ceiling), and transfer-buffer
    footprint.
    """
    lat = pipe.stage_latencies(params, iters=iters)
    inputs = np.zeros((pipe.chunk, pipe.microbatch) + pipe.in_spec.shape,
                      np.float32)
    pipe.reset()
    for _ in range(warmup):
        pipe.push(inputs, n_real=0)
    jax.block_until_ready(pipe._a)  # don't bill queued warmup work to t0
    t0 = time.perf_counter()
    pipe.push(inputs, n_real=0)
    jax.block_until_ready(pipe._a)
    step_s = (time.perf_counter() - t0) / pipe.chunk
    mean_lat = sum(lat) / len(lat)
    return {
        "num_stages": pipe.num_stages,
        "stage_latency_ms": [round(s * 1e3, 4) for s in lat],
        "stage_imbalance": round(max(lat) / mean_lat, 3) if mean_lat else 0.0,
        "pipeline_step_ms": round(step_s * 1e3, 4),
        "step_overhead_vs_max_stage": round(step_s / max(lat), 3)
        if max(lat) > 0 else 0.0,
        "buffer_bytes_per_hop": pipe.metrics.buffer_bytes_per_hop,
        "steady_state_throughput_per_s": round(
            pipe.microbatch / step_s, 2) if step_s else 0.0,
    }
