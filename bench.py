"""Headline benchmark: ResNet50 inference on TPU — throughput, latency, MFU.

Mirrors the reference's measurement protocol — timed-window throughput of
batch-1 streaming inference (reference test/test.py:25-37) against a
single-device predict loop (reference test/local_infer.py:16-23) — and adds
what the reference never measured: a batch sweep, amortized-dispatch
numbers, and model FLOPs utilisation (graph FLOPs / step time / chip peak).

Measurement design.  Each side is reported two ways:

  * single-chip ``stepwise``: dispatch + block per step (reference
    local_infer protocol, kept for parity/continuity), and
    ``scan``: K forwards fused in one on-device ``lax.scan`` dispatch —
    the chip's best single-program throughput.  The baseline
    (``vs_baseline`` denominator) is the best scan number across batch
    sizes, not the weak batch-1 stepwise number.
  * pipeline: swept over (chunk, microbatch) with >=2 chunks in flight
    (no per-chunk sync) and whole-chunk result slabs drained to host
    (``SpmdPipeline.push(raw=True)``).

Both sides keep their input device-resident, mirroring the reference
harness re-feeding one image (test/test.py:20-23).

Device handling: ``jax.devices()`` in this process is the probe.  With no
TPU the bench exits non-zero and prints no ``value`` — there is no CPU
path, no fallback record and no retry ladder.  A TPU whose
``device_kind`` is not in ``defer_tpu/utils/hw.py`` is an error.

Prints exactly one JSON line on stdout:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., extras}
"""

import argparse
import json
import os
import sys
import time

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def init_devices():
    """``jax.devices()``, or exit 3 when they are not TPUs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"bench: needs a TPU; jax found {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind}). "
            f"No value printed.")
        sys.exit(3)
    return devices


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--weights", default=None,
                    help="path to a pretrained ResNet50 checkpoint "
                         "(npz/safetensors; see defer_tpu.utils.pretrained)")
    ap.add_argument("--batches", default="1,32,128,256",
                    help="baseline batch sweep sizes (TPU only)")
    # default sweep covers the best-known configs (r5 winner: chunk=128
    # mb=32 at 11,032 img/s, BENCH_r05_builder.json; r4's default 2x2
    # corners missed the then-winner, under-reporting the pipeline)
    # while every combination stays under the mem_cap guard
    ap.add_argument("--chunks", default="32,128",
                    help="pipeline chunk sweep (steps fused per dispatch)")
    ap.add_argument("--microbatches", default="16,32",
                    help="pipeline microbatch sweep")
    ap.add_argument("--quick", action="store_true",
                    help="small sweep: batches 1,32; one pipeline config")
    ap.add_argument("--fold-bn", action="store_true",
                    help="fold BatchNorm into convs before deployment "
                         "(graph/optimize.py); exact at f32")
    args = ap.parse_args()

    devices = init_devices()

    import jax
    import jax.numpy as jnp

    from defer_tpu import SpmdPipeline, partition, pipeline_mesh
    from defer_tpu.graph.analysis import total_flops
    from defer_tpu.models import resnet50, RESNET50_8STAGE_CUTS
    from defer_tpu.utils.hw import detect_chip, peak_flops

    n = len(devices)
    platform = devices[0].platform
    gen = detect_chip(devices[0])  # an unknown TPU kind raises
    peak = peak_flops(gen)
    log(f"bench: {n} x {platform} device(s), {gen} "
        f"({peak / 1e12:.0f} bf16 TFLOP/s peak)")

    graph = resnet50()
    in_shape = (224, 224, 3)
    compute_dtype = jnp.bfloat16
    # batch 1 first (the stepwise reference-protocol denominator),
    # then LARGEST first: if the measurement deadline truncates the
    # sweep, the honest scan baseline (big batches) is already in
    batches = [1] + sorted({int(b) for b in args.batches.split(",")}
                           - {1}, reverse=True)
    chunks = [int(c) for c in args.chunks.split(",")]
    mbs = [int(m) for m in args.microbatches.split(",")]
    if args.quick:
        batches, chunks, mbs = [1, 32], [128], [8]

    if args.weights:
        from defer_tpu.utils.pretrained import load_pretrained_resnet50
        params = load_pretrained_resnet50(args.weights, graph)
        log(f"bench: loaded pretrained weights from {args.weights}")
    else:
        params = graph.init(jax.random.key(0))
    if args.fold_bn:
        from defer_tpu import fold_batchnorm
        graph, params, n_folded = fold_batchnorm(graph, params)
        log(f"bench: folded {n_folded} BatchNorm ops into convs")
    # per-sample FLOPs (2*MAC convention) of the graph as DEPLOYED — after
    # any folding, so MFU is scored against the work actually executed
    flops_img = float(total_flops(graph))
    log(f"bench: model FLOPs/img = {flops_img / 1e9:.2f} G")

    # ---- single-chip baseline + batch sweep (test/local_infer.py protocol)
    from defer_tpu.utils.xla_opts import compiler_options, jit_kwargs
    if compiler_options():
        log(f"bench: compiler_options = {compiler_options()}")
    fwd = jax.jit(lambda p, x: graph.apply(p, x), **jit_kwargs())
    # fold_batchnorm and the pretrained loaders return HOST numpy params;
    # device-commit the BASELINE copy once, or every single-chip fwd()
    # call re-uploads ~100 MB of host weights.  `params` itself stays
    # host-side: the pipeline packers np.asarray it.  jnp.asarray casts
    # on device for jax.Arrays and uploads-with-cast for host numpy — no
    # gratuitous D2H either way
    params_c = jax.tree.map(
        lambda a: jnp.asarray(a, dtype=compute_dtype), params)
    x_dtype = compute_dtype

    def mfu(ips):
        return round(flops_img * ips / peak, 4)

    from defer_tpu.utils.profiling import (amortized_forward_seconds,
                                           pipeline_window_seconds,
                                           timed_window)

    def scan_step_seconds(b, k):
        """Per-forward seconds with K forwards fused in ONE dispatch."""
        x0 = jnp.zeros((b,) + in_shape, x_dtype)
        return amortized_forward_seconds(graph.apply, params_c, x0, k)

    # total-measurement deadline: a cold compile cache can stretch the
    # full sweep past the caller's capture window — past the deadline,
    # remaining sweep items are skipped and the JSON line is emitted
    # with what was measured (ordering above puts the headline configs
    # first)
    bench_deadline = time.monotonic() + float(
        os.environ.get("DEFER_BENCH_DEADLINE_S", "1500"))
    truncated = []

    def past_deadline(what: str) -> bool:
        if time.monotonic() < bench_deadline:
            return False
        if what not in truncated:
            truncated.append(what)
            log(f"bench: measurement deadline reached; skipping "
                f"remaining {what}")
        return True

    sweep = {}
    single_best_ips = 0.0
    for b in batches:
        # truncation is only legal once BOTH the batch-1 stepwise
        # denominator AND the largest-batch scan baseline are in —
        # otherwise vs_baseline would divide by a weak denominator
        if len(sweep) >= 2 and past_deadline("batch sweep"):
            break
        xb = jnp.zeros((b,) + in_shape, x_dtype)
        sec = timed_window(lambda: jax.block_until_ready(fwd(params_c, xb)))
        k = 64 if b <= 8 else (32 if b <= 64 else 16)
        scan_sec = scan_step_seconds(b, k)
        entry = {
            "img_per_s": round(b / sec, 2),
            "ms_per_img": round(1e3 * sec / b, 4),
            "ms_per_step": round(1e3 * sec, 4),
            "scan_img_per_s": round(b / scan_sec, 2),
            "scan_ms_per_step": round(1e3 * scan_sec, 4),
        }
        entry["mfu"] = mfu(b / sec)
        entry["scan_mfu"] = mfu(b / scan_sec)
        sweep[b] = entry
        single_best_ips = max(single_best_ips, b / scan_sec)
        log(f"single-chip batch {b}: stepwise {b / sec:.2f} img/s "
            f"({1e3 * sec:.2f} ms/step) | scan x{k} "
            f"{b / scan_sec:.2f} img/s ({1e3 * scan_sec:.3f} ms/step"
            f", MFU {entry['scan_mfu']:.1%})")
    single_stepwise_b1 = sweep[batches[0]]["img_per_s"]

    # ---- pipelined inference over all devices (test/test.py protocol)
    num_stages = n
    if num_stages == 8:
        stages = partition(graph, RESNET50_8STAGE_CUTS)
    else:
        stages = partition(graph, num_stages=num_stages)
    from defer_tpu.partition.stage import buffer_footprint
    buffer_dtype = jnp.bfloat16
    buf_elems = buffer_footprint(stages)["buf_elems"]
    mem_cap = 2.5e9  # device bytes allowed for the resident input block

    def bench_pipe(chunk, mb, wire="buffer"):
        """(pipe, img_per_s, sec_per_chunk) with >=2 chunks in flight."""
        pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(num_stages),
                            microbatch=mb, chunk=chunk,
                            buffer_dtype=buffer_dtype,
                            compute_dtype=compute_dtype, wire=wire)
        inputs = pipe.stage_inputs(
            np.zeros((chunk, mb) + in_shape, np.float32))
        # >=2 chunks in flight, whole-chunk result drains, bubble-free
        # warm-compile (warmup() would cache a SECOND chunk-sized block,
        # doubling the footprint the mem_cap guard accounts for)
        sec = pipeline_window_seconds(pipe, inputs)
        return pipe, chunk * mb / sec, sec

    pipe_sweep = {}
    best = None  # (ips, chunk, mb, pipe)
    # largest in-flight block first: the best-known config (c128/mb32)
    # lands before a deadline truncation can cut the grid short
    for chunk, mb in sorted(((c, m) for c in chunks for m in mbs),
                            key=lambda cm: -(cm[0] * cm[1])):
        if best is not None and past_deadline("pipeline sweep"):
            break
        need = chunk * mb * buf_elems * jnp.dtype(buffer_dtype).itemsize
        if need > mem_cap:
            log(f"pipeline chunk={chunk} mb={mb}: SKIPPED "
                f"(resident input block {need / 1e9:.1f} GB > cap)")
            pipe_sweep[f"c{chunk}_m{mb}"] = {"skipped": "memory"}
            continue
        pipe, ips, sec = bench_pipe(chunk, mb)
        entry = {"img_per_s": round(ips, 2),
                 "ms_per_chunk": round(sec * 1e3, 2),
                 "ms_per_step": round(sec * 1e3 / chunk, 4)}
        entry["mfu"] = mfu(ips)
        pipe_sweep[f"c{chunk}_m{mb}"] = entry
        log(f"pipeline chunk={chunk} mb={mb}: {ips:.2f} img/s"
            f" (MFU {entry['mfu']:.1%})")
        if best is None or ips > best[0]:
            best = (ips, chunk, mb, pipe)
    if best is None:
        # every swept config hit the memory cap: clamp the smallest one
        # DOWN to the cap (never run over it) so the bench always emits
        # its JSON line without risking the OOM the cap guards against
        mb = min(mbs)
        itemsize = jnp.dtype(buffer_dtype).itemsize
        chunk = max(2, int(mem_cap // (mb * buf_elems * itemsize)))
        log(f"pipeline: all configs over mem cap; clamped to chunk={chunk} "
            f"mb={mb}")
        pipe, ips, _sec = bench_pipe(chunk, mb)
        pipe_sweep[f"c{chunk}_m{mb}"] = {"img_per_s": round(ips, 2),
                                         "forced": True}
        best = (ips, chunk, mb, pipe)
    pipe_ips, best_chunk, best_mb, pipe = best

    # per-stage latency -> duty cycle / bubble metrics on the best config
    pipe.stage_latencies(iters=3)
    deploy_metrics = pipe.metrics.as_dict()

    # ---- int8 wire (the device-side ZFP analogue) on the best config
    int8_row = None
    if not past_deadline("int8 wire diagnostics"):
        qpipe, q_ips, _ = bench_pipe(best_chunk, best_mb, wire="int8")
        del qpipe  # throughput only; accuracy below on small pipes
        # accuracy: int8 wire vs the bf16 buffer wire actually deployed
        # above AND vs an exact f32 single-program forward, on small
        # dedicated pipes (the big config's run()/flush() would stage
        # another chunk-sized bubble block on device)
        acc = {}
        x_acc = np.random.default_rng(0).standard_normal(
            (4, 1) + in_shape).astype(np.float32)
        y_ref = np.stack([np.asarray(
            fwd(params, jnp.asarray(x)), np.float32) for x in x_acc])
        for w in ("buffer", "int8"):
            p_small = SpmdPipeline(
                stages, params, mesh=pipeline_mesh(num_stages),
                microbatch=1, chunk=4, buffer_dtype=buffer_dtype,
                compute_dtype=compute_dtype, wire=w)
            acc[w] = p_small.run(x_acc)
            del p_small
        denom = max(float(np.abs(y_ref).max()), 1e-6)
        # task-level quality: does the wire change the *decision*?  (a
        # raw logit delta alone can't say whether the quantization
        # matters — top-1/top-5 agreement can)
        ref_top1 = np.argmax(y_ref.reshape(-1, y_ref.shape[-1]), -1)
        ref_top5 = np.argsort(
            y_ref.reshape(-1, y_ref.shape[-1]), -1)[:, -5:]

        def agree(logits):
            flat = np.asarray(logits).reshape(-1, y_ref.shape[-1])
            t1 = float((np.argmax(flat, -1) == ref_top1).mean())
            t5 = float(np.mean([t in row for t, row in
                                zip(np.argmax(flat, -1), ref_top5)]))
            return t1, t5

        q_t1, q_t5 = agree(acc["int8"])
        b_t1, b_t5 = agree(acc["buffer"])
        int8_row = {
            "img_per_s": round(q_ips, 2),
            "mfu": mfu(q_ips),
            "vs_buffer_wire": round(q_ips / pipe_ips, 4),
            # buffer wire is bf16 on TPU — both deltas are vs the exact
            # f32 single-program logits so they are comparable
            "max_abs_logit_err_vs_f32": round(
                float(np.abs(acc["int8"] - y_ref).max()), 5),
            "bf16_buffer_max_abs_logit_err_vs_f32": round(
                float(np.abs(acc["buffer"] - y_ref).max()), 5),
            "rel_logit_err": round(
                float(np.abs(acc["int8"] - y_ref).max()) / denom, 5),
            "top1_agreement_vs_f32": round(q_t1, 4),
            "top1_in_ref_top5": round(q_t5, 4),
            "bf16_buffer_top1_agreement_vs_f32": round(b_t1, 4),
            "bf16_buffer_top1_in_ref_top5": round(b_t5, 4),
        }
        log(f"pipeline int8 wire: {q_ips:.2f} img/s "
            f"({int8_row['vs_buffer_wire']:.2f}x buffer wire), "
            f"rel logit err {int8_row['rel_logit_err']:.4f} "
            f"(bf16 wire err "
            f"{int8_row['bf16_buffer_max_abs_logit_err_vs_f32']})")

    # ---- padded-buffer waste: what each hop actually carries vs buf_elems
    buffer_util = [round(u, 4) for u in pipe.hop_utilization]

    result = {
        "metric": f"resnet50_{num_stages}stage_pipeline_throughput",
        "value": round(pipe_ips, 3),
        "unit": "inferences/sec",
        # baseline: the chip's best single-program throughput (scan-
        # amortized, best batch), not the weak batch-1 stepwise number
        "vs_baseline": round(pipe_ips / single_best_ips, 4),
        "vs_stepwise_batch1": round(pipe_ips / single_stepwise_b1, 4),
        "single_chip_best_img_per_s": round(single_best_ips, 2),
        "platform": platform,
        "device_kind": str(getattr(devices[0], "device_kind", "")),
        "tpu_generation": gen,
        "n_devices": n,
        "compute_dtype": "bfloat16",
        "flops_per_img": flops_img,
        "batch_sweep": {str(k): v for k, v in sweep.items()},
        "pipeline_sweep": pipe_sweep,
        "pipeline_best": {"chunk": best_chunk, "microbatch": best_mb,
                          "img_per_s": round(pipe_ips, 2)},
        "deadline_truncated": truncated or None,
        "deploy_metrics": deploy_metrics,
        "buffer_utilization_per_hop": buffer_util,
        "buffer_elems": pipe.buf_elems,
    }
    if int8_row is not None:
        result["int8_wire"] = int8_row
    result["mfu_pipeline_best"] = mfu(pipe_ips)
    result["mfu_best"] = max(
        [mfu(pipe_ips), mfu(single_best_ips)]
        + [v["scan_mfu"] for v in sweep.values()])
    # telemetry registry snapshot (per-pipeline push/stage latency
    # percentiles, per-hop byte counters) — the bench trajectory's
    # distribution record, not just the window averages above
    from defer_tpu.obs import REGISTRY
    result["metrics_registry"] = REGISTRY.snapshot()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
