"""Benchmark suite over the five BASELINE.md configs.

Reproduces the reference's measurement protocol per config — timed-window
streaming throughput of the pipelined deployment (reference test/test.py:
25-37) against a single-device predict loop (reference test/local_infer.py:
16-23) — and adds the per-stage metrics the reference never had: stage
latency, duty cycle (energy analogue), bubble fraction.

One JSON line per config on stdout; human detail on stderr.

Usage:
  python benchmarks/run.py                  # all configs, device-appropriate
  python benchmarks/run.py --configs resnet50_8,bert_base_12
  python benchmarks/run.py --tiny           # force tiny models (CPU smoke)
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/", 2)[0])

from defer_tpu import SpmdPipeline, partition, pipeline_mesh  # noqa: E402
from defer_tpu import models  # noqa: E402
from defer_tpu.utils.profiling import (amortized_forward_seconds,  # noqa: E402
                                       pipeline_window_seconds, timed_window)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


#: name -> (full_model_fn, full_cuts, full_in_shape, full_dtype,
#:          tiny_model_fn, tiny_stages, tiny_in_shape, tiny_dtype)
CONFIGS = {
    "resnet50_8": (
        models.resnet50, models.RESNET50_8STAGE_CUTS, (224, 224, 3), "f",
        models.resnet_tiny, 8, (32, 32, 3), "f"),
    "vgg19_4": (
        models.vgg19, models.VGG19_4STAGE_CUTS, (224, 224, 3), "f",
        models.vgg_tiny, 4, (32, 32, 3), "f"),
    "inceptionv3_6": (
        models.inception_v3, models.INCEPTION_6STAGE_CUTS, (299, 299, 3), "f",
        models.inception_tiny, 6, (75, 75, 3), "f"),
    "mobilenetv2_2": (
        models.mobilenet_v2, models.MOBILENETV2_2STAGE_CUTS, (224, 224, 3),
        "f", models.mobilenet_tiny, 2, (32, 32, 3), "f"),
    "bert_base_12": (
        models.bert_base, models.BERT_BASE_12STAGE_CUTS, (128,), "i",
        models.bert_tiny, 4, (16,), "i"),
}


def sample(shape, kind, microbatch, lead=()):
    full = lead + (microbatch,) + shape
    if kind == "i":
        return (np.arange(int(np.prod(full))).reshape(full) % 100
                ).astype(np.float32)
    return np.zeros(full, np.float32)


#: config name -> (pretrained-loader family, checkpoint basename)
PRETRAINED = {
    "resnet50_8": "resnet50",
    "vgg19_4": "vgg19",
    "inceptionv3_6": "inception_v3",
    "mobilenetv2_2": "mobilenet_v2",
    "bert_base_12": "bert_base",
}


def _load_weights(name: str, graph, weights_dir: str | None):
    """Trained weights for a full config when a checkpoint is present
    (reference parity: it benchmarks ResNet50(weights="imagenet"),
    test/test.py:13-14).  Returns (params, trained?)."""
    family = PRETRAINED.get(name)
    if weights_dir and family:
        import os
        from defer_tpu.utils.pretrained import load_pretrained
        for ext in (".pt", ".pth", ".npz", ".safetensors", ".bin"):
            p = os.path.join(weights_dir, family + ext)
            if os.path.exists(p):
                log(f"{name}: loading trained weights {p}")
                return load_pretrained(family, p, graph), True
        log(f"{name}: no {family}.* checkpoint in {weights_dir}; "
            f"random init")
    return graph.init(jax.random.key(0)), False


def run_config(name, *, tiny: bool, chunk: int, stage_lat: bool,
               microbatch: int = 1, force_full: bool = False,
               weights_dir: str | None = None):
    (full_fn, full_cuts, full_shape, full_kind,
     tiny_fn, tiny_stages, tiny_shape, tiny_kind) = CONFIGS[name]
    on_tpu = jax.default_backend() == "tpu"
    use_full = (on_tpu or force_full) and not tiny
    n_dev = len(jax.devices())

    if use_full:
        graph, in_shape, kind = full_fn(), full_shape, full_kind
        cuts, num_stages = full_cuts, None
        want = len(full_cuts) + 1
    else:
        graph, in_shape, kind = tiny_fn(), tiny_shape, tiny_kind
        cuts, num_stages = None, min(tiny_stages, n_dev)
        want = num_stages
    if want > n_dev:
        cuts, num_stages, want = None, n_dev, n_dev
        log(f"{name}: only {n_dev} devices; auto-partitioning to {n_dev}")

    params, trained = _load_weights(name, graph,
                                    weights_dir if use_full else None)
    compute_dtype = jnp.bfloat16 if on_tpu and kind == "f" else None

    # single-device baseline (reference test/local_infer.py semantics),
    # reported stepwise (dispatch+sync per predict, reference protocol)
    # AND scan-amortized (K forwards in ONE dispatch — the chip's best
    # single-program number, the vs_baseline denominator)
    x1 = jnp.asarray(sample(in_shape, kind, microbatch))
    if kind == "i":
        x1 = x1.astype(jnp.int32)
    elif compute_dtype is not None:
        # baseline must compute in the same dtype as the pipeline: f32
        # inputs would make every op cast params back up, timing an f32
        # baseline against a bf16 pipeline (inflating vs_baseline)
        x1 = x1.astype(compute_dtype)
    fwd = jax.jit(graph.apply)
    # device-commit the BASELINE copy once (pretrained loaders return
    # host numpy, which every jit call would re-upload).  `params`
    # stays host-side for SpmdPipeline's packer.
    params_c = (jax.tree.map(lambda a: jnp.asarray(a, dtype=compute_dtype),
                             params)
                if compute_dtype else jax.device_put(params))
    base_step_s = timed_window(
        lambda: jax.block_until_ready(fwd(params_c, x1)),
        min_s=2.0, max_iters=256) / microbatch
    base_s = amortized_forward_seconds(
        graph.apply, params_c, x1, 32 if on_tpu else 8) / microbatch

    stages = partition(graph, cuts, num_stages=num_stages)
    pipe = SpmdPipeline(
        stages, params, mesh=pipeline_mesh(len(stages)),
        microbatch=microbatch, chunk=chunk,
        buffer_dtype=jnp.bfloat16 if on_tpu and kind == "f" else jnp.float32,
        compute_dtype=compute_dtype)
    xs = pipe.stage_inputs(sample(in_shape, kind, microbatch, lead=(chunk,)))
    pipe_s = pipeline_window_seconds(pipe, xs) / chunk / microbatch
    lats = None
    if stage_lat:
        lats = pipe.stage_latencies()

    from defer_tpu.graph.analysis import total_flops
    from defer_tpu.utils.hw import (analytic_pipeline_model, detect_chip,
                                    ici_bandwidth, peak_flops)

    m = pipe.metrics.as_dict()
    result = {
        "metric": f"{name}{'_tiny' if not use_full else ''}_throughput",
        "value": round(1.0 / pipe_s, 3),
        "unit": "inferences/sec",
        # honest: vs the scan-amortized single-device forward
        "vs_baseline": round(base_s / pipe_s, 4),
        "vs_stepwise_baseline": round(base_step_s / pipe_s, 4),
        "stages": len(stages),
        "trained_weights": trained,
        "microbatch": microbatch,
        "chunk": chunk,
        "single_device_s": round(base_s, 6),
        "single_device_stepwise_s": round(base_step_s, 6),
        "stage_latency_ms": m["stage_latency_ms"],
        # latency *distributions* (telemetry PR): per-chunk push and
        # per-stage percentiles, so BENCH_*.json rows carry p50/p95/p99
        "push_latency_ms": m.get("push_latency_ms"),
        "stage_latency_percentiles_ms": m.get(
            "stage_latency_percentiles_ms"),
        "duty_cycle": m["duty_cycle"],
        "pipeline_efficiency": m["pipeline_efficiency"],
        "bubble_fraction": m["bubble_fraction"],
        "buffer_bytes_per_hop": m["buffer_bytes_per_hop"],
        # padded-buffer waste per hop: what each stage boundary actually
        # carries vs the homogeneous buf_elems every hop pays
        "buffer_elems": pipe.buf_elems,
        "buffer_utilization_per_hop": [
            round(u, 4) for u in pipe.hop_utilization],
        "buffer_utilization_mean": round(
            sum(pipe.hop_utilization) / len(pipe.hop_utilization), 4),
    }
    gen = detect_chip(jax.devices()[0])  # an unknown TPU kind raises
    peak = peak_flops(gen)  # 0.0 off-TPU: no MFU column
    if peak > 0:
        # the pipeline spans len(stages) chips: utilization is against the
        # aggregate peak, not one chip's
        result["mfu"] = round(
            float(total_flops(graph)) / pipe_s / (peak * len(stages)), 4)
    if lats:
        # the written multi-chip argument: what an N-chip pipeline of these
        # measured stages would do, and where it loses vs ideal N
        result["analytic"] = analytic_pipeline_model(
            lats, m["buffer_bytes_per_hop"],
            ici_bandwidth(gen) if on_tpu else 0.0)

    if use_full and len(stages) < len(full_cuts) + 1 and stage_lat:
        # only 1 chip, but the full N-stage partition's per-stage story is
        # still measurable: time each stage's compiled branch standalone
        # (scan-amortized) and feed the analytic pipeline model — the
        # checkable multi-chip claim per config (BASELINE.md target)
        full = partition(graph, full_cuts)
        full_ms = []
        for s in full:
            sp = s.select_params(params_c)
            is_int = jnp.issubdtype(s.in_spec.dtype, jnp.integer)
            x = jnp.asarray(sample(s.in_spec.shape, "i" if is_int else "f",
                                   microbatch))
            if is_int:
                x = x.astype(jnp.int32)
            elif compute_dtype is not None:
                x = x.astype(compute_dtype)
            sec = amortized_forward_seconds(
                lambda p, xx, _s=s: _s.fn(p, xx), sp, x,
                16 if on_tpu else 4, min_s=1.0, max_iters=16)
            full_ms.append(sec * 1e3)
        from defer_tpu.partition.stage import buffer_footprint
        fp = buffer_footprint(
            full, microbatch=microbatch,
            itemsize=2 if on_tpu and kind == "f" else 4)
        result["full_partition"] = {
            "stages": len(full),
            "stage_ms": [round(v, 4) for v in full_ms],
            "buffer_elems": fp["buf_elems"],
            "buffer_utilization_per_hop": [
                round(u, 4) for u in fp["hop_utilization"]],
            "analytic": analytic_pipeline_model(
                [v / 1e3 for v in full_ms], fp["bytes_per_hop"],
                ici_bandwidth(gen) if on_tpu else 0.0),
        }
    return result


#: the platform every script-delegated row's child is pinned to
SCRIPT_ROW_PLATFORM = "cpu"


def run_script_row(script_name: str, extra_argv: list | None = None):
    """Delegate a row to a standalone smoke script in a subprocess
    pinned to the CPU platform: a chip belongs to one process (this
    one, if it has touched jax), the scripts' own ``setdefault`` is a
    no-op when a TPU host's environment names the TPU, and their
    delay-codec chains measure mechanisms, not the device.  Returns the
    script's JSON row (last stdout line) stamped with the platform
    that measured it."""
    import os
    import subprocess
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", script_name)
    env = {**os.environ, "JAX_PLATFORMS": SCRIPT_ROW_PLATFORM,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    proc = subprocess.run([sys.executable, script] + (extra_argv or []),
                          capture_output=True,
                          text=True, timeout=900, env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{script_name} rc={proc.returncode}: {proc.stderr[-2000:]}")
    row = json.loads(proc.stdout.strip().splitlines()[-1])
    return {**row, "platform": SCRIPT_ROW_PLATFORM}


#: script-delegated rows: `chain_overlap` (multi-process localhost chain,
#: overlapped vs serial node loop), `plan_vs_quantile` (bottleneck-
#: solver cuts vs greedy quantile cuts, predicted + measured — the row
#: reports how much the quantile baseline loses on the skewed chain),
#: `stage_replication` (hybrid pipeline/data-parallel chain: R=2 replicas
#: of a delay-bottlenecked stage vs the serial chain — byte-identical
#: outputs, >= 1.5x measured throughput, solver tie-in) and
#: `obs_overhead` (live observability plane: monitor rows converge to
#: node stats, bottleneck + straggler + replan name the delay-bound
#: stage, clock-aligned waterfalls, telemetry wall overhead < 5%) and
#: `colocated_fastpath` (transport tiers: colocated chain — one OS
#: process, local in-memory hops negotiated by the tier_probe handshake
#: — byte-identical to the all-TCP chain and >= 1.5x faster on a
#: codec-delay-bound chain; fused device hops eliminate the inter-stage
#: frame entirely; rows record the NEGOTIATED tier per hop so BENCH_*
#: trajectories distinguish TCP-bound from colocated/fused runs)
#: ... and `serving_frontdoor` (multi-tenant front door over one
#: deployed chain: >= 3 concurrent tenant streams byte-identical to
#: solo runs, continuous batching >= 1.5x sequential one-stream-at-a-
#: time serving on the delay-bound chain, and SLO-aware shedding
#: holding admitted p99 inside the SLO under a 2x-overload burst of a
#: deterministic OPEN-LOOP Poisson arrival trace — closed-loop load
#: hides queueing delay, so the p99 here is measured against arrivals
#: fixed up front; `--arrival-seed` reseeds the trace)
#: ... and `dag_pipeline` (branch-parallel stage graphs: the two-branch
#: delay-bound inception_tiny region deployed as concurrent sub-
#: pipelines between a broadcast fork and an all-paths (path, seq)
#: join, byte-identical to the serial composition of its own stage
#: programs and >= 1.5x min-of-3 wall vs the best linear-cut chain at
#: the SAME node count; the row also records the critical-path
#: planner's predicted DAG-vs-linear bottlenecks on inception_tiny and
#: the branched MoE family — docs/PLANNER.md)
#: ... and `shm_fastpath` (shared-memory transport tier: the same
#: codec-delay-bound 3-stage chain as REAL OS processes with every hop
#: — dispatcher edges included — negotiated `shm` via the tier_probe
#: handshake: activations cross a shared-memory ring while the socket
#: is demoted to a doorbell; byte-identical to the all-TCP chain,
#: >= 1.5x measured min-of-3 streams, zero codec.* samples on every
#: stage's live channels, and no /dev/shm segment survives teardown —
#: the same-host cross-PROCESS rung the colocated_fastpath row's
#: `local` tier cannot reach)
#: ... and `ici_fastpath` (device-resident transport tier: a copy-bound
#: fat-activation 3-stage chain on a FORCED 4-device host mesh, every
#: hop incl. dispatcher edges negotiated `ici` — live jax.Arrays cross
#: the hops with ZERO host materialization (zero codec.* AND zero
#: host_sync samples asserted; the one host sync per frame happens at
#: the dispatcher's result edge) and the thin cross-device hop performs
#: a real device-to-device jax.device_put per frame (distinct src/dst
#: device ids asserted from stats); byte-identical to all-tcp /
#: all-shm / all-local, >= 1.3x min-of-3 vs all-shm — the two REAL
#: memcpys per hop per frame the device-resident path eliminates; the
#: local tier is reported too but jax CPU host interop is zero-copy
#: both ways, so ici ~= local on this vehicle by design)
#: ... and `cost_model_truth` (the cost-model truth loop: calibrate
#: CalibratedConstants — host-sync / wire bandwidths, per-deployed-
#: codec throughputs — from a no-delay chain's own telemetry, then
#: assert the CALIBRATED model predicts the codec-delay-bound chain's
#: bottleneck stage service within 15% where the default model —
#: which prices the unknown dsleep/esleep codecs as raw — is
#: measurably worse; an injected slowdown must fire a `model_drift`
#: flight-recorder event within 2 monitor intervals; telemetry
#: overhead stays < 5% on the interleaved min-of-3 protocol; the row
#: embeds the fitted constants so BENCH_LEDGER.jsonl carries the
#: calibration trajectory — docs/PLANNER.md "calibrated constants")
#: ... and `request_attribution` (request-scoped serving
#: observability: under the serving row's 2x-burst open-loop trace,
#: the p50 AND p99 sampled requests' attributed budget buckets —
#: admission + batch-gather + per-stage compute + per-hop transport +
#: result edge, folded from the request's clock-aligned spans by
#: obs/attrib.py — sum to within 10% of each request's measured
#: end-to-end latency; the flight recorder's merged event log carries
#: the burst's shed and straggler events in per-process seq order with
#: zero ring drops at default capacity; and recorder+tracing overhead
#: stays < 5% vs telemetry-off on the interleaved min-of-3 protocol
#: obs_overhead established)
#: ... and `pipeline_failover` (the seq-replay substrate's chaos row:
#: kill -9 a mid-chain stage-1 replica while the stream is in flight —
#: the supervisor respawns it, the upstream fan-out heals and replays
#: its unacked window, and the run must end byte-identical to an
#: undisturbed reference; the row's value is the healed hop's measured
#: recovery wall time (ms) from its `failover` flight-recorder event,
#: and the same row carries the zero-downtime live-replan leg: a
#: mid-stream quiesce -> redeploy -> resume cutover onto the same
#: persist processes, byte-identical with its cutover_ms —
#: docs/ROBUSTNESS.md)
#: ... and `decode_profile` (the decode steady-state X-ray: after one
#: warmup generate, a second identical generate must reach XLA ZERO
#: times — measured by the jax.monitoring compile listener — with
#: EXACTLY ceil(num_steps/chunk_steps) scan dispatches and a dispatch
#: share <= ~1 of the generation wall; the guard rail under the mb64
#: decode-cliff autopsy in docs/DECODE_CLIFF.md)
#: ... and `blackbox_overhead` (the flight-recorder black box: the
#: chaos row's kill -9 replayed with --journal-dir on every process,
#: then the postmortem re-assembled OFFLINE from nothing but the
#: on-disk journals — verdict must name the killed replica with
#: journal-stop evidence, rank the nearest downstream stage first
#: among casualties, and show no negative inter-process gap on the
#: anchor-aligned timeline; the row's value is the journaling wall
#: tax from the interleaved min-of-3 on/off protocol, asserted < 5% —
#: docs/OBSERVABILITY.md "Black box & postmortem")
SCRIPT_ROWS = {
    "chain_overlap": "chain_overlap_smoke.py",
    "pipeline_failover": "chaos_smoke.py",
    "ici_fastpath": "ici_smoke.py",
    "plan_vs_quantile": "plan_smoke.py",
    "stage_replication": "replication_smoke.py",
    "obs_overhead": "monitor_smoke.py",
    "colocated_fastpath": "colocate_smoke.py",
    "shm_fastpath": "shm_smoke.py",
    "serving_frontdoor": "serve_smoke.py",
    "request_attribution": "request_obs_smoke.py",
    "dag_pipeline": "dag_smoke.py",
    "cost_model_truth": "capacity_smoke.py",
    "decode_profile": "decode_profile_smoke.py",
    "blackbox_overhead": "postmortem_smoke.py",
}


def ledger_append(path: str, row: dict):
    """Append one row to the machine-readable benchmark ledger
    (JSON-lines, one object per line, append-only — the cross-run
    trajectory BENCH_*.json snapshots cannot give).  Every row — config
    results, script rows, AND failures — lands here with a wall-clock
    stamp, so a probed-down row is an explicit record with a reason
    field, not a silent omission."""
    if not path:
        return
    try:
        with open(path, "a") as f:
            f.write(json.dumps(row, sort_keys=True) + "\n")
    except OSError as e:
        log(f"ledger: cannot append to {path}: {e!r}")


def failure_row(name: str, exc: Exception, *, kind: str,
                elapsed_s: float) -> dict:
    """An explicit machine-readable failure row: the metric that did
    NOT get measured and why.  `reason` carries the exception text
    (e.g. a smoke script's rc/stderr tail), `row_kind` whether it was
    a script-delegated probe or an in-process config."""
    return {
        "metric": name,
        "status": "failed",
        "row_kind": kind,
        "reason": f"{type(exc).__name__}: {exc}",
        "elapsed_s": round(elapsed_s, 1),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default=",".join(CONFIGS)
                    + "," + ",".join(SCRIPT_ROWS))
    ap.add_argument("--tiny", action="store_true",
                    help="force tiny variants (CPU smoke)")
    ap.add_argument("--full", action="store_true",
                    help="force full models even off-TPU (slow)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="steps fused per dispatch (0 = 128 on TPU, 16 off)")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--no-stage-latency", action="store_true")
    ap.add_argument("--weights-dir", default=None,
                    help="directory of trained checkpoints "
                         "(resnet50.pt, vgg19.pt, mobilenet_v2.pt, ...)")
    ap.add_argument("--arrival-seed", type=int, default=None,
                    help="reseed the serving row's open-loop arrival "
                         "trace (deterministic Poisson + 2x burst; "
                         "defaults to the smoke's built-in seed)")
    import os
    default_ledger = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_LEDGER.jsonl")
    ap.add_argument("--ledger", default=default_ledger, metavar="FILE",
                    help="append every row (successes AND explicit "
                         "failure rows) to this JSON-lines ledger "
                         "('' disables)")
    args = ap.parse_args()

    run_unix = time.time()
    platform = jax.default_backend()
    failed: list[str] = []

    def emit(row: dict):
        # a script row arrives stamped with its child's platform; every
        # other row was measured (or failed) in this process
        row = {"platform": platform, **row,
               "run_unix": round(run_unix, 1)}
        if row.get("status") == "failed":
            failed.append(str(row.get("metric")))
        print(json.dumps(row), flush=True)
        ledger_append(args.ledger, row)

    chunk = args.chunk or (128 if platform == "tpu" else 16)
    for name in args.configs.split(","):
        name = name.strip()
        if name in SCRIPT_ROWS:
            t0 = time.time()
            extra = []
            if name in ("serving_frontdoor", "request_attribution") \
                    and args.arrival_seed is not None:
                extra = ["--seed", str(args.arrival_seed)]
            try:
                r = run_script_row(SCRIPT_ROWS[name], extra)
            except Exception as e:  # noqa: BLE001 — keep the suite going
                log(f"{name}: FAILED {type(e).__name__}: {e}")
                emit({**failure_row(name, e, kind="script",
                                    elapsed_s=time.time() - t0),
                      "platform": SCRIPT_ROW_PLATFORM})
                continue
            log(f"{name}: {r['value']}x ({r['unit']}, "
                f"{time.time() - t0:.0f}s)")
            emit(r)
            continue
        if name not in CONFIGS:
            log(f"unknown config {name!r}; have {list(CONFIGS)}")
            emit({"metric": name, "status": "failed",
                  "row_kind": "config",
                  "reason": f"unknown config; have "
                            f"{sorted(list(CONFIGS) + list(SCRIPT_ROWS))}"})
            continue
        t0 = time.time()
        try:
            r = run_config(name, tiny=args.tiny, chunk=chunk,
                           microbatch=args.microbatch,
                           stage_lat=not args.no_stage_latency,
                           force_full=args.full,
                           weights_dir=args.weights_dir)
        except Exception as e:  # noqa: BLE001 — keep the suite going
            log(f"{name}: FAILED {type(e).__name__}: {e}")
            emit(failure_row(name, e, kind="config",
                             elapsed_s=time.time() - t0))
            continue
        log(f"{name}: {r['value']} inf/s ({time.time() - t0:.0f}s)")
        emit(r)
    if failed:
        # every row was attempted and recorded; the run still failed
        log(f"{len(failed)} row(s) failed: {', '.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
