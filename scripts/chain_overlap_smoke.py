"""Chain overlap smoke: prove the rx/compute/tx overlap is real and pays.

Two measurements over a 4-stage resnet_tiny chain:

1. OVERLAP RATIO (in-process thread chain, artificially slow codec):
   every hop uses a codec whose encode/decode sleep a fixed delay, so the
   per-phase histogram totals (``codec.encode_s`` + ``codec.decode_s`` +
   ``node.infer_s``) are a faithful "serial sum" of the work.  Asserts the
   overlapped wall time of the stream is < ``--max-ratio`` (default 0.8)
   of that sum, that rx/infer spans of adjacent microbatches actually
   overlap in time in the collected trace, and that the channel gauges
   (``node.rx_queue_depth`` / ``node.tx_queue_depth`` / ``node.inflight``)
   appear in the metrics snapshot.

2. SPEEDUP (multi-process chains): spawns the 4-stage chain as real OS
   processes, overlapped node loops vs the serial pre-overlap baseline
   (``--no-overlap``), identical inputs, warmup stream excluded from the
   window, byte-identical outputs required.  Two wire configurations:

   * plain ``bf8`` — the honest all-CPU measurement.  Its speedup is
     asserted >= ``--min-speedup`` (default 1.25) only on hosts with
     >= 8 CPUs: with fewer cores every phase competes for the same
     silicon and overlapping CPU-bound work cannot beat its sum (a
     1-core CI box measures ~1.0x by physics, not by regression).
   * ``sleep<ms>+bf8`` — the same bf8 bytes plus a fixed per-side delay
     that models the phases a CPU-bound localhost chain cannot express
     (accelerator compute, NIC serialization).  This speedup is asserted
     >= ``--min-speedup`` on every host: it is the portable proof that
     the overlap machinery actually hides non-CPU phase time.

Exit 0 on success; one JSON row on stdout (the ``chain_overlap`` row of
``benchmarks/run.py``).

Usage:  python scripts/chain_overlap_smoke.py [--trace-out FILE]
            [--metrics-out FILE] [--min-speedup 1.25] [--quick]
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: stage-node subprocesses run on the CPU platform (a chip belongs to
#: one process; these chains demonstrate topology, not the device)
CPU_ENV = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# part 1: in-process thread chain with a slow codec -> overlap ratio + trace
# ---------------------------------------------------------------------------

def overlap_ratio(stages, params, *, delay_s: float, count: int,
                  batch: int) -> dict:
    import numpy as np

    from defer_tpu.codec.codecs import RawCodec
    from defer_tpu.obs import REGISTRY, enable_tracing, tracer
    from defer_tpu.runtime.node import ChainDispatcher, StageNode
    from defer_tpu.transport import framed

    class SlowCodec(RawCodec):
        """Raw codec with a fixed sleep on both sides: makes the codec
        phases big and *exactly known*, so wall-vs-sum is a clean test."""
        name = "slow"

        def encode(self, arr):
            time.sleep(delay_s)
            return super().encode(arr)

        def decode(self, data, shape, dtype):
            time.sleep(delay_s)
            return super().decode(data, shape, dtype)

    framed._CODECS["slow"] = SlowCodec()
    for h in ("codec.encode_s", "codec.decode_s", "node.infer_s"):
        REGISTRY.histogram(h).clear()
    tr = enable_tracing(process="dispatcher")
    tr.start_trace()

    nodes = [StageNode(None, "127.0.0.1:0", None) for _ in range(len(stages))]
    addrs = [f"127.0.0.1:{n.address[1]}" for n in nodes]
    threads = [threading.Thread(target=n.serve, daemon=True) for n in nodes]
    for t in threads:
        t.start()
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((batch,) + tuple(stages[0].in_spec.shape))
          .astype(np.float32) for _ in range(count)]
    disp = ChainDispatcher(addrs[0], codec="slow")
    try:
        disp.deploy(stages, params, addrs, batch=batch)
        disp.stream(xs[:2])  # warm: jit compiles, connections, first frames
        for h in ("codec.encode_s", "codec.decode_s", "node.infer_s"):
            REGISTRY.histogram(h).clear()
        t0 = time.perf_counter()
        outs = disp.stream(xs)
        wall = time.perf_counter() - t0
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=30)
    assert len(outs) == count, (len(outs), count)

    serial_sum = sum(REGISTRY.histogram(h).sum
                     for h in ("codec.encode_s", "codec.decode_s",
                               "node.infer_s"))
    snap = REGISTRY.snapshot()
    for g in ("node.rx_queue_depth", "node.tx_queue_depth", "node.inflight"):
        assert g in snap, f"gauge {g} missing from the metrics snapshot"

    # the trace must show phases of ADJACENT microbatches overlapping in
    # wall time within one stage: rx(j') concurrent with infer(j), j' > j
    spans = tracer().spans
    overlaps = 0
    for k in range(len(stages)):
        rxs = [s for s in spans if s["name"] == f"stage{k}.rx"]
        infers = [s for s in spans if s["name"] == f"stage{k}.infer"]
        for a in rxs:
            for b in infers:
                if a["args"].get("seq", 0) > b["args"].get("seq", 0) \
                        and a["ts_us"] < b["ts_us"] + b["dur_us"] \
                        and b["ts_us"] < a["ts_us"] + a["dur_us"]:
                    overlaps += 1
    assert overlaps > 0, "no rx/infer span overlap found in the trace"
    return {"wall_s": wall, "serial_sum_s": serial_sum,
            "ratio": wall / serial_sum, "span_overlaps": overlaps,
            "snapshot": snap}


# ---------------------------------------------------------------------------
# part 2: multi-process chain, bf8 -> speedup vs the serial node loop
# ---------------------------------------------------------------------------

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def timed_chain(paths, xs_warm, xs, *, overlap: bool, codec: str,
                log_dir: str):
    """Spawn one node process per stage artifact, warm the chain, stream
    ``xs`` timed, tear down.  Returns (outputs, seconds)."""
    from defer_tpu.runtime.node import ChainDispatcher

    n = len(paths)
    ports = _free_ports(n + 1)
    child_env = dict(os.environ)
    child_env.update(CPU_ENV)
    mode = "overlap" if overlap else "serial"
    procs, logs = [], []
    for i in range(n):
        # --tier tcp pins the hops to the pure wire path: this row
        # measures the rx/compute/tx OVERLAP, and an auto-negotiated
        # shm hop would bypass the slow codec being overlapped
        argv = [sys.executable, "-m", "defer_tpu", "node",
                "--artifact", paths[i],
                "--listen", f"127.0.0.1:{ports[i]}",
                "--next", f"127.0.0.1:{ports[i + 1]}",
                "--codec", codec, "--tier", "tcp"] \
            + ([] if overlap else ["--no-overlap"])
        lf = open(os.path.join(log_dir, f"{mode}_node_{i}.log"), "w+")
        logs.append(lf)
        procs.append(subprocess.Popen(argv, env=child_env, stdout=lf,
                                      stderr=subprocess.STDOUT))
    disp = ChainDispatcher(f"127.0.0.1:{ports[0]}",
                           listen=f"127.0.0.1:{ports[-1]}", codec=codec)
    try:
        disp.stream(xs_warm)   # boot + compile excluded from the window
        t0 = time.perf_counter()
        outs = disp.stream(xs)
        dt = time.perf_counter() - t0
    finally:
        disp.close()
        for pr in procs:
            try:
                pr.wait(timeout=30)
            except subprocess.TimeoutExpired:
                pr.kill()
        for lf in logs:
            lf.close()
    return outs, dt


def speedup(stages, params, *, count: int, batch: int, codec: str) -> dict:
    import numpy as np

    from defer_tpu.utils.export import export_pipeline

    rng = np.random.default_rng(1)
    xs = [rng.standard_normal((batch,) + tuple(stages[0].in_spec.shape))
          .astype(np.float32) for _ in range(count)]
    xs_warm = xs[:4]
    with tempfile.TemporaryDirectory(prefix="defer_overlap_") as tmp:
        paths = export_pipeline(stages, params, tmp, batch=batch)
        slow_outs, slow_s = timed_chain(paths, xs_warm, xs, overlap=False,
                                        codec=codec, log_dir=tmp)
        log(f"serial:     {count * batch / slow_s:8.1f} inf/s "
            f"({slow_s:.2f}s)")
        fast_outs, fast_s = timed_chain(paths, xs_warm, xs, overlap=True,
                                        codec=codec, log_dir=tmp)
        log(f"overlapped: {count * batch / fast_s:8.1f} inf/s "
            f"({fast_s:.2f}s)")
    assert len(fast_outs) == len(slow_outs) == count
    for a, b in zip(fast_outs, slow_outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    return {"serial_s": slow_s, "overlap_s": fast_s,
            "speedup": slow_s / fast_s,
            "serial_inf_s": count * batch / slow_s,
            "overlap_inf_s": count * batch / fast_s}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-speedup", type=float, default=1.25,
                    help="required overlapped/serial throughput ratio")
    ap.add_argument("--max-ratio", type=float, default=0.8,
                    help="required wall / serial-phase-sum bound (part 1)")
    ap.add_argument("--count", type=int, default=48,
                    help="timed microbatches through the chain")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--codec", default="bf8")
    ap.add_argument("--delay-ms", type=float, default=5.0,
                    help="slow-codec per-side sleep (part 1)")
    ap.add_argument("--quick", action="store_true",
                    help="part 1 only (no multi-process spawns)")
    ap.add_argument("--trace-out", default=None, metavar="FILE")
    ap.add_argument("--metrics-out", default=None, metavar="FILE")
    args = ap.parse_args()

    import jax

    from defer_tpu import partition
    from defer_tpu.models import resnet_tiny
    from defer_tpu.obs import export_chrome_trace

    graph = resnet_tiny()
    params = graph.init(jax.random.key(0))
    stages = partition(graph, num_stages=4)

    r1 = overlap_ratio(stages, params, delay_s=args.delay_ms / 1e3,
                       count=min(args.count, 24), batch=4)
    log(f"overlap ratio: wall {r1['wall_s']:.2f}s vs serial phase sum "
        f"{r1['serial_sum_s']:.2f}s -> {r1['ratio']:.3f} "
        f"({r1['span_overlaps']} overlapping span pairs)")
    if args.trace_out:
        export_chrome_trace(args.trace_out)
        log(f"trace -> {args.trace_out}")
    if args.metrics_out:
        with open(args.metrics_out, "w") as f:
            json.dump(r1["snapshot"], f, indent=2, default=str)
            f.write("\n")
        log(f"metrics -> {args.metrics_out}")
    assert r1["ratio"] < args.max_ratio, (
        f"overlapped wall {r1['wall_s']:.2f}s is {r1['ratio']:.2f}x the "
        f"serial phase sum (bound {args.max_ratio})")

    cores = os.cpu_count() or 1
    row = {"metric": "chain_overlap", "unit": "x_vs_serial_node_loop",
           "stages": len(stages), "codec": args.codec,
           "batch": args.batch, "count": args.count, "cpu_count": cores,
           "overlap_wall_vs_phase_sum": round(r1["ratio"], 4)}
    if args.quick:
        row["value"] = None
    else:
        # plain bf8: byte-identity always; speedup asserted on hosts with
        # enough cores that compute/codec phases CAN physically overlap
        r_cpu = speedup(stages, params, count=args.count, batch=args.batch,
                        codec=args.codec)
        log(f"{args.codec} speedup: {r_cpu['speedup']:.3f}x "
            f"({'asserted' if cores >= 8 else f'informational on {cores} cpu(s)'})")
        if cores >= 8:
            assert r_cpu["speedup"] >= args.min_speedup, (
                f"{args.codec} overlap speedup {r_cpu['speedup']:.3f}x is "
                f"under the {args.min_speedup}x bar on {cores} cpus "
                f"(serial {r_cpu['serial_inf_s']:.1f} inf/s, overlapped "
                f"{r_cpu['overlap_inf_s']:.1f} inf/s)")
        # sleep-wrapped bf8 (same wire bytes + per-side non-CPU delay):
        # the portable overlap proof, asserted on every host
        wire = f"sleep{args.delay_ms:g}+{args.codec}"
        r_wire = speedup(stages, params, count=args.count,
                         batch=min(args.batch, 8), codec=wire)
        log(f"{wire} speedup: {r_wire['speedup']:.3f}x")
        assert r_wire["speedup"] >= args.min_speedup, (
            f"{wire} overlap speedup {r_wire['speedup']:.3f}x is under "
            f"the {args.min_speedup}x bar (serial "
            f"{r_wire['serial_inf_s']:.1f} inf/s, overlapped "
            f"{r_wire['overlap_inf_s']:.1f} inf/s)")
        row.update({
            "value": round(r_wire["speedup"], 4),
            "wire_codec": wire,
            "serial_inf_per_s": round(r_wire["serial_inf_s"], 2),
            "overlap_inf_per_s": round(r_wire["overlap_inf_s"], 2),
            f"{args.codec}_speedup": round(r_cpu["speedup"], 4),
            f"{args.codec}_speedup_asserted": cores >= 8,
        })
    print(json.dumps(row))
    log("chain overlap smoke: OK")


if __name__ == "__main__":
    main()
