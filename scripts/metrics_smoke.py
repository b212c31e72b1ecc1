"""Telemetry smoke test: tiny CPU pipeline -> non-empty trace + metrics.

Runs a 2-stage resnet_tiny SPMD pipeline on the CPU backend with tracing
enabled, then asserts that (a) the Chrome-trace export contains dispatcher
and per-stage spans sharing one trace id, and (b) the metrics registry
snapshot carries per-stage latency percentiles and per-hop byte counters.
Exit 0 on success; any assertion failure is loud.  Cheap enough for a
tier-1 time budget (~15 s, dominated by one XLA compile).

Usage:  python scripts/metrics_smoke.py [--out-dir DIR]
"""

import argparse
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default=None,
                    help="keep the exports here (default: tempdir)")
    args = ap.parse_args()

    import numpy as np

    import jax

    from defer_tpu import SpmdPipeline, partition, pipeline_mesh
    from defer_tpu.models import resnet_tiny
    from defer_tpu.obs import REGISTRY, enable_tracing, tracer

    tr = enable_tracing(process="dispatcher")
    tr.start_trace()

    graph = resnet_tiny()
    params = graph.init(jax.random.key(0))
    stages = partition(graph, num_stages=2)
    pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(2),
                        microbatch=1, chunk=4)
    xs = np.zeros((4, 1, 32, 32, 3), np.float32)
    for _ in range(3):
        pipe.push(xs)
    pipe.flush()
    pipe.stage_latencies(iters=2)

    out_dir = args.out_dir or tempfile.mkdtemp(prefix="defer_obs_smoke_")
    trace_path = os.path.join(out_dir, "trace.json")
    metrics_path = os.path.join(out_dir, "metrics.json")
    tr.export_chrome(trace_path)
    REGISTRY.dump_json(metrics_path)

    # ---- assertions: the exports are non-empty and self-consistent
    t = json.load(open(trace_path))
    events = [e for e in t["traceEvents"] if e.get("ph") == "X"]
    assert events, "trace export has no spans"
    trace_ids = {e["args"].get("trace_id") for e in events}
    assert len(trace_ids) == 1, f"spans span {len(trace_ids)} trace ids"
    names = {e["name"] for e in events}
    assert any(n.startswith("spmd.push") for n in names), names
    assert any(n.startswith("stage0") for n in names), names

    m = json.load(open(metrics_path))
    prefix = pipe.metrics.prefix
    stage0 = m[f"{prefix}.stage0.latency_s"]
    for q in ("p50", "p95", "p99", "max"):
        assert q in stage0, stage0
    hop0 = m[f"{prefix}.hop0.bytes"]
    assert hop0 > 0, "per-hop byte counter did not accumulate"
    push = m[f"{prefix}.push_latency_s"]
    assert push["count"] >= 3, push

    print(json.dumps({
        "metric": "metrics_smoke", "value": 1, "unit": "ok",
        "spans": len(events),
        "push_p99_ms": round(push["p99"] * 1e3, 3),
        "trace": trace_path, "metrics": metrics_path,
    }))
    print("metrics smoke: OK", file=sys.stderr)
    # clean up tempdir exports unless the caller asked to keep them
    if args.out_dir is None:
        for p in (trace_path, metrics_path):
            os.unlink(p)
        os.rmdir(out_dir)


if __name__ == "__main__":
    main()
