"""Analytic roofline for the MFU-ceiling question.

Standalone per-op timing is blind below the host's dispatch floor
(PROFILE_OPS_r05.json, old installation: every top conv cost exactly
the floor), so the per-op evidence for where the ceiling sits comes from
shape math instead: for every node of the deployed graph, per-sample
FLOPs (the ops' own ``flops`` methods, 2*MAC) and minimum HBM traffic at
bf16, then

    t_min(op) = max(flops / peak_bf16, bytes / hbm_bw)

summed in two scenarios:

- ``unfused``: every op reads its inputs and writes its output (what
  running each op standalone would cost at best);
- ``fused``: elementwise ops (BN / activation / add / pad) are free —
  their bytes ride the producing conv's write and consuming conv's read,
  the XLA behavior PROFILE_OPS_r05's 10.8x fusion gain confirms —
  weights are read once per batch, conv in/out tensors move once each.

``ceiling_mfu = total_flops / (peak * sum t_min)`` is the best MFU any
schedule could reach under the roofline; the measured number
(BENCH_r05_builder.json) is judged against it.

Pure shape math: runs anywhere, no device needed.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


ELEMENTWISE = {"BatchNorm", "Activation", "Add", "ZeroPad2D", "LayerNorm",
               "Dropout"}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--gen", default="v5e")
    args = ap.parse_args()

    import jax

    from defer_tpu import models
    from defer_tpu.graph.analysis import node_flops
    from defer_tpu.utils.hw import hbm_bandwidth, peak_flops

    graph = getattr(models, args.model)()
    peak = peak_flops(args.gen)
    bw = hbm_bandwidth(args.gen)
    if not peak or not bw:
        raise SystemExit(f"unknown TPU generation {args.gen!r} "
                         f"(no peak/bandwidth table entry)")
    b = args.batch
    bpe = 2  # bf16

    rows = []
    tot = {"flops": 0.0, "t_unfused": 0.0, "t_fused": 0.0,
           "bytes_fused": 0.0}
    for name, node in graph.nodes.items():
        in_specs = tuple(graph.out_spec(i) for i in node.inputs)
        out = node.out_spec
        fl = float(node_flops(graph, name)) * b
        act_bytes = (sum(s.size for s in in_specs) + out.size) * b * bpe
        w_bytes = 0.0
        if node.param_spec:
            w_bytes = sum(float(np.prod(l.shape)) * bpe for l in
                          jax.tree.leaves(node.param_spec))
        kind = type(node.op).__name__
        ew = kind in ELEMENTWISE
        t_unf = max(fl / peak, (act_bytes + w_bytes) / bw)
        t_fus = 0.0 if ew else max(fl / peak, (act_bytes + w_bytes) / bw)
        tot["flops"] += fl
        tot["t_unfused"] += t_unf
        tot["t_fused"] += t_fus
        if not ew:
            tot["bytes_fused"] += act_bytes + w_bytes
        rows.append({"node": name, "op": kind, "gflops": round(fl / 1e9, 2),
                     "mbytes": round((act_bytes + w_bytes) / 1e6, 2),
                     "intensity": round(fl / (act_bytes + w_bytes), 1),
                     "t_min_us": round(t_fus * 1e6, 1),
                     "bound": ("ew-fused" if ew else
                               "compute" if fl / peak >=
                               (act_bytes + w_bytes) / bw else "memory")})

    out = {
        "metric": f"{args.model}_roofline",
        "gen": args.gen, "batch": b,
        "peak_bf16_tflops": peak / 1e12, "hbm_gb_s": bw / 1e9,
        "total_gflops": round(tot["flops"] / 1e9, 1),
        "ceiling_mfu_fused": round(
            tot["flops"] / (peak * tot["t_fused"]), 4),
        "ceiling_mfu_unfused": round(
            tot["flops"] / (peak * tot["t_unfused"]), 4),
        "t_fused_ms": round(tot["t_fused"] * 1e3, 3),
        "memory_bound_ops": sorted(
            [r for r in rows if r["bound"] == "memory"],
            key=lambda r: -r["t_min_us"])[:10],
        "top_ops_by_t": sorted([r for r in rows if r["bound"] != "ew-fused"],
                               key=lambda r: -r["t_min_us"])[:10],
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
