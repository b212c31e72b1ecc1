"""Device-resident transport-tier smoke: prove the ici fast path pays.

A 3-stage COPY-BOUND chain ("copychain": a thin 1 KB input Tiled into a
33 MB fat activation, reduced back to thin, then a small Dense head) on
a FORCED 4-device host mesh (``utils.compat.force_host_device_count`` —
a real multi-device jax platform in one process, the test vehicle for
same-mesh work without a TPU).  The fat boundary crosses the
``fan -> squash`` hop; stage placement follows the planner's wisdom:
the fat boundary stays ON-DEVICE (both sides pinned to device 0) and
the thin ``squash -> head`` boundary crosses the mesh (device 0 ->
device 1) with one real cross-device ``jax.device_put`` per frame —
asserted from stats with distinct (src, dst) device ids.

Unlike every earlier tier bench this chain is NOT delay-codec-bound:
the work eliminated is real memory traffic.  The reference point for
the speedup bar is the ``shm`` tier, whose TWO memcpys per hop per
frame (ring write-in + read-out) are real on every backend — exactly
the two memory passes the device-resident path eliminates.  The
``local`` tier is measured and reported too, but on THIS vehicle it is
already effectively device-resident: jax's CPU backend aliases host
views of its own buffers in both directions (``np.asarray`` of a CPU
array is a zero-copy view, and feeding such a view back into a jit is
a zero-copy import — measured, not assumed), so all-ici ~= all-local
here by physics.  On a real accelerator the local tier's host
crossings are D2H + H2D DMAs — the cost the planner's ``host_sync``
term models and the per-stage ``host_sync`` histogram measures; the
ici rows' ZERO samples in that histogram are the vehicle-independent
proof the round-trip is gone.

Checks:

1. All four chains (tcp / shm / local / ici) produce BYTE-IDENTICAL
   outputs; every hop's negotiated tier (dispatcher edges included) is
   asserted from stats.
2. All-ici >= ``--min-speedup`` (1.3) min-of-3 streams vs all-shm (the
   two eliminated memory passes), and not slower than all-local beyond
   noise (>= ``--local-floor``, default 0.7 — equality is the expected
   reading on a zero-copy-interop host; the ratio jitters +-0.2 on
   this 1-core box).
3. ZERO ``codec.*`` AND ZERO ``host_sync`` samples on every ici hop
   (the local chain records one host_sync sample per frame per stage —
   the instrument provably works); the dispatcher's result edge
   host-syncs exactly once per frame.
4. At least one hop performs a real cross-device ``device_put``:
   stage 1's stats carry ``ici_d2d == frames`` with device pair
   ``[0, 1]``.
5. PLANNER: ``TIER_CODECS["ici"]`` + the ``host_sync`` term give the
   strict ordering device < ici < local < shm < tcp on the bench
   graph's fat boundary, an ici hop-tier map beats the all-tcp plan's
   bottleneck strictly, and the tier survives the plan-JSON roundtrip.

Exit 0 on success; one JSON row on stdout (the ``ici_fastpath`` row of
``benchmarks/run.py``).

Usage:  python scripts/ici_smoke.py [--quick] [--reps R] [--count N]
            [--min-speedup 1.3] [--local-floor 0.7]
"""

import argparse
import json
import os
import sys
import threading
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from defer_tpu.utils.compat import force_host_device_count  # noqa: E402

#: the forced same-mesh vehicle: must land before jax's backend init
#: (benchmarks/run.py pins children to a 1-device mesh — override it)
_OK, _WHY = force_host_device_count(4)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_graph(reps: int):
    """copychain: thin -> FAT (reps x 256 f32) -> thin -> head."""
    from defer_tpu import GraphBuilder
    from defer_tpu.graph import ops

    b = GraphBuilder("copychain")
    x = b.input((256,))
    x = b.add(ops.Tile(reps), x, name="fan")
    x = b.add(ops.ReduceMean(axis=1), x, name="squash")
    x = b.add(ops.Dense(256), x, name="head")
    return b.build()


def run_chain(stages, params, xs, *, tier, devices=None, streams=3):
    """Thread-per-node in-process chain (the only process shape a
    device-resident hop can exist in); returns (outs, min_wall, stats,
    dispatcher_tiers)."""
    from defer_tpu.runtime.node import ChainDispatcher, StageNode

    nodes = [StageNode(None, "127.0.0.1:0", None, tier=tier,
                       tier_accept=True)
             for _ in range(len(stages))]
    addrs = [f"127.0.0.1:{nd.address[1]}" for nd in nodes]
    threads = [threading.Thread(target=nd.serve, daemon=True)
               for nd in nodes]
    for t in threads:
        t.start()
    disp = ChainDispatcher(addrs[0], codec="raw", tier=tier)
    try:
        disp.deploy(stages, params, addrs, batch=xs[0].shape[0],
                    tiers=[tier] * len(stages), devices=devices)
        disp.stream(xs[:2])  # warm: compile + connect + negotiate
        wall = float("inf")
        for _ in range(streams):
            t0 = time.perf_counter()
            outs = disp.stream(xs)
            wall = min(wall, time.perf_counter() - t0)
        stats = disp.stats(addrs)
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=60)
    return outs, wall, stats, (disp.tier_out, disp.tier_in)


def planner_check(graph, reps: int) -> dict:
    """The acceptance planner block: strict tier ordering on the fat
    boundary + ici map strict-win + plan-JSON roundtrip."""
    from defer_tpu.plan import StageCostModel, plan_from_json, solve

    costs = {"fan": 1e-4, "squash": 1e-4, "head": 1e-4}
    cm = StageCostModel(graph, gen="v5e", link_bw_s=1e9, node_costs=costs)
    fat = "fan"
    order = {t: cm.with_hop_tiers({fat: t}).comm_seconds(fat, t)
             for t in ("device", "ici", "local", "shm")}
    order["tcp"] = cm.best_codec(fat)[1]
    seq = [order[t] for t in ("device", "ici", "local", "shm", "tcp")]
    assert seq == sorted(seq) and len(set(seq)) == len(seq), (
        f"tier ordering not strict on the fat boundary: {order}")
    p_tcp = solve(graph, 3, cm)
    tiers = {"fan": "ici", "squash": "ici"}
    p_ici = solve(graph, 3, cm, hop_tiers=tiers)
    assert p_ici.bottleneck_s < p_tcp.bottleneck_s, (
        f"ici map did not beat tcp: {p_ici.bottleneck_s} vs "
        f"{p_tcp.bottleneck_s}")
    doc = p_ici.to_json()
    rt = plan_from_json(doc)
    assert rt.hop_tiers == p_ici.hop_tiers and "ici" in rt.hop_tiers
    log(f"planner: tcp bottleneck {p_tcp.bottleneck_s * 1e3:.3f} ms vs "
        f"ici {p_ici.bottleneck_s * 1e3:.3f} ms; fat-boundary tier "
        f"order (us): "
        + " < ".join(f"{t}={order[t] * 1e6:.2f}"
                     for t in ("device", "ici", "local", "shm", "tcp")))
    return {"tcp_bottleneck_ms": round(p_tcp.bottleneck_s * 1e3, 4),
            "ici_bottleneck_ms": round(p_ici.bottleneck_s * 1e3, 4),
            "hop_tiers": p_ici.hop_tiers}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller fat activation + fewer frames (CI)")
    ap.add_argument("--reps", type=int, default=0,
                    help="tile factor: fat bytes = reps * 1024 (default "
                         "32768 full / 16384 quick)")
    ap.add_argument("--count", type=int, default=8)
    ap.add_argument("--min-speedup", type=float, default=1.3,
                    help="all-ici vs all-shm bar (the two real memcpys "
                         "per hop per frame the tier eliminates)")
    ap.add_argument("--local-floor", type=float, default=0.7,
                    help="all-ici vs all-local floor — a regression "
                         "guard, not a win bar: the ratio is expected "
                         "~1.0 on this zero-copy-interop vehicle and "
                         "jitters +-0.2 on the 1-core box")
    args = ap.parse_args()

    import jax
    import numpy as np

    from defer_tpu import partition
    from defer_tpu.obs import REGISTRY

    devs = jax.devices()
    assert len(devs) >= 3, (
        f"forced host mesh did not come up ({_WHY}); have {devs}")
    log(f"host mesh: {len(devs)} x {devs[0].platform} devices ({_WHY})")

    reps = args.reps or (16384 if args.quick else 32768)
    graph = build_graph(reps)
    params = graph.init(jax.random.key(0))
    stages = partition(graph, ["fan", "squash"])
    fat_mb = graph.out_spec("fan").size * 4 / 1e6
    log(f"copychain: fat boundary {fat_mb:.1f} MB f32, "
        f"{args.count} frames, min-of-3 streams")

    rng = np.random.default_rng(0)
    xs = [rng.standard_normal((1, 256)).astype(np.float32)
          for _ in range(args.count)]

    def hist_count(name):
        return int(REGISTRY.histogram(name).summary().get("count", 0))

    # -- the four chains ----------------------------------------------------
    tcp_o, tcp_w, tcp_st, _ = run_chain(stages, params, xs, tier="tcp",
                                        streams=1)
    assert [s["tier"] for s in tcp_st] == ["tcp"] * 3
    shm_o, shm_w, shm_st, _ = run_chain(stages, params, xs, tier="shm")
    assert [s["tier"] for s in shm_st] == ["shm"] * 3
    loc_o, loc_w, loc_st, _ = run_chain(stages, params, xs, tier="local")
    assert [s["tier"] for s in loc_st] == ["local"] * 3
    # the local chain host-syncs once per frame per stage — the
    # instrument the ici rows must show ZERO samples on
    n_loc_frames = args.count * 3 + 2  # 3 streams + 2 warm frames
    assert all(s["host_sync_s"]["count"] == n_loc_frames
               for s in loc_st), [s["host_sync_s"] for s in loc_st]

    enc0 = hist_count("codec.encode_s")
    dec0 = hist_count("codec.decode_s")
    hs0 = hist_count("node.host_sync_s")
    chs0 = hist_count("chain.host_sync_s")
    ici_o, ici_w, ici_st, disp_tiers = run_chain(
        stages, params, xs, tier="auto", devices=[0, 0, 1])

    # 1. negotiated tiers, every hop + both dispatcher edges
    assert [s["tier"] for s in ici_st] == ["ici"] * 3, ici_st
    assert [s["tier_in"] for s in ici_st] == ["ici"] * 3
    assert disp_tiers == ("ici", "ici"), disp_tiers
    assert [s["device"] for s in ici_st] == [0, 0, 1]

    # byte identity across ALL tiers
    for name, outs in (("tcp", tcp_o), ("shm", shm_o), ("local", loc_o)):
        for a, b in zip(outs, ici_o):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        log(f"all-ici byte-identical to all-{name}")

    # 3. zero codec work, zero host syncs on the device-resident chain
    assert hist_count("codec.encode_s") == enc0, "ici hop encoded"
    assert hist_count("codec.decode_s") == dec0, "ici hop decoded"
    assert hist_count("node.host_sync_s") == hs0, (
        "an ici hop materialized to host")
    assert all(s["host_sync_s"]["count"] == 0 for s in ici_st)
    n_frames = args.count * 3 + 2
    assert hist_count("chain.host_sync_s") - chs0 == n_frames, (
        "result edge must host-sync exactly once per frame")

    # 4. the real cross-device transfer: squash(dev0) -> head(dev1)
    assert ici_st[1]["ici_d2d"] == n_frames, ici_st[1]
    assert ici_st[1]["ici_device_pairs"] == [[0, 1]], ici_st[1]
    src, dst = ici_st[1]["ici_device_pairs"][0]
    assert src != dst

    # 2. the speedups
    v_shm = shm_w / ici_w
    v_loc = loc_w / ici_w
    v_tcp = tcp_w / ici_w
    log(f"walls (min-of-3, {args.count} frames): tcp {tcp_w:.3f}s, "
        f"shm {shm_w:.3f}s, local {loc_w:.3f}s, ici {ici_w:.3f}s")
    log(f"all-ici: {v_shm:.2f}x vs shm, {v_loc:.2f}x vs local, "
        f"{v_tcp:.2f}x vs tcp")
    assert v_shm >= args.min_speedup, (
        f"ici {v_shm:.3f}x vs shm under the {args.min_speedup}x bar — "
        f"the two per-hop memcpys were not eliminated")
    assert v_loc >= args.local_floor, (
        f"ici {v_loc:.3f}x vs local under the {args.local_floor} floor "
        f"(expected ~1.0 on a zero-copy-interop host)")

    planner = planner_check(graph, reps)

    row = {
        "metric": "ici_fastpath",
        "value": round(v_shm, 4),
        "unit": "x_vs_shm_chain",
        "stages": 3, "fat_mb": round(fat_mb, 1),
        "count": args.count, "quick": bool(args.quick),
        "devices": [s["device"] for s in ici_st],
        "d2d_pairs": ici_st[1]["ici_device_pairs"],
        "speedup_vs_shm": round(v_shm, 4),
        "speedup_vs_local": round(v_loc, 4),
        "speedup_vs_tcp": round(v_tcp, 4),
        "host_sync_counts_ici": [s["host_sync_s"]["count"]
                                 for s in ici_st],
        "host_sync_counts_local": [s["host_sync_s"]["count"]
                                   for s in loc_st],
        "planner": planner,
        "note": ("vs_local ~1.0 expected: jax CPU host interop is "
                 "zero-copy both ways, so the local tier is already "
                 "device-resident on this vehicle; shm's two memcpys "
                 "per hop are real on every backend"),
    }
    print(json.dumps(row))
    log("ici fast-path smoke: OK")


if __name__ == "__main__":
    main()
