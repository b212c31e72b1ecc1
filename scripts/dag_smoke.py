"""DAG-pipeline smoke: prove branch-parallel stage graphs pay.

A linear cut cannot split the parallel branches of a fork/join region
(``graph.analysis.branch_regions``), so a branching model's region body
serializes inside one stage.  The DAG planner (``plan/dag.py``) instead
mirrors the graph: a broadcast fork, one concurrent sub-pipeline per
branch, an all-paths ``(path, seq)`` join (``transport/branch.py``).
This smoke makes that win measurable on a 1-core host with the
delay-bound pattern (see replication_smoke.py): the two conv branches of
inception_tiny's ``mixed_3`` reduction region each cost a fixed
simulated device delay (``node --infer-delay-ms``, sleeping — not
spinning — so concurrent branch processes overlap like real
accelerators), and the planner scores the same delays as ``node_costs``
— prediction and deployment share one cost regime.

Checks:

1. PLANNER (predictive): with uniform per-heavy-op device delays,
   ``solve_dag``'s critical-path plan STRICTLY beats the best linear
   plan's predicted bottleneck on inception_tiny and on the branched
   MoE family (``moe_branched_tiny`` — the DAG-visible formulation of
   moe_tiny's fused MoE layer, one expert per branch); on the fused
   ``moe_tiny`` itself (no separable regions) the DAG solver degrades
   to exactly the linear plan — never worse.

2. QUICK (in-process thread nodes): the two-branch delay-bound
   inception_tiny chain deployed branch-parallel
   (``ChainDispatcher.deploy_topology``) vs the best linear-cut chain
   at the SAME node count — byte-identical outputs vs the serial
   composition of the deployment's own stage programs (exact), tight
   allclose vs the fused single program, and min-of-3-streams wall
   >= ``--quick-min-speedup`` better.

3. FULL (multi-process, skipped with ``--quick``): the same comparison
   with every topology vertex as a real ``defer_tpu node`` OS process
   (the deployment shape ``chain --dag`` ships), min-of-3 streams,
   measured speedup >= ``--min-speedup`` (default 1.5).  The delays
   sleep rather than burn CPU, so the win is real on a 1-core host.

Exit 0 on success; one JSON row on stdout (the ``dag_pipeline`` row of
``benchmarks/run.py``), recording planned vs linear critical path.

Usage:  python scripts/dag_smoke.py [--quick] [--delay-ms D] [--count N]
            [--min-speedup 1.5] [--quick-min-speedup 1.45]
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

TINY = 1e-6   #: per-node seconds for every non-heavy op


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def delay_costs(graph, heavy: dict) -> dict:
    """Uniform delay-bound cost map: ``heavy`` (node -> seconds) on the
    simulated-device ops, ``TINY`` elsewhere — the regime where both the
    planner's prediction and the deployed chain are bound by the same
    per-frame device time."""
    return {n: heavy.get(n, TINY) for n in graph.topo_order}


def two_branch_delays(graph, delay_s: float, join: str = "mixed_3"):
    """Per-node delays putting ``delay_s`` of simulated device time on
    EACH of the two conv branches of inception_tiny's ``join`` reduction
    region (the pool branch stays free): a linear stage must serialize
    2*delay_s, concurrent branch processes pay delay_s."""
    from defer_tpu.graph.analysis import branch_regions
    region = next(r for r in branch_regions(graph) if r.join == join)
    heavy = {}
    for b in region.branches[:2]:
        for n in b.nodes:
            heavy[n] = delay_s / len(b.nodes)
    return region, heavy


# ---------------------------------------------------------------------------
# part 1: the planner strictly beats the linear plan on branching graphs
# ---------------------------------------------------------------------------

def planner_check(delay_s: float) -> dict:
    from defer_tpu.models import inception_tiny, moe_branched_tiny, moe_tiny
    from defer_tpu.plan import StageCostModel, best_linear_plan, solve_dag

    out = {}
    cases = []
    g = inception_tiny()
    _, heavy = two_branch_delays(g, delay_s)
    cases.append((g, heavy, 5))
    g = moe_branched_tiny()
    heavy = {n: delay_s for n in g.topo_order
             if n.startswith("block_") or "_e" in n}
    # 12 processes: both 4-expert regions fan out (3 trunk segments +
    # 8 expert branches); under that, the serialized experts floor both
    # planners equally
    cases.append((g, heavy, 12))
    for g, heavy, budget in cases:
        cm = StageCostModel(g, gen="v5e", link_bw_s=1e12,
                            node_costs=delay_costs(g, heavy))
        dag = solve_dag(g, cm, num_nodes=budget)
        lin = best_linear_plan(g, cm, budget)
        assert dag.bottleneck_s < lin.bottleneck_s, (
            f"{g.name}: DAG bottleneck {dag.bottleneck_s * 1e3:.3f} ms "
            f"does not strictly beat linear "
            f"{lin.bottleneck_s * 1e3:.3f} ms at {budget} nodes")
        assert dag.parallel_regions, g.name
        log(f"planner: {g.name} @ {budget} nodes: DAG "
            f"{dag.bottleneck_s * 1e3:.3f} ms (cp "
            f"{dag.critical_path_s * 1e3:.3f} ms) vs linear "
            f"{lin.bottleneck_s * 1e3:.3f} ms -> "
            f"{lin.bottleneck_s / dag.bottleneck_s:.3f}x")
        out[g.name] = {
            "budget": budget,
            "dag_bottleneck_ms": round(dag.bottleneck_s * 1e3, 4),
            "dag_critical_path_ms": round(dag.critical_path_s * 1e3, 4),
            "linear_bottleneck_ms": round(lin.bottleneck_s * 1e3, 4),
            "predicted_speedup": round(
                lin.bottleneck_s / dag.bottleneck_s, 4)}

    # the fused MoE has no separable regions: the DAG solver must
    # degrade to exactly the linear plan, never worse
    g = moe_tiny()
    cm = StageCostModel(g, gen="v5e")
    dag = solve_dag(g, cm, num_nodes=4)
    lin = best_linear_plan(g, cm, 4)
    assert not dag.parallel_regions
    assert abs(dag.bottleneck_s - lin.bottleneck_s) <= 1e-12, (
        dag.bottleneck_s, lin.bottleneck_s)
    log(f"planner: {g.name} has no separable regions -> DAG degenerates "
        f"to the linear plan ({dag.num_stages} stages), as it must")
    out[g.name] = {"degenerate_linear": True,
                   "bottleneck_ms": round(dag.bottleneck_s * 1e3, 4)}
    return out


# ---------------------------------------------------------------------------
# shared: build the two deployments (branch-parallel vs best linear)
# ---------------------------------------------------------------------------

def build_deployments(delay_s: float):
    """(graph, params, dag topology+delays, linear topology+delays).

    Both topologies come from the SAME delay-bound cost model and the
    same node budget; per-vertex delays are the summed per-node delays
    of the vertex's slice, so the deployed chains are bound by exactly
    the seconds the planner scored."""
    import jax

    from defer_tpu import partition
    from defer_tpu.models import inception_tiny
    from defer_tpu.plan import StageCostModel, best_linear_plan, solve_dag
    from defer_tpu.runtime.topology import ChainTopology

    graph = inception_tiny()
    _, heavy = two_branch_delays(graph, delay_s)
    costs = delay_costs(graph, heavy)
    cm = StageCostModel(graph, gen="v5e", link_bw_s=1e12,
                        node_costs=costs)
    budget = 5
    dag = solve_dag(graph, cm, num_nodes=budget)
    assert dag.parallel_regions, dag.to_json()
    dag_topo = ChainTopology.from_json(dag.topology_json())
    dag_delays = {v.vid: sum(heavy.get(n, 0.0) for n in v.nodes)
                  for v in dag_topo.vertices}

    lin = best_linear_plan(graph, cm, budget)
    lin_stages = partition(graph, lin.cuts if lin.num_stages > 1 else [])
    lin_topo = ChainTopology.linear(lin_stages)
    lin_delays = {v.vid: sum(heavy.get(n, 0.0) for n in v.nodes)
                  for v in lin_topo.vertices}

    params = graph.init(jax.random.key(0))
    pred = {"dag_bottleneck_ms": round(dag.bottleneck_s * 1e3, 4),
            "dag_critical_path_ms": round(dag.critical_path_s * 1e3, 4),
            "linear_bottleneck_ms": round(lin.bottleneck_s * 1e3, 4),
            "linear_stages": lin.num_stages, "budget": budget,
            "dag_labels": [v.label for v in dag_topo.vertices]}
    return graph, params, (dag_topo, dag_delays), \
        (lin_topo, lin_delays), pred


def min_of_3_streams(disp, xs) -> float:
    """Min wall over 3 identical streams on one live deployment (this
    1-core host jitters >15% on single streams — BASELINE lesson)."""
    walls = []
    for _ in range(3):
        t0 = time.perf_counter()
        disp.stream(xs)
        walls.append(time.perf_counter() - t0)
    return min(walls)


def serial_reference(topo, stages, params, xs, batch: int):
    """Outputs of the serial composition of the deployment's OWN stage
    programs — the byte-identity reference (per-stage StableHLO vs the
    fused single program differ ~1e-6 in fusion, so THIS is the exact
    contract a distributed deployment must honor)."""
    import numpy as np

    from defer_tpu.utils.export import export_stage_bytes, \
        load_stage_program

    progs = [load_stage_program(export_stage_bytes(s, params, batch=batch))
             for s in stages]
    graph_input = topo.entry.inputs[0]
    outs = []
    for x in xs:
        vals = {}
        for v, p in zip(topo.vertices, progs):
            ins = [x if name == graph_input else vals[name]
                   for name in v.inputs]
            vals[v.output] = np.asarray(p(*ins))
        outs.append(vals[topo.exit.output])
    return outs


# ---------------------------------------------------------------------------
# part 2: in-process thread chains (quick mode)
# ---------------------------------------------------------------------------

def run_inproc(graph, params, topo, delays, xs, batch: int):
    """Thread-per-vertex deployment of ``topo``; returns (outs,
    min-of-3 wall seconds, stats)."""
    from defer_tpu.runtime.node import ChainDispatcher, StageNode

    stages = topo.stage_specs(graph)
    nodes = [StageNode(None, "127.0.0.1:0", None)
             for _ in topo.vertices]
    addrs = [f"127.0.0.1:{n.address[1]}" for n in nodes]
    threads = [threading.Thread(target=n.serve, daemon=True)
               for n in nodes]
    for t in threads:
        t.start()
    disp = ChainDispatcher(addrs[0], codec="raw")
    try:
        disp.deploy_topology(topo, stages, params, addrs, batch=batch,
                             stage_delays=delays)
        outs = disp.stream(xs)      # warm: compile + connect (untimed)
        wall = min_of_3_streams(disp, xs)
        stats = disp.stats(addrs)
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=60)
    return outs, wall, stats


def quick_check(graph, params, dag_dep, lin_dep, *, count: int,
                batch: int, min_speedup: float) -> dict:
    import numpy as np

    dag_topo, dag_delays = dag_dep
    lin_topo, lin_delays = lin_dep
    rng = np.random.default_rng(0)
    in_spec = graph.out_spec(dag_topo.entry.inputs[0])
    xs = [rng.standard_normal((batch,) + in_spec.shape).astype(np.float32)
          for _ in range(count)]

    lin_outs, lin_wall, _ = run_inproc(graph, params, lin_topo,
                                       lin_delays, xs, batch)
    dag_outs, dag_wall, stats = run_inproc(graph, params, dag_topo,
                                           dag_delays, xs, batch)
    assert len(dag_outs) == len(lin_outs) == count

    # byte-identity: the branched deployment == serial composition of
    # its own stage programs, exactly
    ref = serial_reference(dag_topo, dag_topo.stage_specs(graph),
                           params, xs, batch)
    for a, b in zip(ref, dag_outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and tight allclose vs the fused single-program forward
    import jax
    fwd = jax.jit(graph.apply)
    worst = max(float(np.abs(np.asarray(fwd(params, x)) - y).max())
                for x, y in zip(xs, dag_outs))
    assert worst < 1e-4, worst

    # every branch vertex processed every frame (broadcast, not split)
    per_branch = {s.get("branch"): s.get("processed") for s in stats
                  if s.get("branch") is not None}
    warm_total = count * 4  # warm + 3 timed streams on one connection
    assert per_branch and all(v == warm_total for v in per_branch.values()
                              ), per_branch

    speedup = lin_wall / dag_wall
    log(f"quick: linear {count * batch / lin_wall:6.1f} inf/s, "
        f"branch-parallel {count * batch / dag_wall:6.1f} inf/s -> "
        f"{speedup:.3f}x (branch split {per_branch})")
    assert speedup >= min_speedup, (
        f"in-process branch-parallel speedup {speedup:.3f}x under the "
        f"{min_speedup}x bar (linear {lin_wall:.3f}s vs dag "
        f"{dag_wall:.3f}s)")
    return {"linear_s": round(lin_wall, 4), "dag_s": round(dag_wall, 4),
            "speedup": round(speedup, 4),
            "max_abs_err_vs_single_program": worst}


# ---------------------------------------------------------------------------
# part 3: multi-process deployment — the >= 1.5x measured claim
# ---------------------------------------------------------------------------

def _free_ports(n):
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


def timed_procs(graph, params, topo, delays, xs, *, batch: int,
                log_dir: str):
    """Every topology vertex as a real ``defer_tpu node`` OS process
    (the ``chain --dag`` deployment shape): spawn, warm, min-of-3
    streams, teardown.  Returns (outs, wall_s)."""
    from defer_tpu.runtime.node import (ChainDispatcher, _await_binds,
                                        _kill_procs, dag_vertex_argv)
    from defer_tpu.utils.export import export_stage

    stages = topo.stage_specs(graph)
    vs = topo.vertices
    ports = _free_ports(len(vs) + 1)
    addrs = [f"127.0.0.1:{ports[i]}" for i in range(len(vs))]
    result = f"127.0.0.1:{ports[-1]}"

    argvs = []
    for v, stage in zip(vs, stages):
        path = os.path.join(log_dir, f"vertex_{v.vid}.zip")
        if not os.path.exists(path):
            export_stage(stage, params, path, batch=batch)
        argvs.append(dag_vertex_argv(v, path, addrs=addrs,
                                     result_addr=result, codec="raw",
                                     stage_delays=delays))

    child_env = dict(os.environ)
    child_env.update(CPU_ENV)
    procs, logs = [], []
    labels = [v.label for v in vs]
    failed = True
    try:
        for v, argv in zip(vs, argvs):
            lf = open(os.path.join(
                log_dir, f"node_{v.label.replace('.', '_')}.log"), "w+")
            logs.append(lf)
            procs.append(subprocess.Popen(argv, env=child_env, stdout=lf,
                                          stderr=subprocess.STDOUT))
        _await_binds(procs, labels, logs, addrs,
                     proc_of=list(range(len(vs))))
        disp = ChainDispatcher(addrs[0], listen=result, codec="raw")
        try:
            outs = disp.stream(xs)   # boot+compile excluded from window
            wall = min_of_3_streams(disp, xs)
            failed = False
        finally:
            if failed:
                _kill_procs(procs)   # dead sockets make close() fast
            disp.close()
            if not failed:
                for pr in procs:
                    try:
                        pr.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        pr.kill()
    except BaseException:
        _kill_procs(procs)
        raise
    finally:
        for lf in logs:
            lf.close()
    return outs, wall


def speedup_check(graph, params, dag_dep, lin_dep, *, count: int,
                  batch: int, min_speedup: float) -> dict:
    import numpy as np

    from defer_tpu.runtime.node import _BindRace

    def with_retry(**kw):
        for attempt in range(3):
            try:
                return timed_procs(**kw)
            except _BindRace as e:
                log(f"bind race on attempt {attempt + 1} ({e}); retrying")
        return timed_procs(**kw)

    dag_topo, dag_delays = dag_dep
    lin_topo, lin_delays = lin_dep
    rng = np.random.default_rng(1)
    in_spec = graph.out_spec(dag_topo.entry.inputs[0])
    xs = [rng.standard_normal((batch,) + in_spec.shape).astype(np.float32)
          for _ in range(count)]
    with tempfile.TemporaryDirectory(prefix="defer_dag_smoke_") as tmp:
        lin_dir = os.path.join(tmp, "lin")
        dag_dir = os.path.join(tmp, "dag")
        os.makedirs(lin_dir)
        os.makedirs(dag_dir)
        lin_outs, lin_wall = with_retry(
            graph=graph, params=params, topo=lin_topo, delays=lin_delays,
            xs=xs, batch=batch, log_dir=lin_dir)
        log(f"linear:          {count * batch / lin_wall:8.1f} inf/s "
            f"({lin_wall:.2f}s min-of-3)")
        dag_outs, dag_wall = with_retry(
            graph=graph, params=params, topo=dag_topo, delays=dag_delays,
            xs=xs, batch=batch, log_dir=dag_dir)
        log(f"branch-parallel: {count * batch / dag_wall:8.1f} inf/s "
            f"({dag_wall:.2f}s min-of-3)")
    assert len(dag_outs) == len(lin_outs) == count
    ref = serial_reference(dag_topo, dag_topo.stage_specs(graph),
                           params, xs, batch)
    for a, b in zip(ref, dag_outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    speedup = lin_wall / dag_wall
    assert speedup >= min_speedup, (
        f"branch-parallel speedup {speedup:.3f}x under the "
        f"{min_speedup}x bar (linear {lin_wall:.2f}s vs dag "
        f"{dag_wall:.2f}s, min-of-3)")
    return {"linear_s": round(lin_wall, 4), "dag_s": round(dag_wall, 4),
            "speedup": round(speedup, 4),
            "linear_inf_s": round(count * batch / lin_wall, 2),
            "dag_inf_s": round(count * batch / dag_wall, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--min-speedup", type=float, default=1.5,
                    help="required dag/linear wall ratio (multi-process)")
    ap.add_argument("--quick-min-speedup", type=float, default=1.45,
                    help="required ratio for the in-process quick check "
                         "(thread scheduling noise, slightly lower bar)")
    ap.add_argument("--count", type=int, default=12,
                    help="frames per timed stream")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--delay-ms", type=float, default=40.0,
                    help="simulated device seconds per heavy branch")
    ap.add_argument("--quick", action="store_true",
                    help="planner + in-process checks only (no spawns)")
    args = ap.parse_args()

    delay_s = args.delay_ms / 1e3
    r_planner = planner_check(delay_s)
    graph, params, dag_dep, lin_dep, pred = build_deployments(delay_s)
    log(f"deploying {pred['dag_labels']} vs {pred['linear_stages']} "
        f"linear stages @ {pred['budget']} nodes")
    r_quick = quick_check(graph, params, dag_dep, lin_dep,
                          count=min(args.count, 10), batch=args.batch,
                          min_speedup=args.quick_min_speedup)

    row = {"metric": "dag_pipeline", "unit": "x_vs_linear_chain",
           "model": graph.name, "count": args.count, "batch": args.batch,
           "delay_ms": args.delay_ms, "cpu_count": os.cpu_count() or 1,
           "planned": pred, "planner": r_planner, "quick": r_quick}
    if args.quick:
        row["value"] = None
    else:
        r = speedup_check(graph, params, dag_dep, lin_dep,
                          count=args.count, batch=args.batch,
                          min_speedup=args.min_speedup)
        row.update({"value": r["speedup"],
                    **{k: v for k, v in r.items() if k != "speedup"}})
    print(json.dumps(row))
    log("dag pipeline smoke: OK")


if __name__ == "__main__":
    main()
