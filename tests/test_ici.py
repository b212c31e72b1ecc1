"""Device-resident transport tier (docs/TRANSPORT.md): live jax.Array
handoff, same-mesh negotiation + ladder order, host-sync observability,
and the planner's ici pseudo-codec + host_sync term — the in-process
halves of ``scripts/ici_smoke.py``.
"""

import socket
import threading

import numpy as np
import pytest

import jax

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.obs import REGISTRY
from defer_tpu.runtime.node import (ChainDispatcher, StageNode,
                                    _normalize_hop_tiers)
from defer_tpu.transport.framed import (K_CTRL, K_TENSOR, K_TENSOR_SEQ,
                                        PROTOCOL_VERSION, recv_frame,
                                        send_ctrl)
from defer_tpu.transport.ici import (IciPipe, IciSender, grant_ici,
                                     offer_ici)
from defer_tpu.transport.shm import answer_tier_probe, offer_tier_ladder


def _counter(name: str) -> float:
    return REGISTRY.counter(name).value


def _hist_count(name: str) -> int:
    return int(REGISTRY.histogram(name).summary().get("count", 0))


# ---------------------------------------------------------------------------
# pipe semantics: live arrays, device placement
# ---------------------------------------------------------------------------

def test_pipe_same_device_passes_live_array_by_reference():
    p = IciPipe(depth=4)
    x = jax.device_put(np.arange(8, dtype=np.float32), jax.devices()[0])
    p.sender.dest_device = jax.devices()[0]
    p.sender.send(x)
    p.sender.send(x * 2, seq=5)
    p.sender.send_end()
    kind, got = p.receiver.get(1.0)
    assert kind == K_TENSOR and got is x  # BY REFERENCE: zero copies
    kind, (seq, got2) = p.receiver.get(1.0)
    assert kind == K_TENSOR_SEQ and seq == 5
    np.testing.assert_array_equal(np.asarray(got2), np.arange(8) * 2)
    assert p.sender.d2d == 0 and p.sender.device_pairs == set()


def test_pipe_cross_device_send_pays_exactly_one_device_put(host_devices):
    d0, d1 = host_devices[0], host_devices[1]
    p = IciPipe(depth=4)
    p.sender.dest_device = d1
    x = jax.device_put(np.arange(8, dtype=np.float32), d0)
    before = _counter("transport.ici_d2d")
    p.sender.send(x)
    kind, got = p.receiver.get(1.0)
    assert kind == K_TENSOR and got is not x
    assert next(iter(got.devices())).id == d1.id
    np.testing.assert_array_equal(np.asarray(got), np.asarray(x))
    assert p.sender.d2d == 1
    assert p.sender.device_pairs == {(d0.id, d1.id)}
    assert _counter("transport.ici_d2d") == before + 1
    # a host (numpy) input is uploaded but is NOT a d2d transfer
    p.sender.send(np.ones(4, dtype=np.float32))
    _, got_np = p.receiver.get(1.0)
    assert next(iter(got_np.devices())).id == d1.id
    assert p.sender.d2d == 1


# ---------------------------------------------------------------------------
# grant validation: same process AND same mesh
# ---------------------------------------------------------------------------

def _probe(pipe: IciPipe, **over) -> dict:
    from defer_tpu.transport import ici as ici_mod
    import os
    token = ici_mod._register(pipe)
    msg = {"cmd": "tier_probe", "want": "ici", "pid": os.getpid(),
           "proto": PROTOCOL_VERSION, "token": token,
           "backend": jax.default_backend(),
           "platform": jax.devices()[0].platform,
           "device_ids": [jax.devices()[0].id]}
    msg.update(over)
    return msg


def test_grant_checks_in_order():
    assert grant_ici(_probe(IciPipe())) is not None
    assert grant_ici(_probe(IciPipe(), proto=PROTOCOL_VERSION + 1)) is None
    assert grant_ici(_probe(IciPipe(), pid=1)) is None
    assert grant_ici(_probe(IciPipe(), backend="tpu9")) is None
    # the same-mesh proof: an unresolvable device id refuses the grant
    assert grant_ici(_probe(IciPipe(), device_ids=[10 ** 6])) is None
    assert grant_ici(_probe(IciPipe(), device_ids=[])) is None
    assert grant_ici(_probe(IciPipe(), platform="warp")) is None
    msg = _probe(IciPipe())
    assert grant_ici(msg) is not None
    assert grant_ici(msg) is None  # token claims exactly once


# ---------------------------------------------------------------------------
# ladder order (the satellite regression): ici > local > shm > tcp
# ---------------------------------------------------------------------------

def _ladder_peer(conn, *, grants: dict):
    """Serve tier probes on ``conn``: grant a want iff grants[want]."""
    rx = None

    def run():
        nonlocal rx
        while True:
            kind, msg = recv_frame(conn)
            if kind != K_CTRL:
                return
            want = msg.get("want")
            if grants.get(want):
                _, rx = answer_tier_probe(conn, msg, accept=True,
                                          device=None)
                return
            send_ctrl(conn, {"cmd": "tier_reply", "tier": "tcp"})

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, lambda: rx


def test_auto_ladder_prefers_ici_over_local():
    a, b = socket.socketpair()
    t, _rx = _ladder_peer(b, grants={"ici": True, "local": True})
    fb0 = _counter("transport.tier_fallback")
    tier, tx, fell = offer_tier_ladder(a, tier="auto", hop="t")
    t.join(timeout=5)
    assert tier == "ici" and isinstance(tx, IciSender) and not fell
    assert _counter("transport.tier_fallback") == fb0
    a.close(), b.close()


def test_refused_ici_degrades_to_local_not_tcp():
    """ici refused (foreign mesh) but local granted: the hop lands on
    local — NOT tcp — and no fallback is recorded (a granted rung is
    not a degradation)."""
    a, b = socket.socketpair()
    t, _rx = _ladder_peer(b, grants={"ici": False, "local": True})
    fb0 = _counter("transport.tier_fallback")
    tier, tx, fell = offer_tier_ladder(a, tier="auto", hop="t")
    t.join(timeout=5)
    assert tier == "local" and tx is not None and not fell
    assert _counter("transport.tier_fallback") == fb0
    a.close(), b.close()


def test_all_rungs_refused_counts_exactly_one_fallback():
    a, b = socket.socketpair()

    def refuse_all():
        for _ in range(3):  # ici, local, shm
            kind, msg = recv_frame(b)
            assert kind == K_CTRL
            send_ctrl(b, {"cmd": "tier_reply", "tier": "tcp"})

    t = threading.Thread(target=refuse_all, daemon=True)
    t.start()
    fb0 = _counter("transport.tier_fallback")
    hop0 = _counter("transport.tier_fallback.hopX")
    tier, tx, fell = offer_tier_ladder(a, tier="auto", hop="hopX")
    t.join(timeout=5)
    assert (tier, tx, fell) == ("tcp", None, True)
    assert _counter("transport.tier_fallback") == fb0 + 1
    assert _counter("transport.tier_fallback.hopX") == hop0 + 1
    a.close(), b.close()


def test_pinned_tiers_offer_only_their_rung():
    """``--tier shm``/``local``/``ici`` pins suppress every other offer
    — the audit half of the delay-codec-bench satellite: a pinned hop
    sends exactly one probe, and ``tcp`` sends none (covered by the
    chain fixture's tcp baseline)."""
    for pin, n_probes in (("shm", 1), ("local", 1), ("ici", 1)):
        a, b = socket.socketpair()
        wants = []

        def peer():
            while True:
                try:
                    kind, msg = recv_frame(b)
                except (ConnectionError, OSError):
                    return
                if kind != K_CTRL:
                    return
                wants.append(msg.get("want"))
                send_ctrl(b, {"cmd": "tier_reply", "tier": "tcp"})

        t = threading.Thread(target=peer, daemon=True)
        t.start()
        tier, tx, fell = offer_tier_ladder(a, tier=pin, hop="t")
        assert (tier, tx, fell) == ("tcp", None, True)
        assert wants == [pin], f"pin {pin} leaked offers: {wants}"
        a.close(), b.close()
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# in-process chains: device-resident end to end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


def _run_chain_inproc(stages, params, xs, *, tier, devices=None,
                      accepts=None):
    n = len(stages)
    nodes = [StageNode(None, "127.0.0.1:0", None, tier=tier,
                       tier_accept=True if accepts is None else accepts[i])
             for i in range(n)]
    addrs = [f"127.0.0.1:{nd.address[1]}" for nd in nodes]
    threads = [threading.Thread(target=nd.serve, daemon=True)
               for nd in nodes]
    for t in threads:
        t.start()
    disp = ChainDispatcher(addrs[0], codec="raw", tier=tier)
    try:
        disp.deploy(stages, params, addrs, batch=xs[0].shape[0],
                    tiers=[tier] * n, devices=devices)
        outs = disp.stream(xs)
        stats = disp.stats(addrs)
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=60)
    return outs, stats, disp


@pytest.fixture(scope="module")
def chain3(tiny):
    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(7)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(5)]
    outs, stats, _ = _run_chain_inproc(stages, params, xs, tier="tcp")
    return g, params, stages, xs, outs, stats


def test_tcp_pin_suppresses_every_offer(chain3):
    """The delay-codec benches pin ``--tier tcp`` so shm cannot bypass
    their codecs — the pin must suppress the new ici offer too: the
    tcp baseline chain moved zero frames through ici (or local) pipes
    and negotiated tcp everywhere."""
    _, _, _, xs, _, stats = chain3
    assert [s["tier"] for s in stats] == ["tcp"] * 3
    assert [s["tier_in"] for s in stats] == [None] * 3  # never probed
    assert [s["ici_d2d"] for s in stats] == [0] * 3


def test_ici_chain_device_resident_end_to_end(chain3, host_devices):
    """The tentpole acceptance, in-process half: every hop (dispatcher
    edges included) negotiates ici under ``auto``, outputs are
    byte-identical to the all-TCP chain, ZERO ``codec.*`` and ZERO
    ``host_sync`` samples land on any ici hop (the round-trip is GONE,
    not just cheaper), at least one hop performs a real cross-device
    ``device_put`` with distinct (src, dst) device ids, and the ONE
    host sync per frame happens at the dispatcher's result edge."""
    g, params, stages, xs, base, _ = chain3
    enc0, dec0 = _hist_count("codec.encode_s"), _hist_count("codec.decode_s")
    hs0 = _hist_count("node.host_sync_s")
    chs0 = _hist_count("chain.host_sync_s")
    if0 = _counter("transport.ici_frames")
    outs, stats, disp = _run_chain_inproc(stages, params, xs,
                                          tier="auto",
                                          devices=[0, 1, 2])
    assert [s["tier"] for s in stats] == ["ici"] * 3
    assert [s["tier_in"] for s in stats] == ["ici"] * 3
    assert (disp.tier_out, disp.tier_in) == ("ici", "ici")
    assert [s["device"] for s in stats] == [0, 1, 2]
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # zero codec work AND zero host syncs on the stage nodes
    assert _hist_count("codec.encode_s") == enc0
    assert _hist_count("codec.decode_s") == dec0
    assert _hist_count("node.host_sync_s") == hs0
    assert [s["host_sync_s"]["count"] for s in stats] == [0] * 3
    # real cross-device transfers: stage0 -> dev1, stage1 -> dev2
    assert stats[0]["ici_d2d"] == len(xs)
    assert stats[0]["ici_device_pairs"] == [[0, 1]]
    assert stats[1]["ici_device_pairs"] == [[1, 2]]
    # 4 hops (disp->s0->s1->s2->result) x frames rode the ici pipes...
    assert _counter("transport.ici_frames") - if0 == 4 * len(xs)
    # ...and the result edge host-synced exactly once per frame
    assert _hist_count("chain.host_sync_s") - chs0 == len(xs)


def test_local_chain_pays_host_sync_ici_removes(chain3):
    """The host-sync observability satellite: a local-tier chain
    records exactly one host_sync sample per frame per stage — the
    measured cost the planner's host_sync term models and the ici
    chain's zero count proves gone."""
    g, params, stages, xs, _, _ = chain3
    outs, stats, _ = _run_chain_inproc(stages, params, xs, tier="local")
    assert [s["tier"] for s in stats] == ["local"] * 3
    assert [s["host_sync_s"]["count"] for s in stats] == [len(xs)] * 3
    assert all(s["host_sync_s"]["max"] >= 0 for s in stats)


def test_refused_ici_chain_degrades_with_labeled_fallback(chain3):
    """A pinned ici hop whose peer refuses degrades to tcp with the
    stream byte-identical and the hop's fallback attributable."""
    g, params, stages, xs, base, _ = chain3
    before = _counter("transport.tier_fallback")
    outs, stats, _ = _run_chain_inproc(stages, params, xs, tier="ici",
                                       accepts=[True, False, True])
    assert _counter("transport.tier_fallback") > before
    by_stage = {s["stage"]: s for s in stats}
    assert by_stage[0]["tier"] == "tcp"    # its offer was refused
    assert by_stage[0]["tier_fallbacks"] == 1
    assert by_stage[1]["tier"] == "ici"    # stage 2 still granted
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_extension_dtype_roundtrips_every_tier():
    """bfloat16 activations (ops.Cast — the TPU-native regime) cross
    tcp frames AND shm ring descriptors as themselves: extension
    dtypes ship by NAME when numpy's ``.str`` is an opaque void alias
    (``wire_dtype``/``dtype_from_wire``), so a bf16 boundary is
    byte-identical across every tier instead of decoding as raw
    bytes."""
    from defer_tpu import GraphBuilder
    from defer_tpu.graph import ops

    b = GraphBuilder("bf16chain")
    x = b.input((32,))
    x = b.add(ops.Dense(32), x, name="d0")
    x = b.add(ops.Cast("bfloat16"), x, name="half")
    b.add(ops.Dense(16), x, name="head")
    g = b.build()
    params = g.init(jax.random.key(1))
    stages = partition(g, ["d0", "half"])  # hop 1->2 carries bf16
    rng = np.random.default_rng(2)
    xs = [rng.standard_normal((1, 32)).astype(np.float32)
          for _ in range(3)]
    base, _, _ = _run_chain_inproc(stages, params, xs, tier="tcp")
    assert np.asarray(base[0]).dtype == np.dtype("bfloat16")
    for tier in ("shm", "auto"):
        outs, stats, _ = _run_chain_inproc(stages, params, xs, tier=tier)
        assert stats[1]["tier"] == ("shm" if tier == "shm" else "ici")
        for a, bb in zip(base, outs):
            assert np.asarray(bb).dtype == np.dtype("bfloat16")
            np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))


# ---------------------------------------------------------------------------
# guard rails
# ---------------------------------------------------------------------------

def test_ici_pin_on_fan_role_node_rejected():
    with pytest.raises(ValueError, match="fan paths"):
        StageNode(None, "127.0.0.1:0", None, tier="ici", replica=0)
    with pytest.raises(ValueError, match="fan paths"):
        StageNode(None, "127.0.0.1:0", None, tier="ici", branch=1)
    with pytest.raises(ValueError, match="fan paths"):
        StageNode(None, "127.0.0.1:0", "127.0.0.1:1,127.0.0.1:2",
                  tier="ici")


def test_ici_hop_tiers_validation():
    # adjacent replication never composes with a device-resident hop
    with pytest.raises(ValueError, match="replicated"):
        _normalize_hop_tiers(["ici", "tcp"], 3, [1, 2, 1], "tcp")
    # the chain-wide default expansion is validated the same way
    with pytest.raises(ValueError, match="replicated"):
        _normalize_hop_tiers(None, 3, [1, 2, 1], "ici")
    assert _normalize_hop_tiers(["ici", "auto"], 3, [1, 1, 1], "tcp") \
        == ["ici", "auto"]
    with pytest.raises(ValueError, match="ici"):
        _normalize_hop_tiers(["warp"], 2, [1, 1], "tcp")


def test_ici_hop_tiers_require_overlap(tiny):
    from defer_tpu.runtime.node import run_chain
    g, params = tiny
    stages = partition(g, num_stages=3)
    with pytest.raises(ValueError, match="overlap"):
        run_chain(stages, params, [], overlap=False,
                  hop_tiers=["ici", "tcp"])


def test_chain_level_ici_tier_rejected_loudly(tiny):
    """tier='ici'/'local' as the CHAIN tier also claims the dispatcher
    edges — always cross-process in a spawned chain, so the pin could
    only silently degrade; rejected with a pointer at hop_tiers."""
    from defer_tpu.runtime.node import run_chain
    g, params = tiny
    stages = partition(g, num_stages=3)
    for t in ("ici", "local"):
        with pytest.raises(ValueError, match="hop_tiers"):
            run_chain(stages, params, [], tier=t)


def test_device_pin_validation(tiny):
    g, params = tiny
    with pytest.raises(ValueError, match="out of range"):
        StageNode(None, "127.0.0.1:0", None, device=99)
    from defer_tpu.runtime.node import run_chain
    stages = partition(g, num_stages=3)
    with pytest.raises(ValueError, match="out of range"):
        run_chain(stages, params, [], device_map={9: 0})
    with pytest.raises(ValueError, match="host mesh"):
        run_chain(stages, params, [], devices=2, device_map={0: 5})
    with pytest.raises(ValueError, match=">= 0"):
        run_chain(stages, params, [], device_map={0: -1})
    # device-tier fusion renumbers stages: a pre-fusion pin would land
    # on the wrong stage silently — rejected loudly instead
    with pytest.raises(ValueError, match="fusion"):
        run_chain(stages, params, [], device_map={2: 1},
                  hop_tiers=["device", "ici"])


def test_force_host_device_count_after_init_skips_with_reason():
    from defer_tpu.utils.compat import force_host_device_count
    import os
    flags = os.environ.get("XLA_FLAGS")
    ok, why = force_host_device_count(len(jax.devices()))
    assert ok, why
    ok, why = force_host_device_count(len(jax.devices()) + 1)
    assert not ok and "already initialized" in why
    # a refused request leaves the environment (children inherit it) alone
    assert os.environ.get("XLA_FLAGS") == flags


# ---------------------------------------------------------------------------
# planner: the ici pseudo-codec + host_sync term
# ---------------------------------------------------------------------------

def _fat_boundary_model():
    from defer_tpu import GraphBuilder
    from defer_tpu.graph import ops
    from defer_tpu.plan import StageCostModel

    b = GraphBuilder("fatcut")
    x = b.input((4096,))
    for i in range(3):
        x = b.add(ops.Dense(4096), x, name=f"d{i}")
    x = b.add(ops.Dense(8), x, name="head")
    g = b.build()
    costs = {"d0": 1e-3, "d1": 1e-3, "d2": 1e-3, "head": 1e-4}
    return g, StageCostModel(g, gen="v4", link_bw_s=1e6, node_costs=costs)


def test_tier_ordering_is_principled():
    """The acceptance bar: device <= ici <= local <= shm <= tcp,
    STRICT on a fat boundary — because every non-device-resident tier
    pays the host_sync round-trip and ici pays only the interconnect."""
    g, cm = _fat_boundary_model()
    cost = {t: cm.with_hop_tiers({"d1": t}).comm_seconds("d1", t)
            for t in ("device", "ici", "local", "shm")}
    cost["tcp"] = cm.best_codec("d1")[1]
    assert cost["device"] < cost["ici"] < cost["local"] \
        < cost["shm"] < cost["tcp"]
    # the ici hop is exactly the interconnect pass: no host term at all
    assert cost["ici"] == pytest.approx(cm.cut_bytes("d1") / cm.ici_bw_s)
    # everything else carries the host_sync round-trip
    hs = cm.host_sync_seconds("d1")
    assert hs > 0
    assert cost["local"] == pytest.approx(
        cm.cut_bytes("d1") / cm.local_bw_s + hs)


def test_solver_exploits_ici_map_and_json_roundtrip():
    from defer_tpu.plan import plan_from_json, replan, solve

    g, cm = _fat_boundary_model()
    p_tcp = solve(g, 3, cm)
    tiers = {c: "ici" for c in ("d0", "d1", "d2")}
    p_ici = solve(g, 3, cm, hop_tiers=tiers)
    assert p_ici.bottleneck_s < p_tcp.bottleneck_s  # STRICT: comm-bound
    assert set(p_ici.codecs) == {"ici"}
    doc = p_ici.to_json()
    assert doc["hop_tiers"] == ["ici", "ici"]
    assert doc["cost_model"]["ici_bw_s"] == cm.ici_bw_s
    assert doc["cost_model"]["host_sync_bw_s"] == cm.host_sync_bw_s
    assert plan_from_json(doc).hop_tiers == ["ici", "ici"]
    # the tier (and its bandwidths) survive a replan
    rp = replan(g, p_ici, {0: 2e-3, 1: 1e-3, 2: 1e-3},
                cm.with_hop_tiers(tiers))
    assert set(rp.new_plan.hop_tiers) == {"ici"}
    assert set(rp.old_plan_corrected.hop_tiers) == {"ici"}


def test_ici_tier_never_applies_to_fan_hops():
    g, cm = _fat_boundary_model()
    cm = cm.with_hop_tiers({"d1": "ici"})
    name, s = cm.best_codec_replicated("d1", 1, 1)
    assert name == "ici"
    name2, s2 = cm.best_codec_replicated("d1", 2, 1)
    assert name2 != "ici" and s2 > s


def test_dag_fan_boundary_rejects_ici():
    """Acceptance bar: a hop-tier map with ici on a fan boundary (the
    fork of a branch region) is rejected loudly."""
    from defer_tpu.graph.ir import GraphBuilder
    from defer_tpu.graph import ops
    from defer_tpu.plan import StageCostModel
    from defer_tpu.plan.dag import solve_dag

    b = GraphBuilder("fork2")
    x = b.add(ops.Dense(16), b.input((16,)), name="stem")
    left = b.add(ops.Dense(16), x, name="l0")
    right = b.add(ops.Dense(16), x, name="r0")
    b.add(ops.Add(), [left, right], name="merge")
    g = b.build()
    costs = {n: 1e-3 for n in g.topo_order}
    cm = StageCostModel(g, gen="v5e", link_bw_s=1e12, node_costs=costs)
    with pytest.raises(ValueError, match="wire-framed"):
        solve_dag(g, cm, num_nodes=4, hop_tiers={"stem": "ici"})


def test_monitor_renders_host_sync_column(capsys):
    from defer_tpu.cli import _render_monitor
    row = {"stage": 0, "replica": None, "branch": None, "join": 0,
           "tier": "ici", "tier_fallbacks": 0, "alive": True,
           "throughput_per_s": 1.0,
           "infer_ms": {"p50": 1.0, "p95": 2.0, "p99": 3.0},
           "host_sync_ms": {"p50": 0.0, "count": 0},
           "rx_q": 0, "tx_q": 0, "rx_hi": 0, "tx_hi": 0, "inflight": 0,
           "rx_bytes_per_s": 0, "tx_bytes_per_s": 0, "processed": 5,
           "addr": "x"}
    row2 = dict(row, stage=1, tier="local",
                host_sync_ms={"p50": 1.25, "count": 5})
    _render_monitor([row, row2], None, [], {}, clear=False)
    out = capsys.readouterr().out
    assert "HS50" in out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert "-" in lines[1].split()  # zero samples renders the proof mark
    assert "1.250" in lines[2]
