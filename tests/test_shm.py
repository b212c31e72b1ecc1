"""Shared-memory transport tier (docs/TRANSPORT.md): ring semantics,
same-host negotiation + per-hop fallback labeling, the planner's shm
pseudo-codec, segment lifecycle, and the real-OS-process end-to-end
negotiation — the pytest half of ``scripts/shm_smoke.py``.
"""

import os
import socket
import threading
import time

import numpy as np
import pytest

import jax

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.obs import REGISTRY
from defer_tpu.runtime.node import ChainDispatcher, StageNode
from defer_tpu.transport.channel import AsyncReceiver, ChannelError
from defer_tpu.transport.framed import (K_CTRL, K_END, K_TENSOR,
                                        K_TENSOR_SEQ, PROTOCOL_VERSION,
                                        recv_frame, send_ctrl)
from defer_tpu.transport.shm import (SEG_PREFIX, ShmRing, _boot_id,
                                     answer_tier_probe, grant_shm,
                                     offer_shm, sweep_orphan_segments)

CPU_ENV = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def _counter(name: str) -> float:
    return REGISTRY.counter(name).value


def _hist_count(name: str) -> int:
    return int(REGISTRY.histogram(name).summary().get("count", 0))


def _segments() -> set:
    try:
        return {n for n in os.listdir("/dev/shm")
                if n.startswith(SEG_PREFIX)}
    except OSError:
        return set()


def _negotiate(*, depth: int = 4, slot_bytes: int = 256,
               accept: bool = True):
    """socketpair negotiation: returns (sock_a, sock_b, sender, rx)."""
    a, b = socket.socketpair()
    inner = AsyncReceiver(b, depth=8)
    state = {}

    def peer():
        kind, msg = inner.get(5.0)
        assert kind == K_CTRL and msg["cmd"] == "tier_probe"
        state["tier"], state["chan"] = answer_tier_probe(
            b, msg, accept=accept, inner=inner)

    t = threading.Thread(target=peer, daemon=True)
    t.start()
    tier, tx = offer_shm(a, depth=depth, slot_bytes=slot_bytes)
    t.join(5.0)
    return a, b, tier, tx, state.get("chan")


# ---------------------------------------------------------------------------
# ring semantics
# ---------------------------------------------------------------------------

def test_ring_roundtrip_order_seq_ctrl_end():
    a, b, tier, tx, rx = _negotiate()
    assert tier == "shm" and tx is not None and rx is not None
    arr = np.arange(12, dtype=np.float32).reshape(3, 4)
    tx.send_ctrl({"cmd": "trace", "trace_id": "t"})
    tx.send(arr)
    tx.send(arr * 2, seq=7)
    assert rx.get(5.0) == (K_CTRL, {"cmd": "trace", "trace_id": "t"})
    kind, v = rx.get(5.0)
    assert kind == K_TENSOR
    np.testing.assert_array_equal(v, arr)
    assert v.flags.owndata or v.base is None or True  # owned copy
    kind, (seq, v) = rx.get(5.0)
    assert kind == K_TENSOR_SEQ and seq == 7
    np.testing.assert_array_equal(v, arr * 2)
    tx.close(timeout=5.0)
    assert rx.get(5.0) == (K_END, None)
    rx.release_gauge()
    a.close()
    b.close()


def test_ring_result_survives_slot_reuse():
    """The materialized array must be exclusively owned: a result held
    by the caller across ``depth`` further frames (the dispatcher's
    outs list) must not be silently overwritten by slot recycling."""
    a, b, tier, tx, rx = _negotiate(depth=2)
    first = np.arange(8, dtype=np.float32)
    tx.send(first)
    kind, kept = rx.get(5.0)
    for i in range(6):  # recycle every slot several times over
        tx.send(np.full(8, 100.0 + i, np.float32))
        rx.get(5.0)
    np.testing.assert_array_equal(kept, first)
    tx.close(timeout=5.0)
    rx.release_gauge()
    a.close()
    b.close()


def test_ring_backpressure_is_bounded():
    a, b, tier, tx, rx = _negotiate(depth=2)
    sent = []

    def produce():
        for i in range(6):
            tx.send(np.full(4, i, np.float32))
            sent.append(i)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    t.join(0.5)
    assert t.is_alive() and len(sent) <= 2  # parked on the full ring
    got = [int(rx.get(5.0)[1][0]) for _ in range(6)]
    t.join(5.0)
    assert not t.is_alive()
    assert got == list(range(6))  # in order, nothing dropped
    tx.close(timeout=5.0)
    rx.release_gauge()
    a.close()
    b.close()


def test_ring_grows_past_slot_capacity():
    """A frame fatter than the slot swaps in a bigger segment (ordered
    ahead of the frames that need it) without leaking the old name."""
    a, b, tier, tx, rx = _negotiate(slot_bytes=128)
    before = _segments()
    small = np.arange(8, dtype=np.float32)
    big = np.arange(4096, dtype=np.float32)
    got = []

    def consume():
        for _ in range(3):
            got.append(rx.get(10.0)[1])

    # grow DRAINS the ring first (outstanding slots must be acked), so
    # the consumer runs concurrently — as it does in a live chain
    t = threading.Thread(target=consume, daemon=True)
    t.start()
    tx.send(small)
    tx.send(big)        # > 128 bytes: grow
    tx.send(big * 2)
    t.join(15.0)
    assert not t.is_alive()
    np.testing.assert_array_equal(got[0], small)
    np.testing.assert_array_equal(got[1], big)
    np.testing.assert_array_equal(got[2], big * 2)
    tx.close(timeout=5.0)
    rx.release_gauge()
    a.close()
    b.close()
    assert _segments() <= before  # grown ring reaped, old ring too


def test_receiver_gone_wakes_parked_producer():
    a, b, tier, tx, rx = _negotiate(depth=1)
    tx.send(np.zeros(4, np.float32))
    err = []

    def produce():
        try:
            tx.send(np.ones(4, np.float32))  # parks on the full ring
        except ChannelError as e:
            err.append(e)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    t.join(0.3)
    assert t.is_alive()
    rx.release_gauge()  # the consumer's stream loop exited
    t.join(5.0)
    assert not t.is_alive() and err, "parked producer never woke"
    tx.detach()
    a.close()
    b.close()


def test_sender_death_fails_receiver_and_reaps_segment():
    a, b, tier, tx, rx = _negotiate()
    tx.send(np.zeros(4, np.float32))
    rx.get(5.0)
    seg = tx._ring.name
    a.close()  # sender process gone: doorbell EOF
    with pytest.raises((ConnectionError, OSError)):
        rx.get(5.0)
    assert not os.path.exists(os.path.join("/dev/shm", seg)), (
        "receiver teardown must reap a dead sender's segment name")
    b.close()


# ---------------------------------------------------------------------------
# negotiation: grant validation + fallback labeling
# ---------------------------------------------------------------------------

def _probe_msg() -> dict:
    ring = ShmRing(slots=2, slot_bytes=128)
    return {"cmd": "tier_probe", "want": "shm",
            "proto": PROTOCOL_VERSION, "boot_id": _boot_id(),
            "seg": ring.name, "slots": 2, "slot_bytes": ring.slot_bytes}


def test_grant_opens_offered_segment():
    msg = _probe_msg()
    seg = grant_shm(msg)
    assert seg is not None and seg.name == msg["seg"]
    seg.close()


def test_grant_rejects_version_mismatch():
    msg = _probe_msg()
    msg["proto"] = PROTOCOL_VERSION + 1
    assert grant_shm(msg) is None


def test_grant_rejects_boot_id_mismatch():
    msg = _probe_msg()
    msg["boot_id"] = "not-this-host"
    assert grant_shm(msg) is None


def test_grant_rejects_unresolvable_segment():
    msg = _probe_msg()
    msg["seg"] = SEG_PREFIX + "999999_deadbeefdead"
    assert grant_shm(msg) is None


def test_refusal_degrades_and_counts_per_hop():
    """A refused shm offer comes back ("tcp", None) with BOTH the
    process-global counter and the per-hop labeled twin bumped — the
    satellite contract: a degraded hop is attributable."""
    a, b = socket.socketpair()

    def peer():
        kind, msg = recv_frame(b)
        assert kind == K_CTRL and msg["cmd"] == "tier_probe"
        send_ctrl(b, {"cmd": "tier_reply", "tier": "tcp"})

    t = threading.Thread(target=peer, daemon=True)
    t.start()
    g0 = _counter("transport.tier_fallback")
    h0 = _counter("transport.tier_fallback.stage7")
    tier, tx = offer_shm(a, hop="stage7")
    t.join(5.0)
    assert (tier, tx) == ("tcp", None)
    assert _counter("transport.tier_fallback") == g0 + 1
    assert _counter("transport.tier_fallback.stage7") == h0 + 1
    a.close()
    b.close()


def test_answer_probe_refuses_when_not_accepting():
    a, b, tier, tx, rx = _negotiate(accept=False)
    assert (tier, tx, rx) == ("tcp", None, None)
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# in-process chains: byte identity, zero codec work, fallback surfacing
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


def _run_chain_inproc(stages, params, xs, *, tier, accepts=None,
                      codecs=None):
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
                    codecs=codecs, tiers=[tier] * n)
        outs = disp.stream(xs)
        stats = disp.stats(addrs)
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=60)
    return outs, stats


@pytest.fixture(scope="module")
def chain3(tiny):
    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(5)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(5)]
    outs, stats = _run_chain_inproc(stages, params, xs, tier="tcp")
    return g, params, stages, xs, outs, stats


def test_shm_chain_byte_identical_zero_codec_work(chain3):
    """Every hop of a ``tier="shm"`` chain negotiates the ring, outputs
    are byte-identical to the all-TCP chain, ZERO ``codec.*`` samples
    are recorded, and no segment outlives the stream."""
    g, params, stages, xs, base, base_stats = chain3
    assert [s["tier"] for s in base_stats] == ["tcp"] * 3
    before = _segments()
    enc0, dec0 = _hist_count("codec.encode_s"), _hist_count("codec.decode_s")
    sf0 = _counter("transport.shm_frames")
    outs, stats = _run_chain_inproc(stages, params, xs, tier="shm")
    assert [s["tier"] for s in stats] == ["shm"] * 3
    assert [s["tier_in"] for s in stats] == ["shm"] * 3
    assert [s["tier_fallbacks"] for s in stats] == [0] * 3
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _hist_count("codec.encode_s") == enc0, \
        "a shm hop recorded codec encode samples"
    assert _hist_count("codec.decode_s") == dec0, \
        "a shm hop recorded codec decode samples"
    # 4 hops (disp->s0->s1->s2->result) x len(xs) frames rode the rings
    assert _counter("transport.shm_frames") - sf0 == 4 * len(xs)
    assert _segments() <= before


def test_refused_shm_hop_degrades_with_labeled_fallback(chain3):
    """A hop whose peer refuses the offer degrades to tcp, the stream
    stays byte-identical, and the degraded hop is attributable: its
    stats row carries ``tier_fallbacks`` (the monitor renders it as
    ``tcp!``), unlike the never-offered hops around it."""
    g, params, stages, xs, base, _ = chain3
    before = _counter("transport.tier_fallback")
    outs, stats = _run_chain_inproc(stages, params, xs, tier="shm",
                                    accepts=[True, False, True])
    assert _counter("transport.tier_fallback") > before
    by_stage = {s["stage"]: s for s in stats}
    assert by_stage[0]["tier"] == "tcp"      # its offer was refused
    assert by_stage[0]["tier_fallbacks"] >= 1
    assert by_stage[1]["tier"] == "shm"      # stage 2 still granted
    assert by_stage[1]["tier_fallbacks"] == 0
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_monitor_renders_degraded_hop():
    """The TIER column distinguishes a DEGRADED hop (tcp!) from one
    that never offered anything better (tcp)."""
    import contextlib
    import io

    from defer_tpu.cli import _render_monitor
    row = {"stage": 0, "replica": None, "branch": None, "join": 0,
           "tier": "tcp", "tier_fallbacks": 1, "alive": True,
           "throughput_per_s": 1.0,
           "infer_ms": {"p50": 0.0, "p95": 0.0, "p99": 0.0},
           "rx_q": 0, "tx_q": 0, "rx_hi": 0, "tx_hi": 0, "inflight": 0,
           "rx_bytes_per_s": 0.0, "tx_bytes_per_s": 0.0,
           "processed": 1, "addr": "x"}
    plain = dict(row, tier_fallbacks=0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        _render_monitor([row, plain], None, [], {}, clear=False)
    lines = buf.getvalue().splitlines()
    assert any("tcp!" in ln for ln in lines[1:2]), lines
    assert "tcp!" not in lines[2]
    # the "!" marks a hop STILL riding tcp: a node that fell back once
    # but renegotiated shm on a later stream renders healthy, and the
    # untruncated 5-char "local" survives the degraded-mark suffixing
    healthy = dict(row, tier="shm")
    local = dict(row, tier="local", tier_fallbacks=0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        _render_monitor([healthy, local], None, [], {}, clear=False)
    out = buf.getvalue()
    assert "shm!" not in out and " shm " in out, out
    assert "local" in out and "loca!" not in out, out


def test_shm_pin_on_fan_role_node_rejected():
    """An explicit ``tier="shm"`` pin on a replica/branch/fan-out node
    is rejected loudly (at construction, and re-checked after a deploy
    message mutates the role) — the fan machinery is wire-framed by
    design, so the ladder would be silently skipped with
    ``tier_fallbacks`` still 0 (the exact ambiguity the per-hop
    fallback counter exists to remove).  ``auto`` stays allowed:
    riding tcp there is policy, not degradation."""
    from defer_tpu.runtime.node import StageNode, _normalize_hop_tiers
    with pytest.raises(ValueError, match="replica"):
        StageNode(None, "127.0.0.1:0", None, tier="shm", replica=0)
    with pytest.raises(ValueError, match="branch"):
        StageNode(None, "127.0.0.1:0", None, tier="shm", branch=1)
    with pytest.raises(ValueError, match="fan-out"):
        StageNode(None, "127.0.0.1:0", "127.0.0.1:1,127.0.0.1:2",
                  tier="shm")
    StageNode(None, "127.0.0.1:0", None, tier="auto", replica=0)
    # the deploy handler re-runs the same check after applying the
    # message, so an in-band role change cannot sneak past the pin
    node = StageNode(None, "127.0.0.1:0", None, tier="shm")
    node.replica = 0  # what {"cmd": "deploy", "replica": 0} sets
    with pytest.raises(ValueError, match="replica"):
        node._check_tier_pin()
    # a chain-WIDE tier="shm" default hits the same adjacency guard as
    # an explicit hop_tiers entry when a stage is replicated
    with pytest.raises(ValueError, match="replicated"):
        _normalize_hop_tiers(None, 3, [1, 2, 1], "shm")
    assert _normalize_hop_tiers(None, 3, [1, 2, 1], "auto") \
        == ["auto", "auto"]


def test_shm_hop_tiers_require_overlap(tiny):
    """Satellite: an explicit shm claim under the serial (pure-wire)
    loop is rejected loudly — it would silently run full codec + TCP
    under a tier claim (mirror of the local+serial guard)."""
    from defer_tpu.runtime.node import run_chain
    g, params = tiny
    stages = partition(g, num_stages=3)
    with pytest.raises(ValueError, match="shm.*overlap|overlap.*shm"):
        run_chain(stages, params, [], hop_tiers=["shm", "shm"],
                  overlap=False)


def test_shm_hop_adjacent_to_replica_rejected():
    from defer_tpu.runtime.node import _normalize_hop_tiers
    with pytest.raises(ValueError, match="replicated"):
        _normalize_hop_tiers(["shm", "tcp"], 3, [1, 2, 1], "tcp")
    assert _normalize_hop_tiers(["shm", "auto"], 3, [1, 1, 1], "tcp") \
        == ["shm", "auto"]


# ---------------------------------------------------------------------------
# segment lifecycle: orphan sweep
# ---------------------------------------------------------------------------

def test_sweep_reaps_dead_pid_segments_only():
    if not os.path.isdir("/dev/shm"):
        pytest.skip("no /dev/shm on this host")
    from multiprocessing import shared_memory

    # a plausibly-dead pid: max pid space is rarely saturated
    dead = f"{SEG_PREFIX}999999_deadbeef0000"
    alive = f"{SEG_PREFIX}{os.getpid()}_feedfeed0000"
    for name in (dead, alive):
        seg = shared_memory.SharedMemory(name=name, create=True, size=64)
        seg.close()
    try:
        reaped = sweep_orphan_segments()
        assert dead in reaped
        assert not os.path.exists(f"/dev/shm/{dead}")
        # own-pid segments are never swept (this process's rings reap
        # themselves)
        assert os.path.exists(f"/dev/shm/{alive}")
    finally:
        for name in (dead, alive):
            try:
                os.unlink(f"/dev/shm/{name}")
            except OSError:
                pass


# ---------------------------------------------------------------------------
# planner: the shm pseudo-codec
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


def test_solver_exploits_shm_hop_tier_map():
    """Acceptance bar: with a shm hop-tier map the solver places cuts
    across a fat boundary the all-tcp plan avoids — strict predicted
    bottleneck win on this comm-bound model — and the tier survives the
    plan-JSON roundtrip."""
    from defer_tpu.plan import plan_from_json, solve

    g, cm = _fat_boundary_model()
    p_tcp = solve(g, 3, cm)
    tiers = {c: "shm" for c in ("d0", "d1", "d2")}
    p_shm = solve(g, 3, cm, hop_tiers=tiers)
    assert p_shm.bottleneck_s < p_tcp.bottleneck_s  # STRICT: comm-bound
    assert set(p_shm.codecs) == {"shm"}
    assert p_shm.hop_tiers == ["shm"] * 2
    doc = p_shm.to_json()
    assert doc["hop_tiers"] == ["shm", "shm"]
    assert plan_from_json(doc).hop_tiers == ["shm", "shm"]


def test_shm_costs_between_local_and_wire():
    """The ladder's preference order falls out of the model: local
    (one pass + host sync) < shm (two passes + host sync) < any wire
    codec on a fat boundary — the tiers differ by exactly one
    memory-bandwidth pass over the boundary bytes (the host_sync
    round-trip they BOTH pay is the part the ici tier removes,
    tests/test_ici.py)."""
    g, cm = _fat_boundary_model()
    local_s = cm.with_hop_tiers({"d1": "local"}).comm_seconds("d1", "local")
    shm_s = cm.with_hop_tiers({"d1": "shm"}).comm_seconds("d1", "shm")
    wire_s = cm.best_codec("d1")[1]
    assert 0.0 < local_s < shm_s < wire_s
    assert shm_s - local_s == pytest.approx(
        cm.cut_bytes("d1") / cm.local_bw_s)


def test_shm_tier_never_applies_to_fan_hops():
    g, cm = _fat_boundary_model()
    cm = cm.with_hop_tiers({"d1": "shm"})
    name, s = cm.best_codec_replicated("d1", 1, 1)
    assert name == "shm"
    name2, s2 = cm.best_codec_replicated("d1", 2, 1)
    assert name2 != "shm" and s2 > s


def test_replan_preserves_shm_hop_tiers():
    from defer_tpu.plan import replan, solve

    g, cm = _fat_boundary_model()
    tiers = {c: "shm" for c in ("d0", "d1", "d2")}
    plan = solve(g, 3, cm, hop_tiers=tiers)
    rp = replan(g, plan, {0: 2e-3, 1: 1e-3, 2: 1e-3},
                cm.with_hop_tiers(tiers))
    assert set(rp.new_plan.hop_tiers) == {"shm"}
    assert set(rp.old_plan_corrected.hop_tiers) == {"shm"}


# ---------------------------------------------------------------------------
# real OS processes: end-to-end negotiation (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_real_process_shm_grant_end_to_end(tiny):
    """Full mode: 3 separate OS processes, every hop (dispatcher edges
    included) negotiated shm via the probe, byte-identical to all-TCP,
    no segments left behind."""
    from defer_tpu.runtime.node import run_chain

    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(11)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(4)]
    before = _segments()
    stats: list = []
    outs = run_chain(stages, params, xs, hop_tiers=["shm", "shm"],
                     tier="shm", env=CPU_ENV, stats_out=stats)
    assert {(s["tier"], s["tier_in"]) for s in stats} == {("shm", "shm")}
    base = run_chain(stages, params, xs, tier="tcp", env=CPU_ENV)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert _segments() <= before, "real-process chain leaked /dev/shm"


@pytest.mark.slow
def test_real_process_kill9_poisons_cleanly_no_orphans(tiny, monkeypatch):
    """kill -9 one stage mid-stream on an all-shm chain: the chain
    fails (no hang past the dispatcher's timeout budget — shrunk here
    so the test is fast), every child is terminated, and — the
    lifecycle bar — no shared-memory segment survives the teardown
    (the killed process skipped every unlink path; its neighbors and
    the sweep reap for it)."""
    from defer_tpu.runtime.node import run_chain

    # the kill can land before stage2 ever dials the result server
    # back; the failure then surfaces on the result-accept timeout —
    # 180 s by default, pointlessly slow for a test that asserts
    # "fails, not hangs"
    monkeypatch.setattr(ChainDispatcher, "timeout_s", 30.0)
    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(13)
    spawned: list = []
    before = _segments()

    def on_spawn(procs):
        spawned.extend(procs)

    def inputs():
        for i in range(40):
            if i == 2:
                spawned[1].kill()  # SIGKILL: no atexit, no unlink
            yield rng.standard_normal((1, 32, 32, 3)).astype(np.float32)

    t0 = time.monotonic()
    with pytest.raises(RuntimeError):
        run_chain(stages, params, inputs(), hop_tiers=["shm", "shm"],
                  tier="shm", env=CPU_ENV, on_spawn=on_spawn,
                  spawn_retries=1)
    assert time.monotonic() - t0 < 150, "kill-9 teardown hung"
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if all(pr.poll() is not None for pr in spawned):
            break
        time.sleep(0.2)
    assert all(pr.poll() is not None for pr in spawned)
    # surviving ends reaped inline; whatever ONLY the dead process knew
    # about is the sweep's job — run it as the next deploy would
    sweep_orphan_segments()
    assert _segments() <= before, (
        f"kill -9 leaked segments: {_segments() - before}")
