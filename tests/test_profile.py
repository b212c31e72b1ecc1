"""Stage-interior profiling plane (obs/profile.py): ProfileSession
delta arithmetic, recompile episode discipline, memory-pressure
thresholds, the profile_start/profile_stop ctrl protocol (double-start
refused loudly), the phase-sum invariant on a live in-process chain,
and the monitor's DISP/DEV/MEM rendering."""

import io
import socket
import threading
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.obs import (LatencyHistogram, MemoryWatcher,
                           ProfileSession, RecompileWatcher, recorder)
from defer_tpu.obs.profile import NODE_PHASES, device_memory_bytes


def _recompile_events():
    return [e for e in recorder().snapshot() if e["kind"] == "recompile"]


def _mem_events():
    return [e for e in recorder().snapshot()
            if e["kind"] == "mem_pressure"]


# ---------------------------------------------------------------------------
# ProfileSession: window deltas over cumulative histograms
# ---------------------------------------------------------------------------

def test_profile_session_deltas_and_double_start():
    h = {"dispatch": LatencyHistogram(), "infer": LatencyHistogram()}
    h["dispatch"].record(0.010)
    h["infer"].record(0.015)              # pre-window traffic
    seen = [7]
    sess = ProfileSession(h, processed=lambda: seen[0])
    started = sess.start()
    assert started["t0_unix"] > 0
    with pytest.raises(RuntimeError, match="already started"):
        sess.start()
    for _ in range(4):
        h["dispatch"].record(0.002)
        h["infer"].record(0.003)
    seen[0] = 12
    rep = sess.stop()
    # the report prices the WINDOW, not the process lifetime
    assert rep["phases"]["dispatch"]["count"] == 4
    assert rep["phases"]["dispatch"]["sum_s"] == pytest.approx(
        0.008, rel=0.01)
    assert rep["phases"]["infer"]["mean_ms"] == pytest.approx(
        3.0, rel=0.01)
    assert rep["processed"] == 5
    assert rep["duration_s"] > 0
    assert rep["recompiles"] >= 0
    with pytest.raises(RuntimeError, match="never started"):
        sess.stop()


def test_profile_session_absent_phase_stays_honest():
    """A None histogram (e.g. an engine phase on a plain node) reports
    count 0 / mean None — never a fabricated number."""
    sess = ProfileSession({"gather": None})
    sess.start()
    rep = sess.stop()
    assert rep["phases"]["gather"] == {
        "count": 0, "sum_s": 0.0, "mean_ms": None, "p50_ms_cum": None}


# ---------------------------------------------------------------------------
# RecompileWatcher: counting always, ONE event per episode once armed
# ---------------------------------------------------------------------------

def test_recompile_wrap_episode_discipline():
    """The episodes, driven through the listener's own entry: a backend
    event a fresh signature, under the compiled function's name."""
    w = RecompileWatcher(episode_gap_s=0.2)
    event = "/jax/core/compile/backend_compile_duration"

    def compiled(program="stage_fn"):
        w.on_duration(event, 0.001, fun_name=f"jit({program})")

    c0 = w.count
    ev0 = len(_recompile_events())
    # warmup compiles BEFORE arm: counted, silent
    compiled()
    w.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.001,
                  fun_name="stage_fn")      # no backend event: no count
    assert w.count - c0 == 1
    assert len(_recompile_events()) == ev0
    w.arm()
    # a burst of fresh signatures: every one counts, ONE event
    compiled()
    compiled("other_fn")
    compiled()
    assert w.count - c0 == 4
    evs = _recompile_events()
    assert len(evs) == ev0 + 1
    assert evs[-1]["data"] == {"count": w.count - 2, "program": "stage_fn"}
    # quiet >= episode_gap_s re-arms lazily: the next compile fires
    time.sleep(0.25)
    compiled()
    assert len(_recompile_events()) == ev0 + 2
    # disarm: counting continues, emission stops
    w.disarm()
    compiled()
    assert w.count - c0 == 6
    assert len(_recompile_events()) == ev0 + 2
    # and every one of them is in the table, under its program
    rows = w.programs()
    assert rows["stage_fn"]["count"] == 5 and rows["other_fn"]["count"] == 1


def test_recompile_monitoring_listener_counts_real_jit():
    """The jax.monitoring path: a fresh jit signature reaches XLA and
    is counted; the warm repeat is a program-cache hit and is NOT."""
    w = RecompileWatcher(episode_gap_s=60.0)
    w.install()
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones((2, 3))).block_until_ready()   # make the jit exist
    c0 = w.count
    f(jnp.ones((2, 3))).block_until_ready()   # warm: cache hit
    assert w.count == c0
    f(jnp.ones((4, 3))).block_until_ready()   # fresh shape: compiles
    assert w.count > c0


# ---------------------------------------------------------------------------
# MemoryWatcher: gauge + threshold excursions with hysteresis
# ---------------------------------------------------------------------------

def test_memory_watcher_threshold_and_hysteresis():
    keep = jnp.ones((128,))               # ensure live bytes exist
    mw = MemoryWatcher()
    n0 = len(_mem_events())
    mw.set_threshold(1.0)                 # 1 byte: certainly exceeded
    n = mw.observe()
    assert n is not None and n > 1
    assert len(_mem_events()) == n0 + 1
    ev = _mem_events()[-1]["data"]
    assert ev["bytes"] == n and ev["threshold"] == 1
    assert ev["live_arrays"] >= 1
    # still over threshold: the excursion already fired, stays quiet
    mw.observe()
    assert len(_mem_events()) == n0 + 1
    # drop below 90% of a huge threshold -> re-arms, then fires again
    mw.set_threshold(1e15)
    mw.observe()
    mw.set_threshold(1.0)
    mw.observe()
    assert len(_mem_events()) == n0 + 2
    del keep


def test_memory_watcher_env_threshold(monkeypatch):
    mw = MemoryWatcher()
    monkeypatch.setenv("DEFER_MEM_PRESSURE_BYTES", "12345")
    assert mw.threshold_bytes() == 12345.0
    mw.set_threshold(99.0)                # explicit wins over env
    assert mw.threshold_bytes() == 99.0


def test_device_memory_bytes_counts_live_arrays():
    before = device_memory_bytes()
    assert before is not None             # jax imported in this test
    a = jnp.ones((1024,), jnp.float32)
    a.block_until_ready()
    after = device_memory_bytes()
    assert after >= before + 4096
    del a


# ---------------------------------------------------------------------------
# profile ctrl protocol: start/stop window, double-start refused loudly
# ---------------------------------------------------------------------------

def _profile_stub():
    from defer_tpu.runtime.node import LatencyHistogram as LH
    from defer_tpu.runtime.node import StageNode
    class _Prog:  # manifest carrier: the only prog attr ctrl reads
        manifest = {"index": 1, "name": "stage1"}

    node = StageNode.__new__(StageNode)
    node.prog = _Prog()
    node.codec = "raw"
    node.processed = 0
    node.reweights = 0
    node.address = ("127.0.0.1", 0)
    node._pending_trace = None
    node._merge = None
    node.infer_hist = LH()
    node.host_sync_hist = LH()
    node.disp_hist = LH()
    node.queue_hist = LH()
    node.dev_hist = LH()
    return node


def test_profile_ctrl_window_and_double_start():
    from defer_tpu.transport.framed import K_CTRL, recv_frame

    node = _profile_stub()
    a, b = socket.socketpair()
    try:
        assert node._handle_ctrl(a, {"cmd": "profile_start"})
        kind, rep = recv_frame(b)
        assert kind == K_CTRL and rep["cmd"] == "profile_started"
        assert rep["node"] == "stage1"
        # double start: loud refusal, session intact
        assert node._handle_ctrl(a, {"cmd": "profile_start"})
        kind, rep = recv_frame(b)
        assert rep["cmd"] == "profile_err"
        assert "already active" in rep["error"]
        assert node._profile is not None
        # traffic inside the window
        for _ in range(3):
            node.disp_hist.record(0.001)
            node.queue_hist.record(0.0005)
            node.dev_hist.record(0.002)
            node.host_sync_hist.record(0.0015)
            node.infer_hist.record(0.005)
        node.processed = 3
        assert node._handle_ctrl(a, {"cmd": "profile_stop"})
        kind, rep = recv_frame(b)
        assert rep["cmd"] == "profile_report"
        r = rep["report"]
        assert r["stage"] == 1 and r["node"] == "stage1"
        assert r["processed"] == 3
        for name in NODE_PHASES:
            assert r["phases"][name]["count"] == 3
        assert r["phases"]["infer"]["sum_s"] == pytest.approx(
            0.015, rel=0.01)
        # stop without a session: loud too
        assert node._handle_ctrl(a, {"cmd": "profile_stop"})
        kind, rep = recv_frame(b)
        assert rep["cmd"] == "profile_err"
        assert "no active profile session" in rep["error"]
    finally:
        a.close()
        b.close()


def test_stats_reply_carries_profile_telemetry():
    """The stats ctrl reply surfaces the phase histograms, the compile
    counter, live memory, and the session flag."""
    from defer_tpu.transport.framed import K_CTRL, recv_frame

    node = _profile_stub()
    node.disp_hist.record(0.004)
    node.queue_hist.record(0.001)
    node.dev_hist.record(0.006)
    a, b = socket.socketpair()
    try:
        assert node._handle_ctrl(a, {"cmd": "stats"})
        kind, rep = recv_frame(b)
        assert kind == K_CTRL
        assert rep["dispatch_s"]["count"] == 1
        assert rep["queue_s"]["count"] == 1
        assert rep["device_s"]["count"] == 1
        assert rep["dispatch_s"]["p50"] == pytest.approx(0.004, rel=0.5)
        assert rep["recompiles"] >= 0
        assert rep["mem_bytes"] is None or rep["mem_bytes"] >= 0
        assert rep["profiling"] is False
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# the phase-sum invariant on a real (in-process) chain
# ---------------------------------------------------------------------------

def test_phase_sums_tile_infer_on_live_chain():
    """dispatch + queue + device + host_sync must account for the
    issue-to-materialize infer wall on every stage of a streaming
    chain (the scripts/profile_smoke.py invariant, minimally)."""
    from defer_tpu import partition
    from defer_tpu.models import resnet_tiny
    from defer_tpu.runtime.node import ChainDispatcher, StageNode

    graph = resnet_tiny()
    params = graph.init(jax.random.key(0))
    stages = partition(graph, num_stages=2)
    nodes = [StageNode(None, "127.0.0.1:0", None) for _ in range(2)]
    addrs = [f"127.0.0.1:{n.address[1]}" for n in nodes]
    for n in nodes:
        threading.Thread(target=n.serve, daemon=True).start()
    disp = ChainDispatcher(addrs[0], codec="raw")
    disp.deploy(stages, params, addrs, batch=2)
    try:
        xs = [np.random.default_rng(i).standard_normal(
            (2, 32, 32, 3)).astype(np.float32) for i in range(24)]
        disp.stream(xs[:4])               # compile
        disp.stream(xs)
        for node in nodes:
            inf = node.infer_hist.summary()
            parts = sum(h.summary().get("sum", 0.0)
                        for h in (node.disp_hist, node.queue_hist,
                                  node.dev_hist, node.host_sync_hist))
            assert inf["count"] >= 24
            assert parts == pytest.approx(inf["sum"], rel=0.15), (
                node.manifest["index"], parts, inf["sum"])
    finally:
        disp.close()


# ---------------------------------------------------------------------------
# monitor rendering: DISP/DEV/MEM columns, "-" at zero samples
# ---------------------------------------------------------------------------

def _row(stage, *, disp=None, dev=None, mem=None, recompiles=None):
    def ms(v):
        return ({"p50": v, "count": 10} if v is not None
                else {"p50": 0.0, "count": 0})
    return {"stage": stage, "replica": None, "branch": None, "join": 0,
            "tier": "tcp", "tier_fallbacks": 0,
            "throughput_per_s": 10.0, "processed": 100, "alive": True,
            "infer_ms": {"p50": 1.0, "p95": 1.2, "p99": 1.4},
            "host_sync_ms": ms(0.2),
            "dispatch_ms": ms(disp), "device_ms": ms(dev),
            "queue_ms": ms(None), "mem_bytes": mem,
            "recompiles": recompiles, "mfu": None,
            "pred_ms": None, "meas_ms": None, "err": None,
            "rx_q": 0, "tx_q": 0, "rx_hi": 0, "tx_hi": 0,
            "inflight": 0, "rx_bytes_per_s": 0.0,
            "tx_bytes_per_s": 0.0, "addr": f"127.0.0.1:{5000 + stage}"}


def test_monitor_renders_phase_columns_and_dash_when_absent():
    from defer_tpu.cli import _render_monitor

    rows = [_row(0, disp=0.5, dev=1.25, mem=2.5e6, recompiles=2),
            _row(1)]                      # no samples yet: all dashes
    buf = io.StringIO()
    with redirect_stdout(buf):
        _render_monitor(rows, None, [], {}, clear=False)
    out = buf.getvalue()
    assert "DISP" in out and "DEV" in out and "MEM" in out
    body = [ln for ln in out.splitlines()[1:] if ln.strip()]
    assert len(body) == 2
    assert "0.500" in body[0] and "1.250" in body[0]
    assert "2.5M" in body[0]
    # never fabricate: a node with zero phase samples renders "-" in
    # the DISP, DEV, and MEM columns (plus HS50's existing dash)
    cols = body[1].split()
    # STAGE BR REP TIER INF/S P50 P95 P99 HS50 DISP DEV MEM ...
    assert cols[9] == "-" and cols[10] == "-" and cols[11] == "-"
