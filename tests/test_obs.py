"""Telemetry subsystem: histogram quantiles, registry formats, tracer
span links, cross-process trace propagation through the MPMD chain, and
the satellite fixes (as_dict completeness, codec-cache thread safety,
infer_stream timeout plumbing)."""

import json
import socket
import threading

import numpy as np
import pytest

import jax

from defer_tpu.obs import (LatencyHistogram, MetricsRegistry, Tracer,
                           tracer)


# ---------------------------------------------------------------------------
# histogram
# ---------------------------------------------------------------------------

def test_histogram_constant_distribution():
    h = LatencyHistogram()
    for _ in range(100):
        h.record(0.25)
    assert h.count == 100
    assert h.min == h.max == 0.25
    # quantiles of a constant are that constant (clamped to observed range)
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        assert h.quantile(q) == 0.25
    p = h.percentiles
    assert p["p50"] == p["p99"] == p["max"] == 0.25


def test_histogram_uniform_quantiles_within_bucket_resolution():
    h = LatencyHistogram()
    for i in range(1, 10001):
        h.record(i / 1000.0)  # uniform on (0, 10]
    # log buckets at 8/octave -> <= ~9% relative error per quantile
    assert h.quantile(0.5) == pytest.approx(5.0, rel=0.1)
    assert h.quantile(0.95) == pytest.approx(9.5, rel=0.1)
    assert h.quantile(0.99) == pytest.approx(9.9, rel=0.1)
    assert h.max == 10.0
    assert h.mean == pytest.approx(5.0005, rel=1e-6)


def test_histogram_merge_matches_combined():
    a, b, c = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    xs = [0.001 * (i + 1) for i in range(50)]
    ys = [0.1 * (i + 1) for i in range(50)]
    for x in xs:
        a.record(x)
        c.record(x)
    for y in ys:
        b.record(y)
        c.record(y)
    a.merge(b)
    assert a.count == c.count == 100
    assert a.sum == pytest.approx(c.sum)
    assert a.min == c.min and a.max == c.max
    for q in (0.1, 0.5, 0.9, 0.99):
        assert a.quantile(q) == pytest.approx(c.quantile(q))


def test_histogram_empty_and_outliers():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    assert h.summary() == {"count": 0}
    h.record(0.0)          # clamps into the bottom bucket, keeps exact min
    h.record(float("nan"))  # ignored
    h.record(1e6)           # huge outlier is representable
    assert h.count == 2
    assert h.min == 0.0 and h.max == 1e6


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_type_conflict():
    r = MetricsRegistry()
    c = r.counter("a.b")
    assert r.counter("a.b") is c
    c.inc(3)
    c.n += 2
    assert r.counter("a.b").value == 5
    with pytest.raises(TypeError):
        r.gauge("a.b")


def test_registry_snapshot_and_callbacks():
    r = MetricsRegistry()
    r.counter("tx.frames").inc(7)
    r.gauge("depth").set(3.5)
    h = r.histogram("lat_s")
    for v in (0.01, 0.02, 0.04):
        h.record(v)
    state = {"inferences": 42}
    r.register_callback("pipe.inferences", lambda: state["inferences"])
    s = r.snapshot()
    assert s["tx.frames"] == 7
    assert s["depth"] == 3.5
    assert s["lat_s"]["count"] == 3
    assert {"p50", "p95", "p99", "max"} <= set(s["lat_s"])
    assert s["pipe.inferences"] == 42
    state["inferences"] = 43  # callbacks are live
    assert r.snapshot()["pipe.inferences"] == 43
    # snapshot is json-serializable as-is
    json.dumps(s)


def test_registry_prometheus_exposition():
    r = MetricsRegistry()
    r.counter("transport.tx_bytes").inc(1024)
    h = r.histogram("push.latency_s")
    h.record(0.5)
    text = r.exposition()
    assert "# TYPE transport_tx_bytes counter" in text
    assert "transport_tx_bytes 1024" in text
    assert "# TYPE push_latency_s summary" in text
    assert 'push_latency_s{quantile="0.5"}' in text
    assert "push_latency_s_count 1" in text


def test_registry_unregister_prefix():
    r = MetricsRegistry()
    r.counter("p0.a")
    r.counter("p0.b")
    r.counter("p1.a")
    r.unregister("p0.")
    s = r.snapshot()
    assert "p0.a" not in s and "p0.b" not in s and "p1.a" in s


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_tracer_disabled_records_nothing():
    t = Tracer(process="t")
    with t.span("work", {"k": 1}) as s:
        assert s.span_id is None  # shared no-op
    assert t.spans == []


def test_tracer_span_nesting_and_links():
    t = Tracer(process="t", enabled=True)
    tid = t.start_trace()
    with t.span("outer") as outer:
        with t.span("inner"):
            pass
    spans = t.spans
    assert len(spans) == 2
    inner, outer_s = spans  # inner finishes first
    assert inner["name"] == "inner" and outer_s["name"] == "outer"
    assert inner["trace"] == outer_s["trace"] == tid
    assert inner["parent"] == outer_s["span"]
    assert outer_s["parent"] is None
    assert inner["dur_us"] >= 1 and inner["ts_us"] >= outer_s["ts_us"]


def test_tracer_inject_adopt_roundtrip():
    parent = Tracer(process="dispatcher", enabled=True)
    parent.start_trace()
    with parent.span("root"):
        ctx = parent.inject()
    child = Tracer(process="stage0")  # e.g. another process, off
    child.adopt(json.loads(json.dumps(ctx)))  # survives the wire
    assert child.enabled  # adoption turns tracing on remotely
    with child.span("stage0.infer"):
        pass
    (s,) = child.spans
    assert s["trace"] == parent.trace_id
    assert s["parent"] == ctx["span_id"]
    # drain/ingest stitches the remote spans into the parent's export
    parent.ingest(child.drain())
    assert child.spans == []
    names = {x["name"] for x in parent.spans}
    assert names == {"root", "stage0.infer"}


def test_tracer_chrome_export(tmp_path):
    t = Tracer(process="procA", enabled=True)
    with t.span("alpha", {"x": 1}):
        pass
    t.record("beta", 0.0, 0.001)
    path = str(tmp_path / "trace.json")
    t.export_chrome(path)
    doc = json.load(open(path))
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    xs = [e for e in events if e["ph"] == "X"]
    assert metas and metas[0]["args"]["name"] == "procA"
    assert {e["name"] for e in xs} == {"alpha", "beta"}
    for e in xs:
        assert e["dur"] >= 1 and "trace_id" in e["args"]


# ---------------------------------------------------------------------------
# PipelineMetrics as a registry view (satellite: as_dict completeness)
# ---------------------------------------------------------------------------

def test_pipeline_metrics_as_dict_self_describing():
    from defer_tpu.utils.metrics import PipelineMetrics

    m = PipelineMetrics(num_stages=4, microbatch=2)
    m.inferences, m.steps = 6, 5
    d = m.as_dict()
    # the bubble_fraction inputs must be in the export (satellite fix)
    assert d["microbatch"] == 2 and d["steps"] == 5
    assert d["bubble_fraction"] == pytest.approx(1.0 - (6 / 2) / 5)


def test_pipeline_metrics_histograms_and_bind():
    from defer_tpu.obs import MetricsRegistry
    from defer_tpu.utils.metrics import PipelineMetrics

    r = MetricsRegistry()
    m = PipelineMetrics(num_stages=2, microbatch=1)
    prefix = m.bind(registry=r, prefix="pipeX")
    assert prefix == "pipeX"
    m.inferences = 3
    m.push_latency.record(0.010)
    m.push_latency.record(0.020)
    m.record_stage_latency(0, 0.001)
    m.record_stage_latency(1, 0.004)
    d = m.as_dict()
    assert d["push_latency_ms"]["count"] == 2
    assert d["push_latency_ms"]["p50"] == pytest.approx(10.0, rel=0.2)
    assert len(d["stage_latency_percentiles_ms"]) == 2
    # legacy mean view stays in sync
    assert d["stage_latency_ms"][1] == pytest.approx(4.0, rel=0.1)
    # registry snapshot carries the same data (the "view" contract)
    s = r.snapshot()
    assert s["pipeX.inferences"] == 3
    assert s["pipeX.push_latency_s"]["count"] == 2
    assert s["pipeX.stage1.latency_s"]["count"] == 1


def test_spmd_pipeline_populates_registry(monkeypatch):
    """An SPMD deployment publishes push percentiles + per-hop bytes."""
    from defer_tpu import SpmdPipeline, partition, pipeline_mesh
    from defer_tpu.models import resnet_tiny
    from defer_tpu.obs import REGISTRY

    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    stages = partition(g, num_stages=2)
    pipe = SpmdPipeline(stages, params, mesh=pipeline_mesh(2),
                        microbatch=1, chunk=2)
    xs = np.zeros((2, 1, 32, 32, 3), np.float32)
    pipe.push(xs)
    pipe.flush()
    prefix = pipe.metrics.prefix
    s = REGISTRY.snapshot()
    assert s[f"{prefix}.push_latency_s"]["count"] >= 2
    bph = pipe.metrics.buffer_bytes_per_hop
    # every hop paid bytes_per_hop per executed step
    assert s[f"{prefix}.hop0.bytes"] == pipe.metrics.steps * bph
    assert s[f"{prefix}.hop1.bytes"] == pipe.metrics.steps * bph


# ---------------------------------------------------------------------------
# transport satellites: codec cache thread safety, timeout plumbing
# ---------------------------------------------------------------------------

def test_codec_cache_concurrent_population():
    """Sender and receiver threads fault codecs in concurrently; every
    thread must get a working codec and the cache must hold one instance
    per name (the old unlocked dict could interleave construction)."""
    import defer_tpu.transport.framed as fr

    fr._CODECS.clear()
    names = ["raw", "lzb", "bf8", "bf12", "bf16"]
    got: list = []
    errs: list = []
    start = threading.Barrier(8)

    def worker():
        try:
            start.wait(timeout=5)
            for _ in range(50):
                for n in names:
                    got.append((n, fr._codec(n)))
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    by_name: dict = {}
    for n, c in got:
        assert by_name.setdefault(n, c) is c  # one instance per name
    with pytest.raises(ValueError):
        fr._codec("zstd99")


def test_infer_stream_timeout_plumbed():
    """A peer that never drains trips TimeoutError at the caller's
    timeout_s, not the old hardcoded 600 s."""
    from defer_tpu.transport.framed import TensorClient

    a, b = socket.socketpair()
    try:
        c = TensorClient.__new__(TensorClient)
        c._sock = a
        c.timeout_s = 0.2
        with pytest.raises(TimeoutError, match="did not drain"):
            c.infer_stream([np.zeros((1, 4), np.float32)])
        # per-call override beats the instance default
        c.timeout_s = 600.0
        with pytest.raises(TimeoutError):
            c.infer_stream([np.zeros((1, 4), np.float32)], timeout_s=0.2)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# cross-process propagation (satellite: trace id through a 2-proc chain)
# ---------------------------------------------------------------------------

def test_stage_node_adopts_and_dumps_trace_ctx():
    """Unit-level round trip of the K_CTRL trace commands against a live
    StageNode handler (no subprocesses): adopt -> record -> dump."""
    from defer_tpu.runtime.node import StageNode
    from defer_tpu.transport.framed import K_CTRL, recv_frame, send_ctrl

    node = StageNode.__new__(StageNode)
    node.prog = None
    node.next_hop = None
    node.codec = "raw"
    node.processed = 0
    node.reweights = 0
    node.address = ("127.0.0.1", 0)
    node._pending_trace = None

    tr = tracer()
    was_enabled, old_proc = tr.enabled, tr.process
    try:
        a, b = socket.socketpair()
        ctx = {"cmd": "trace", "trace_id": "feedc0defeedc0de",
               "span_id": "abad1deaabad1dea"}
        assert node._handle_ctrl(a, ctx)
        assert node._pending_trace == ctx
        assert tr.enabled and tr.trace_id == "feedc0defeedc0de"
        tr.record("stage?.infer", 0.0, 0.001)
        (s,) = [x for x in tr.spans if x["name"] == "stage?.infer"]
        assert s["trace"] == "feedc0defeedc0de"
        assert s["parent"] == "abad1deaabad1dea"
        # trace_dump replies with (and drains) the recorded spans
        node._handle_ctrl(a, {"cmd": "trace_dump"})
        kind, reply = recv_frame(b)
        assert kind == K_CTRL
        names = [x["name"] for x in reply["spans"]]
        assert "stage?.infer" in names
        a.close()
        b.close()
    finally:
        tr.enabled = was_enabled
        tr.process = old_proc
        tr._remote_parent = None
        tr.clear()


def test_trace_id_survives_two_process_chain():
    """The satellite round trip: a trace id injected at the dispatcher
    rides K_CTRL frames through a 2-process node chain and every stage
    process's spans come back stitched under the dispatcher's root."""
    from defer_tpu import partition
    from defer_tpu.models import resnet_tiny
    from defer_tpu.runtime.node import run_chain

    cpu_env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    stages = partition(g, num_stages=2)
    xs = [np.random.default_rng(7).standard_normal((1, 32, 32, 3))
          .astype(np.float32) for _ in range(3)]

    tr = tracer()
    was_enabled, old_proc = tr.enabled, tr.process
    tr.clear()
    try:
        tr.enabled = True
        tr.process = "dispatcher"
        tid = tr.start_trace()
        outs = run_chain(stages, params, xs, env=cpu_env)
        assert len(outs) == 3
        spans = tr.spans
        root = [s for s in spans if s["name"] == "chain.stream"]
        assert len(root) == 1
        # every stage process contributed spans, all under ONE trace id
        for k in range(2):
            stage_spans = [s for s in spans
                           if s["name"] == f"stage{k}.infer"]
            assert len(stage_spans) == 3, \
                f"stage {k}: {[s['name'] for s in spans]}"
            for s in stage_spans:
                assert s["trace"] == tid
                assert s["parent"] == root[0]["span"]
                assert s["proc"] == f"stage{k}"
    finally:
        tr.enabled = was_enabled
        tr.process = old_proc
        tr._remote_parent = None
        tr.clear()
