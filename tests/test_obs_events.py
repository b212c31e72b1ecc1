"""Flight recorder (obs/events.py): ring bounds, wire-schema
roundtrip, cluster-wide merge ordering, and the admission layer's
shed/admit emission."""

import json

import pytest

from defer_tpu.obs.cluster import ClusterView
from defer_tpu.obs.events import (EVENT_KINDS, FlightRecorder,
                                  merge_events, recorder, validate_event)
from defer_tpu.serve import AdmissionController, TenantConfig


# ---------------------------------------------------------------------------
# ring bounds
# ---------------------------------------------------------------------------

def test_ring_drops_oldest_and_counts_losses():
    rec = FlightRecorder(process="p", capacity=8)
    for i in range(13):
        rec.emit("admit", tenant="t", rid=i)
    evs = rec.snapshot()
    assert len(evs) == 8
    assert rec.dropped == 5
    # the OLDEST were evicted: the survivors are seqs 5..12, contiguous
    assert [e["seq"] for e in evs] == list(range(5, 13))
    assert [e["data"]["rid"] for e in evs] == list(range(5, 13))
    # the cursor contract survives eviction: a reader that was at 0
    # sees only what is left, and the seq gap proves the loss
    cursor, batch = rec.events_since(0)
    assert cursor == 13 and len(batch) == 8
    cursor2, batch2 = rec.events_since(cursor)
    assert cursor2 == cursor and batch2 == []
    rec.emit("shed", tenant="t", reason="deadline")
    cursor3, batch3 = rec.events_since(cursor)
    assert len(batch3) == 1 and batch3[0]["kind"] == "shed"


def test_ring_limit_paginates_losslessly():
    """A limited read returns the OLDEST events and a partial cursor,
    so a backlog drains across successive reads with nothing skipped."""
    rec = FlightRecorder(process="p", capacity=64)
    for i in range(10):
        rec.emit("admit", rid=i)
    cursor, batch = rec.events_since(0, limit=3)
    assert [e["data"]["rid"] for e in batch] == [0, 1, 2]
    cursor, batch = rec.events_since(cursor, limit=3)
    assert [e["data"]["rid"] for e in batch] == [3, 4, 5]
    cursor, batch = rec.events_since(cursor, limit=100)
    assert [e["data"]["rid"] for e in batch] == [6, 7, 8, 9]
    assert rec.events_since(cursor, limit=3)[1] == []


# ---------------------------------------------------------------------------
# wire schema
# ---------------------------------------------------------------------------

def test_event_wire_roundtrip_and_validation():
    rec = FlightRecorder(process="stage1")
    ev = rec.emit("tier", hop="stage1", tier="shm", fallback=False)
    wire = json.loads(json.dumps(ev))      # the obs_push trip
    assert validate_event(wire) == ev
    with pytest.raises(ValueError, match="unknown event kind"):
        validate_event({**wire, "kind": "nope"})
    with pytest.raises(ValueError, match="exactly keys"):
        validate_event({k: v for k, v in wire.items() if k != "proc"})
    with pytest.raises(ValueError, match="seq"):
        validate_event({**wire, "seq": -1})
    with pytest.raises(ValueError, match="unknown event kind"):
        rec.emit("not_a_kind")
    # every documented kind is emittable
    for kind in EVENT_KINDS:
        validate_event(json.loads(json.dumps(
            FlightRecorder(process="x").emit(kind))))


# ---------------------------------------------------------------------------
# cluster-wide merge
# ---------------------------------------------------------------------------

def _push_with_events(stage, events, dropped=0):
    return {"node": {"stage": stage, "replica": None},
            "processed": 0,
            "events": {"dropped": dropped, "events": events}}


def test_cluster_view_merges_cross_process_events_in_order():
    """Two processes' event streams merge by aligned timestamp with
    per-process seq as the tie break — one process's events can never
    reorder against each other."""
    a = FlightRecorder(process="stage0")
    b = FlightRecorder(process="stage1")
    e0 = a.emit("stream_begin", hop="stage0")
    e1 = b.emit("stream_begin", hop="stage1")
    e2 = a.emit("stream_end", hop="stage0", n=4)
    # fabricate aligned timestamps so the intended order is unambiguous
    e0["t_us"], e1["t_us"], e2["t_us"] = 100, 200, 300
    view = ClusterView()
    view.ingest(_push_with_events(0, [e0, e2], dropped=0), "a:1")
    view.ingest(_push_with_events(1, [e1], dropped=2), "b:2")
    merged = view.events(include_local=False)
    assert [e["t_us"] for e in merged] == [100, 200, 300]
    assert [e["proc"] for e in merged] == ["stage0", "stage1", "stage0"]
    assert view.events_dropped == 2
    # same-instant burst from ONE process stays in seq order
    e3 = b.emit("straggler", stage=1, reason="slow")
    e4 = b.emit("replan", moved=True)
    e3["t_us"] = e4["t_us"] = 400
    assert [e["seq"] for e in merge_events([e4, e3])
            if e["t_us"] == 400] == [e3["seq"], e4["seq"]]
    # take_events drains incrementally (the monitor's read)
    assert len(view.take_events()) == 3
    assert view.take_events() == []


# ---------------------------------------------------------------------------
# emission sites
# ---------------------------------------------------------------------------

def test_admission_emits_shed_and_admit_events():
    rec = recorder()
    before = rec.cursor()
    ctl = AdmissionController(service_s=lambda: 0.2)
    ctl.configure(TenantConfig("evt_t", deadline_ms=100.0))
    assert not ctl.admit("evt_t", object()).admitted
    ctl2 = AdmissionController(service_s=lambda: 0.0)
    ctl2.configure(TenantConfig("evt_t2"))
    assert ctl2.admit("evt_t2", object()).admitted
    _, evs = rec.events_since(before)
    kinds = {(e["kind"], e["data"].get("tenant")) for e in evs}
    assert ("shed", "evt_t") in kinds
    assert ("admit", "evt_t2") in kinds
    shed = next(e for e in evs if e["kind"] == "shed")
    assert shed["data"]["reason"] == "deadline"
    assert shed["data"]["predicted_ms"] > 100.0


# ---------------------------------------------------------------------------
# host_pause: the pause watch's event (obs/profile.py PauseWatcher)
# ---------------------------------------------------------------------------

def test_a_host_pause_event_validates_and_survives_the_wire():
    assert "host_pause" in EVENT_KINDS
    rec = FlightRecorder(process="p")
    full = dict(layer="decode", phase="sync", round=128, wall_ms=512.25,
                typical_ms=18.6, cpu_ms=0.1, proc_cpu_ms=0.4,
                since_ms=900.0, vol_switches=31, invol_switches=2,
                major_faults=0, runq_wait_ms=488.0, steal_ms=0.0,
                gc_collections=1)
    # a platform without /proc leaves those fields out: still one event
    bare = {k: v for k, v in full.items()
            if k not in ("runq_wait_ms", "steal_ms")}
    for data in (full, bare):
        ev = rec.emit("host_pause", **data)
        back = validate_event(json.loads(json.dumps(ev)))
        assert back["kind"] == "host_pause" and back["data"] == data
    merged = merge_events(rec.snapshot(), rec.snapshot())
    assert [e["seq"] for e in merged] == [0, 1]
