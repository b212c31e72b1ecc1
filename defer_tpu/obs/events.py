"""Flight recorder: a bounded, seq-stamped structured event ring.

Traces answer "where did this request's time go"; histograms answer
"how fast is this stage" — neither answers "WHAT HAPPENED": the shed
that bounced a tenant, the tier offer that degraded to tcp, the
straggler flag, the replan suggestion, the replica that died.  Those
are rare, structured control-plane facts, and this module is their
substrate: every process keeps one :class:`FlightRecorder` (module
singleton via :func:`recorder`), subsystems :func:`emit` events into
it, and the ring is

* **bounded** — past ``capacity`` the OLDEST event is evicted per
  append and ``events.dropped`` counts the loss (same contract as the
  tracer's span buffer);
* **seq-stamped** — a per-process monotone sequence number, so a
  consumer can prove it saw every event (gap = drop);
* **timeline-aligned** — ``t_us`` comes from the process tracer's
  anchored clock (:meth:`Tracer.now_us`), and a ``clock_adjust``
  shifts buffered events along with buffered spans, so events and
  spans interleave on ONE Perfetto-coherent axis;
* **wire-schematized** — an event is a flat JSON-safe dict
  (``{"kind", "seq", "t_us", "proc", "data"}``), shippable in an
  ``obs_push`` frame, a control reply, or a bench row, and
  :func:`validate_event` is the loud schema check both ends share.

Cluster-wide: stage nodes piggyback new events on their ``obs_push``
frames (``runtime/node.py``), answer ``{"cmd": "events_since"}``
control queries, and :class:`~defer_tpu.obs.cluster.ClusterView`
merges every process's stream into one ordered log
(``monitor --events``).  See docs/OBSERVABILITY.md for the kind table.
"""

from __future__ import annotations

import collections
import os
import threading

from .registry import REGISTRY
from .trace import register_anchor_hook, tracer

#: known event kinds -> one-line meaning (docs/OBSERVABILITY.md mirrors
#: this table).  Emitting an unknown kind raises: the schema is the
#: contract that makes a merged cluster-wide log queryable.
EVENT_KINDS = {
    "admit": "front door admitted one unit (tenant, rid)",
    "shed": "admission shed one unit (tenant, reason, predicted_ms)",
    "tier": "a hop negotiated its transport tier (hop, tier)",
    "tier_fallback": "a colocated-tier offer degraded to tcp (hop)",
    "straggler": "the detector flagged a stage (stage, reason, ratio)",
    "replan": "a replan suggestion was produced (moved, corrections)",
    "node_dead": "a watched node's push stream died (addr)",
    "watchdog": "the dispatcher watchdog fired (action, gen)",
    "stream_begin": "a data stream opened on a stage node (stage)",
    "stream_end": "a data stream drained on a stage node (stage, n)",
    "client_open": "a tenant connection said hello (tenant)",
    "client_close": "a tenant connection finished or died (tenant)",
    "decode_join": "a decode request claimed an engine slot (rid)",
    "decode_cancel": "a decode request's slot was reclaimed (rid)",
    "decode_done": "a decode request's answer was written to its client: "
                   "its life in milliseconds from admitted (rid, tenant, "
                   "prompt, new_tokens, popped_ms, prefill_ms, first_ms, "
                   "last_ms, delivered_ms, forced_steps, pass_rounds, "
                   "worst_gap_ms, first_step, last_step)",
    "model_drift": "a stage's measured service drifted from the cost "
                   "model's prediction (stage, rel_err)",
    "redial": "a connect_retry attempt failed and backed off "
              "(addr, attempt, delay_ms, error)",
    "replica_lost": "a fan-in upstream connection died mid-stream "
                    "(hop, error)",
    "failover": "a replay fan-out healed a dead channel "
                "(hop, chan, addr, replayed, recovery_ms)",
    "quiesce": "a stage drained to a stable sequence point "
               "(hop, processed)",
    "cutover": "a live replan cut the chain over mid-stream "
               "(stages, quiesced)",
    "backend_lost": "the serve front door's chain backend died "
                    "(error, shed)",
    "replica_respawn": "the chain supervisor respawned a dead replica "
                       "(stage, replica, addr, rc)",
    "recompile": "XLA compiled a program after warmup — one event per "
                 "episode (count, program)",
    "mem_pressure": "live device-array bytes crossed the configured "
                    "threshold (bytes, threshold, live_arrays)",
    "host_pause": "a program phase took far longer than it usually does "
                  "(layer, phase, round, passes where the phase keeps a "
                  "typical time a count, wall_ms, typical_ms, cpu_ms, "
                  "proc_cpu_ms; since the thread's baseline: since_ms, "
                  "vol_switches, invol_switches, major_faults, "
                  "runq_wait_ms, steal_ms, gc_collections)",
    "setup_done": "the process's set-up is over: where its time went, in "
                  "seconds that sum to elapsed_s (elapsed_s, import_s, "
                  "place_s, relay_s, state_s, trace_s, lower_s, "
                  "compile_s, cache_load_s, first_call_s, warm_run_s, "
                  "unnamed_s, costliest, threads)",
    "journal": "the black-box journal spiller started or stopped "
               "(action, dir)",
    "postmortem": "a postmortem bundle was assembled "
                  "(reason, out, procs, first_fault)",
}

#: the wire schema's required keys (and the only keys)
_WIRE_KEYS = frozenset({"kind", "seq", "t_us", "proc", "data"})

#: evictions across every recorder in this process (the visible price
#: of the cap, like ``trace.dropped_spans``)
_DROPPED = REGISTRY.counter("events.dropped")


def validate_event(doc) -> dict:
    """Loudly check one wire-form event; returns it.  Both ends of the
    events plane share this — a malformed event fails at the boundary,
    not deep inside a monitor render."""
    if not isinstance(doc, dict) or set(doc) != _WIRE_KEYS:
        raise ValueError(f"event must have exactly keys "
                         f"{sorted(_WIRE_KEYS)}, got {doc!r}")
    if doc["kind"] not in EVENT_KINDS:
        raise ValueError(f"unknown event kind {doc['kind']!r}; "
                         f"known: {sorted(EVENT_KINDS)}")
    if not isinstance(doc["seq"], int) or doc["seq"] < 0:
        raise ValueError(f"event seq must be a non-negative int, "
                         f"got {doc['seq']!r}")
    if not isinstance(doc["t_us"], int):
        raise ValueError(f"event t_us must be an int, got {doc['t_us']!r}")
    if not isinstance(doc["proc"], str):
        raise ValueError(f"event proc must be a str, got {doc['proc']!r}")
    if not isinstance(doc["data"], dict):
        raise ValueError(f"event data must be a dict, got {doc['data']!r}")
    return doc


class FlightRecorder:
    """One process's bounded structured-event ring."""

    #: default ring capacity (events, not bytes); the serving burst the
    #: bench provokes fits with an order of magnitude to spare
    DEFAULT_CAPACITY = int(os.environ.get("DEFER_EVENTS_CAP",
                                          "4096") or 4096)

    def __init__(self, process: str | None = None,
                 capacity: int | None = None):
        self.process = process or f"pid{os.getpid()}"
        self.capacity = (self.DEFAULT_CAPACITY if capacity is None
                         else max(1, int(capacity)))
        self._ring: collections.deque[dict] = collections.deque()
        self._lock = threading.Lock()
        #: next seq to stamp (monotone, never reused)
        self._seq = 0
        #: events ever removed from the FRONT (drained or evicted) —
        #: the ``events_since`` cursor anchor, same contract as
        #: ``Tracer._base``
        self._base = 0
        #: events evicted because the ring was full (lifetime)
        self.dropped = 0

    # -- recording ---------------------------------------------------------

    def emit(self, kind: str, **data) -> dict:
        """Append one event (O(1) under a short lock); returns it.
        ``data`` values must be JSON-safe — they ride obs_push frames
        verbatim."""
        if kind not in EVENT_KINDS:
            raise ValueError(f"unknown event kind {kind!r}; "
                             f"known: {sorted(EVENT_KINDS)}")
        ev = {"kind": kind, "proc": self.process, "data": data}
        with self._lock:
            # t_us stamped UNDER the same lock that assigns seq, so one
            # process's seq order and timestamp order can never invert
            # (merge_events' tie-break relies on it)
            ev["t_us"] = tracer().now_us()
            ev["seq"] = self._seq
            self._seq += 1
            self._ring.append(ev)
            over = len(self._ring) - self.capacity
            for _ in range(over):
                self._ring.popleft()
                self.dropped += 1
                self._base += 1
                _DROPPED.n += 1
        return ev

    def shift_anchor(self, delta_us: int) -> None:
        """Shift buffered events by ``delta_us`` — called through the
        tracer's anchor hook when a ``clock_adjust`` lands, so events
        stay coherent with the spans they interleave with."""
        with self._lock:
            for ev in self._ring:
                ev["t_us"] += int(delta_us)

    # -- reading -----------------------------------------------------------

    def events_since(self, cursor: int, limit: int | None = None
                     ) -> tuple[int, list[dict]]:
        """(new_cursor, events emitted after ``cursor``) WITHOUT
        draining — the obs_push / ``events_since`` incremental read.
        ``limit`` caps one batch at the OLDEST N and the returned
        cursor stops after them, so a backlog paginates losslessly
        across successive reads (a newest-N cut would advance the
        cursor past events nobody ever saw, an invisible drop).  Only
        ring EVICTION loses events, and ``dropped`` counts that."""
        with self._lock:
            base = self._base
            snapshot = list(self._ring)
        start = max(0, cursor - base)
        out = snapshot[start:]
        if limit is not None and len(out) > limit:
            out = out[:limit]
        return base + start + len(out), out

    def cursor(self) -> int:
        """Monotone count of events ever emitted — pass back to
        :meth:`events_since` for an incremental batch."""
        with self._lock:
            return self._base + len(self._ring)

    def snapshot(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    def drain(self) -> list[dict]:
        with self._lock:
            out = list(self._ring)
            self._ring.clear()
            self._base += len(out)
        return out

    def clear(self) -> None:
        self.drain()
        self.dropped = 0


def merge_events(*batches) -> list[dict]:
    """Merge event batches from several processes into one ordered log:
    primary order is the clock-aligned ``t_us``, ties (and one
    process's burst inside one microsecond) break on per-process
    ``seq`` — so a single process's events can never reorder against
    each other.  ``(proc, seq)`` is a process-unique identity, so
    duplicates across batches (e.g. several in-process node reporters
    pushing one shared ring) collapse to one entry."""
    seen: set[tuple] = set()
    out = []
    for batch in batches:
        for ev in batch:
            key = (ev.get("proc"), ev.get("seq"))
            if key in seen:
                continue
            seen.add(key)
            out.append(ev)
    out.sort(key=lambda e: (e.get("t_us", 0), e.get("proc", ""),
                            e.get("seq", 0)))
    return out


#: process singleton, timeline-coupled to the process tracer
_RECORDER = FlightRecorder()
register_anchor_hook(_RECORDER.shift_anchor)


def recorder() -> FlightRecorder:
    return _RECORDER


def emit(kind: str, **data) -> dict:
    """Emit one event into the process recorder (the one-liner call
    sites use)."""
    return _RECORDER.emit(kind, **data)
