"""Standalone stage-node processes: the multi-process MPMD chain.

Reference parity: the reference's compute node is a separate process on
another machine that receives its partition, then serves the chain forever —
recv activation, predict, relay to its successor (reference
src/node.py:80-108, boot at src/node.py:110-127).  The last node relays back
to the dispatcher (reference src/dispatcher.py:51-55).

The TPU-native redesign keeps the topology but none of the machinery:

* The partition arrives as a *compiled artifact* — StableHLO + weights
  (``utils/export.py``) loaded with zero model code — not Keras JSON
  rebuilt layer by layer (src/node.py:31-37).
* One typed framed connection per hop (``transport/framed.py``) instead of
  three fixed ports; the hop codec (raw / lzb / blockfloat) is the ZFP+LZ4
  analogue and is *symmetric* (the reference's decode sides are buggy,
  SURVEY.md §3.5).
* Readiness is connect-with-retry, not 5-second poll loops
  (src/node.py:33,96), and shutdown is an in-band END frame that cascades
  down the chain, not process kill.

The SPMD mesh engine (``runtime/spmd.py``) is the primary execution model;
this chain exists for the reference's one topology it doesn't cover —
stages as separate processes/hosts with a network between them.
"""

from __future__ import annotations

import collections
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
from typing import Any, Sequence

import numpy as np

from ..obs import REGISTRY, LatencyHistogram, new_span_id, tracer
from ..obs.report import ObsReporter, WatermarkSplit
from ..transport.channel import AsyncReceiver, AsyncSender, _sampled
from ..transport.ici import IciSender
from ..transport.framed import (K_ACK, K_BYTES, K_CTRL, K_END, K_TENSOR,
                                K_TENSOR_SEQ, configure_socket,
                                connect_retry, recv_expect, recv_frame,
                                send_ack, send_ctrl, send_end, send_frame)
from ..transport.branch import BranchJoin, BroadcastSender
from ..transport.replay import ACK_EVERY, ReplayFanOut
from ..transport.replicate import FanInMerge, FanOutSender


#: guards lazy creation of per-node watermark splitters (``__new__``-
#: built test stubs have no __init__ to create one in)
_WM_LOCK = threading.Lock()

#: serve()-loop sentinel a ``shutdown`` control command enqueues: a
#: persistent node returns its accumulated stream total NOW
_SHUTDOWN = object()

#: fan-in dedup window under failover: how far behind the merge head a
#: replayed duplicate may land and still be absorbed silently.  Bounds
#: the fan-out's retained window (ack lag + reorder capacity) with an
#: order of magnitude of slack — beyond it, a duplicate is a protocol
#: bug and raises exactly as in strict mode.
_REPLAY_DEDUP_WINDOW = 4096


def _connect_retry(host: str, port: int, timeout_s: float = 30.0
                   ) -> socket.socket:
    """Connect, retrying while the peer boots (replaces the reference's
    sleep-5 polling rendezvous, src/node.py:95-96).  The policy lives in
    :func:`transport.framed.connect_retry`; this alias keeps the
    historical call sites (and test monkeypatch points)."""
    return connect_retry(host, port, timeout_s)


def _parse_hostport(s: str, default_host: str = "127.0.0.1"
                    ) -> tuple[str, int]:
    host, _, port = s.rpartition(":")
    return (host or default_host), int(port)


def _parse_hops(s: str) -> list[tuple[str, int]]:
    """``host:port[,host:port...]`` -> list of (host, port).  More than
    one entry means the downstream stage is replicated: the sender fans
    out round-robin with sequence numbers (docs/TRANSPORT.md)."""
    return [_parse_hostport(p) for p in s.split(",") if p]


class StageNode:
    """One compute node of a process chain: recv -> stage fn -> relay.

    ``python -m defer_tpu node --listen :5000`` boots an EMPTY node that
    receives its stage artifact in-band over the control handshake —
    completing parity with the reference node, which also boots with
    nothing and gets its model over the wire (src/node.py:20-55).
    ``--artifact stage_k.zip --next host:5000`` pre-loads from a local
    file instead (the r3/r4 behavior, kept for pre-provisioned hosts).

    Replication (docs/TRANSPORT.md): ``--next`` may name R comma-
    separated replicas of the downstream stage — frames then fan out
    round-robin with sequence numbers.  ``--fan-in R`` declares R
    sequence-stamped upstream connections, merged through a bounded
    reorder buffer that releases frames strictly in order.  ``--replica
    N`` labels this process's spans/stats as replica N of its stage.
    """

    #: class-level defaults so instances built via ``__new__`` (tests)
    #: still serve; the overlapped loop keeps ``inflight`` device
    #: dispatches un-synced and ``rx_depth``/``tx_depth`` decoded frames
    #: of queue slack per side
    overlap: bool = True
    rx_depth: int = 8
    tx_depth: int = 8
    inflight: int = 2
    fan_in: int = 1
    replica: int | None = None
    #: branched stage graphs (docs/TRANSPORT.md): ``fan_mode="broadcast"``
    #: sends every frame to EVERY downstream hop (parallel branches all
    #: read the fork tensor) instead of round-robin replica fan-out;
    #: ``branch`` labels this node's path through a fork/join region
    #: (spans/stats become ``stageK.bJ``, and the outbound stream_begin
    #: carries the path so the join can slot this connection); ``join_in
    #: >= 2`` makes this node the region's join — P labeled upstream
    #: connections merge through a (path, seq) reorder buffer and the
    #: multi-input stage program runs on all P parts per sequence
    fan_mode: str = "rr"
    branch: int | None = None
    join_in: int = 0
    #: bench-only simulated accelerator seconds per frame (serialized in
    #: the compute loop, sleeping — not spinning — so concurrent stage
    #: processes on a small host still overlap like real devices would;
    #: how the DAG smoke makes branch compute delay-bound on 1 core)
    infer_delay_s: float = 0.0
    next_hops: list[tuple[str, int]] | None = None
    #: outbound transport-tier policy (docs/TRANSPORT.md): "auto" walks
    #: the tier ladder on the downstream dial — ici (same process +
    #: same mesh, device-resident jax.Arrays) over local (same process,
    #: host ndarray by reference) over shm (same host, shared-memory
    #: ring) over tcp — via tier_probe handshakes that silently degrade
    #: when a rung's proof fails; "ici"/"local"/"shm" pin that single
    #: rung's offer; "tcp" never probes — the status-quo wire path
    tier: str = "tcp"
    #: jax device index this node's stage program is pinned to (the
    #: deployment half of the ici tier: upstream device_puts each
    #: activation here, the program consumes it device-resident); None
    #: = the backend default placement
    device: int | None = None
    #: answer inbound tier probes (False = refuse every offer: the hop
    #: degrades to tcp with the sender's fallback counter bumped)
    tier_accept: bool = True
    #: negotiated tiers, for stats/obs ("local"/"shm"/"tcp"; None = no
    #: data path yet)
    tier_out: str | None = None
    tier_in: str | None = None
    #: outbound hops that WANTED a colocated tier but degraded to tcp —
    #: the per-hop twin of the process-global
    #: ``transport.tier_fallback`` counter (a shared count cannot tell a
    #: degraded hop from a never-offered one)
    tier_fallbacks: int = 0
    #: waterfall sampling period carried by the trace context (0 = every
    #: frame records spans, N >= 1 = only wire-seq multiples of N)
    trace_sample_every: int = 0
    #: seq-replay failover substrate (docs/ROBUSTNESS.md): fan-out hops
    #: retain sent frames until the downstream fan-in's cumulative
    #: ``replay_ack`` and HEAL dead replica channels (redial + replay);
    #: replica hops relay acks upstream; fan-in hops ack, dedup replay
    #: overlaps, and tolerate a replica's mid-stream EOF for one redial
    #: grace period
    failover: bool = False
    #: keep serving across stream segments: serve() accumulates per-
    #: stream tensor counts and returns only on a ``shutdown`` control
    #: command — the node half of a zero-downtime live replan
    #: (quiesce -> redeploy -> resume, docs/ROBUSTNESS.md)
    persist: bool = False
    #: redial grace a fan-in allows a dead upstream before poisoning
    #: the merge (the chain supervisor's respawn must beat this)
    failover_grace_s: float = 30.0
    #: live fan-in data connections (the ack plane's targets); class
    #: default covers ``__new__``-built stubs
    _fanin_conns: list | None = None
    #: bumped per fan-in data-path registration — a respawned replica's
    #: dial-in inside the grace period cancels the delayed poisoning
    _fanin_epoch: int = 0
    #: live data-path channels (set once a connection proves to be the
    #: stream) — what obs_push reads queue depths/watermarks from
    _live_rx = None
    _live_tx = None
    #: branch-join reorder buffer (class default covers ``__new__``-
    #: built test stubs)
    _join: BranchJoin | None = None
    #: per-NODE infer histogram (None on ``__new__``-built stubs): the
    #: registry's ``node.infer_s`` is process-wide, which in-process
    #: thread chains share across nodes — this instance copy keeps
    #: stats/obs_push attribution per node everywhere
    infer_hist: LatencyHistogram | None = None
    #: per-NODE host-sync histogram: seconds spent materializing stage
    #: outputs to host memory (``np.asarray`` — the D2H half of the
    #: round-trip every non-ici hop pays; an ici hop records ZERO
    #: samples here, which is the observable proof the round-trip is
    #: gone).  Instance copy for the same attribution reason as
    #: ``infer_hist``; ``node.host_sync_s`` is the registry twin.
    host_sync_hist: LatencyHistogram | None = None
    #: per-NODE phase histograms (docs/OBSERVABILITY.md §Profiling) —
    #: the X-ray of the opaque ``infer`` interval: ``disp_hist`` times
    #: the jit call RETURNING (host-side dispatch cost; jax queues the
    #: compute and returns), ``queue_hist`` times the frame's residency
    #: in the async in-flight window (dispatch return -> its drain
    #: turn), ``dev_hist`` times ``block_until_ready`` (device
    #: compute).  Together with ``host_sync_hist`` the four phases tile
    #: the frame: dispatch + queue + device + host_sync ≈ infer
    #: (scripts/profile_smoke.py asserts the sum).  Registry twins:
    #: ``node.dispatch_s`` / ``node.queue_s`` / ``node.device_s``.
    disp_hist: LatencyHistogram | None = None
    queue_hist: LatencyHistogram | None = None
    dev_hist: LatencyHistogram | None = None
    #: active profile_start session (obs/profile.py); None between
    #: sessions — the double-start refusal's state
    _profile = None
    #: per-subscriber watermark splitter (class default covers
    #: ``__new__``-built stubs; created lazily under ``_WM_LOCK``)
    _wm_split: WatermarkSplit | None = None
    #: analytic capacity of the deployed stage, shipped by the
    #: dispatcher in the deploy message (``flops`` / ``bytes_moved`` at
    #: the deploy batch) — what stats/obs_push MFU accounting divides
    #: by.  None until a deploy carries them (a standalone node without
    #: a dispatcher reports no MFU rather than a fabricated one).
    stage_flops: float | None = None
    stage_bytes_moved: float | None = None
    #: cached chip peak (bytes are cheap; the jax probe is not).
    #: 0.0 = probed, not a TPU (MFU stays None — utils/hw.py policy:
    #: never fabricate MFU against a guessed peak); None = not probed.
    _peak_flops_s: float | None = None
    #: sharding of the latest stage output (None until a frame ran) —
    #: what stats reads ``output_device_ids`` from
    _last_sharding = None

    def __init__(self, artifact: str | None, listen: str,
                 next_hop: str | None, *, codec: str = "raw",
                 overlap: bool = True, rx_depth: int = 8,
                 tx_depth: int = 8, inflight: int = 2,
                 fan_in: int = 1, replica: int | None = None,
                 fan_mode: str = "rr", branch: int | None = None,
                 join_in: int = 0, infer_delay_s: float = 0.0,
                 tier: str = "tcp", tier_accept: bool = True,
                 device: int | None = None, failover: bool = False,
                 persist: bool = False):
        # bind before the (slow: jax import + StableHLO deserialize)
        # artifact load so upstream connect-retries land as soon as the
        # process exists
        host, port = _parse_hostport(listen, "0.0.0.0")
        self._srv = socket.create_server((host, port))
        self.address = self._srv.getsockname()
        self.prog = None
        if artifact is not None:
            from ..utils.export import load_stage_program
            self.prog = load_stage_program(artifact)
        self.next_hops = _parse_hops(next_hop) if next_hop else None
        self.codec = codec
        self.overlap = overlap
        self.rx_depth = rx_depth
        self.tx_depth = tx_depth
        self.inflight = max(1, inflight)
        self.fan_in = max(1, fan_in)
        self.replica = replica
        if fan_mode not in ("rr", "broadcast"):
            raise ValueError(f"fan_mode must be rr|broadcast, "
                             f"got {fan_mode!r}")
        self.fan_mode = fan_mode
        self.branch = None if branch is None else int(branch)
        self.join_in = max(0, int(join_in))
        if self.join_in == 1:
            raise ValueError("join_in must be 0 or >= 2 (a single-path "
                             "join is a plain unicast hop)")
        if self.join_in >= 2 and self.fan_in > 1:
            raise ValueError("a node cannot be both a branch join and a "
                             "replica fan-in (the two merges own "
                             "different sequence namespaces)")
        self.infer_delay_s = max(0.0, float(infer_delay_s))
        if tier not in ("tcp", "auto", "local", "shm", "ici"):
            raise ValueError(f"tier must be tcp|auto|local|shm|ici, "
                             f"got {tier!r}")
        self.tier = tier
        self.tier_accept = tier_accept
        self.tier_out = None
        self.tier_in = None
        self.tier_fallbacks = 0
        self.device = None
        if device is not None:
            self.set_device(int(device))
        self._check_tier_pin()
        self.processed = 0    # tensors relayed, lifetime
        self.reweights = 0    # weights-only re-pushes accepted
        #: trace-context K_CTRL received from upstream, held until this
        #: node opens its downstream connection so the context cascades
        #: hop by hop through the whole chain
        self._pending_trace: dict | None = None
        #: fan-in state: the reorder merge shared by the upstream reader
        #: connections and the single compute loop (lazy, lock-guarded)
        self._merge: FanInMerge | None = None
        self._merge_lock = threading.Lock()
        self.failover = bool(failover)
        self.persist = bool(persist)
        self._fanin_conns = None
        self._fanin_epoch = 0
        #: branch-join state: the (path, seq) reorder buffer shared by
        #: the P labeled upstream readers and one compute loop
        self._join: BranchJoin | None = None
        self._done_q = None   # serve()'s completion queue (set per serve)
        self._live_rx = None
        self._live_tx = None
        self.infer_hist = LatencyHistogram()
        self.host_sync_hist = LatencyHistogram()
        self.disp_hist = LatencyHistogram()
        self.queue_hist = LatencyHistogram()
        self.dev_hist = LatencyHistogram()
        self._profile = None
        #: live obs_push reporter threads (one per subscription)
        self._reporters: list[ObsReporter] = []

    @property
    def manifest(self):
        return None if self.prog is None else self.prog.manifest

    @property
    def next_hop(self) -> tuple[str, int] | None:
        """First downstream hop (back-compat accessor; ``next_hops``
        holds the full replica list)."""
        return self.next_hops[0] if self.next_hops else None

    @next_hop.setter
    def next_hop(self, value: tuple[str, int] | None) -> None:
        self.next_hops = None if value is None else [value]

    def _span_label(self) -> str:
        """Span/track prefix for this node's rx/tx/infer telemetry;
        replicas get a ``stageK.rN`` prefix and branch-path nodes a
        ``stageK.bJ`` one, so traces/stats show which parallel path a
        row belongs to instead of a flattened index."""
        m = self.manifest
        base = (f"stage{m['index']}" if m is not None
                else f"node{self.address[1]}")
        if self.replica is not None:
            return f"{base}.r{self.replica}"
        if self.branch is not None:
            return f"{base}.b{self.branch}"
        return base

    def _check_tier_pin(self) -> None:
        """Reject an explicit colocated-tier pin (``shm``/``ici``/
        ``local``) on a node whose hop rides the ordered fan machinery
        (replica into a fan-in merge, labeled branch into a join,
        fan-out next hops) — those paths are wire-framed by design, so
        :meth:`_make_tx` would silently skip the offer and run full
        codec + TCP under a tier claim with ``tier_fallbacks`` still 0.
        Mirrors the chain-level ``hop_tiers`` adjacency guard; ``auto``
        stays allowed (riding tcp there is policy, not degradation)."""
        if self.tier not in ("shm", "ici", "local"):
            return
        role = ("replica" if self.replica is not None
                else "branch" if self.branch is not None
                else "fan-out" if self.next_hops
                and len(self.next_hops) > 1 else None)
        if role is not None:
            raise ValueError(
                f"tier {self.tier!r} pinned on a {role} node; fan paths "
                f"ride tcp (drop the replicas/branching or the tier pin)")

    def set_device(self, device: int) -> None:
        """Pin this node's stage program to jax device index ``device``
        (``jax.devices()[device]``): outputs stay resident there, and
        an upstream ici hop device_puts each activation onto it before
        the program runs.  Applied to an already-loaded program
        immediately; an in-band deploy applies it at load."""
        import jax
        devs = jax.devices()
        if not 0 <= device < len(devs):
            raise ValueError(
                f"device {device} out of range: this process has "
                f"{len(devs)} jax device(s) (force a bigger host mesh "
                f"with --xla_force_host_platform_device_count)")
        self.device = device
        if self.prog is not None:
            self.prog.place(devs[device])

    def _jax_device(self):
        """The pinned jax device object, or None."""
        if self.device is None:
            return None
        import jax
        return jax.devices()[self.device]

    def _host_sync(self, y, seq=None, t0=None):
        """Materialize one stage output to host memory (``np.asarray``
        — the D2H sync every non-device-resident hop pays), timed into
        the per-node ``host_sync_hist`` + the registry twin and
        recorded as a ``stageK.host_sync`` span.  Device-resident (ici)
        hops never call this, so their zero sample count is the
        observable proof the host round-trip is gone.

        ``t0`` (the previous phase's end timestamp, when given) chains
        the phase windows end-to-start so the X-ray tiles the frame —
        a fresh clock read per phase would leak each site's own
        recording overhead into unaccounted gaps between phases.
        Returns ``(out, t_end)``; the loops close the ``infer``
        interval at ``t_end`` for the same reason."""
        # finish the (async-dispatched) device compute FIRST — timed
        # as the DEVICE phase: this histogram prices only the host
        # materialization the planner's host_sync term models; folding
        # compute wait into it would mis-calibrate host_sync_bw_s by
        # orders of magnitude
        t0 = self._device_wait(y, seq=seq, t0=t0)
        out = np.asarray(y)
        t_end = time.perf_counter()
        dt = t_end - t0
        REGISTRY.histogram("node.host_sync_s").record(dt)
        if self.host_sync_hist is not None:
            self.host_sync_hist.record(dt)
        tr = tracer()
        if tr.enabled and _sampled(self.trace_sample_every, seq):
            tr.record(f"{self._span_label()}.host_sync", t0, dt,
                      {} if seq is None else {"seq": seq})
        # (out, phase end): the caller closes the infer interval at
        # t_end, not a fresh clock read — otherwise THIS site's own
        # recording cost (worst with every-frame spans) leaks into
        # infer but no phase, and the tiling invariant drifts on
        # microsecond-scale stages
        return out, t_end

    def _dispatch(self, *xs, seq=None):
        """Run the stage program and time the DISPATCH phase — the jit
        call returning, i.e. host-side tracing/queueing cost only (jax
        dispatches asynchronously; the compute itself lands in the
        DEVICE phase at sync time).  Returns ``(t0, y)`` with ``t0``
        the dispatch start, which stays the anchor the loops measure
        the issue-to-materialize ``infer`` interval from.  A dispatch
        p50 near the infer p50 means the frame is HOST-bound — the
        MPK/persistent-program evidence this plane exists to surface.

        Returns ``(t0, t_end, y)``: ``t0`` stays the anchor the loops
        measure the issue-to-materialize ``infer`` interval from, and
        ``t_end`` seeds the QUEUE phase (:meth:`_queue_wait`) so the
        four phases tile the interval exactly."""
        t0 = time.perf_counter()
        y = self.prog(*xs)
        t_end = time.perf_counter()
        dt = t_end - t0
        REGISTRY.histogram("node.dispatch_s").record(dt)
        if self.disp_hist is not None:
            self.disp_hist.record(dt)
        tr = tracer()
        if tr.enabled and _sampled(self.trace_sample_every, seq):
            tr.record(f"{self._span_label()}.dispatch", t0, dt,
                      {} if seq is None else {"seq": seq})
        return t0, t_end, y

    def _queue_wait(self, t_end, seq=None):
        """Time from the dispatch returning to this frame's drain turn,
        recorded as the QUEUE phase — the frame's residency in the
        async in-flight window (``pending``) while OLDER frames sync
        and newer ones dispatch.  This is the overlap actually working:
        a large queue share on a non-bottleneck stage is hidden
        latency, not lost time.  The serial loop records it too (it is
        ~0 there), so dispatch + queue + device + host_sync tiles the
        ``infer`` interval on every loop and the profile plane's
        phase-sum invariant holds everywhere.  Returns the phase's end
        timestamp — pass it as the next phase's ``t0`` so the windows
        chain without leaking recording overhead between them."""
        t_now = time.perf_counter()
        dt = t_now - t_end
        REGISTRY.histogram("node.queue_s").record(dt)
        if self.queue_hist is not None:
            self.queue_hist.record(dt)
        tr = tracer()
        if tr.enabled and _sampled(self.trace_sample_every, seq):
            tr.record(f"{self._span_label()}.queue", t_end, dt,
                      {} if seq is None else {"seq": seq})
        return t_now

    def _device_wait(self, y, seq=None, t0=None):
        """``block_until_ready`` timed as the DEVICE phase: device
        compute plus the queueing of whatever in-flight window sits
        ahead of this frame.  No-op on plain host arrays.  Both host
        hops (via :meth:`_host_sync`) and device-resident ici hops
        (directly) pay this, so the DEV column is comparable across
        tiers while host_sync keeps its ici-hops-record-zero proof.

        ``t0`` chains from the previous phase's end (see
        :meth:`_host_sync`); returns THIS phase's end timestamp (its
        start when the array needs no sync) for the next window."""
        sync = getattr(y, "block_until_ready", None)
        if sync is None:
            return t0 if t0 is not None else time.perf_counter()
        if t0 is None:
            t0 = time.perf_counter()
        sync()
        t_end = time.perf_counter()
        dt = t_end - t0
        self._last_sharding = y.sharding
        REGISTRY.histogram("node.device_s").record(dt)
        if self.dev_hist is not None:
            self.dev_hist.record(dt)
        tr = tracer()
        if tr.enabled and _sampled(self.trace_sample_every, seq):
            tr.record(f"{self._span_label()}.device", t0, dt,
                      {} if seq is None else {"seq": seq})
        return t_end

    def _make_tx(self, connect_timeout_s: float):
        """Open the downstream connection(s): one :class:`AsyncSender`,
        or a :class:`FanOutSender` round-robining across a replicated
        downstream stage (announced with a ``stream_begin`` control
        frame so even a replica that ends up with zero frames knows it
        is on the data path).

        With ``tier="auto"`` a single (non-fan) hop walks the tier
        ladder (``transport.shm.offer_tier_ladder``, shared with the
        dispatcher's edges): first the colocated fast path (same
        process, zero copies), then the shared-memory tier (same host,
        payload through a shm ring with the socket demoted to a
        doorbell); ``tier="shm"`` offers only the shm rung.  Any
        rung granted keeps the socket open as the hop's lifetime
        anchor; all refused, the hop degrades to the status-quo wire
        path with this hop's fallback counted once.  Fan-out and
        replica dial-backs never probe — the ordered fan machinery is
        wire-framed by design."""
        if not self.next_hops:
            raise ValueError("no next hop configured")
        socks = [_connect_retry(*h, timeout_s=connect_timeout_s)
                 for h in self.next_hops]
        if len(socks) == 1:
            tx = None
            if self.tier != "tcp" and self.replica is None \
                    and self.branch is None:
                # branch-path hops never probe: the join end is wire-
                # framed by design (ordered (path, seq) merge)
                from ..obs.events import emit as emit_event
                from ..transport.shm import offer_tier_ladder
                self.tier_out, tx, fell_back = offer_tier_ladder(
                    socks[0], tier=self.tier, depth=self.tx_depth,
                    hop=self._span_label(), device=self._jax_device())
                if fell_back:
                    self.tier_fallbacks += 1
                emit_event("tier", hop=self._span_label(),
                           tier=self.tier_out, wanted=self.tier,
                           fallback=bool(fell_back))
            if tx is None:
                self.tier_out = "tcp"
                tx = AsyncSender(socks[0], depth=self.tx_depth,
                                 codec=self.codec,
                                 gauge="node.tx_queue_depth",
                                 span=self._span_label,
                                 hist="node.tx_s")
            if self.branch is not None:
                # announce this connection's join path BEFORE any frame
                # so the downstream join can slot it (harmless to a
                # non-join downstream, which ignores the label)
                tx.send_ctrl({"cmd": "stream_begin",
                              "path": self.branch})
        elif self.fan_mode == "broadcast":
            # branched stage graph: every parallel branch receives every
            # frame, stamped with one shared sequence number; channel i
            # is path i of the region (docs/TRANSPORT.md)
            self.tier_out = "tcp"
            tx = BroadcastSender(socks, depth=self.tx_depth,
                                 codec=self.codec,
                                 gauge="node.tx_queue_depth",
                                 span=self._span_label,
                                 hist="node.tx_s")
        else:
            self.tier_out = "tcp"
            if self.failover:
                # seq-replay fan-out (docs/ROBUSTNESS.md): retain each
                # frame until the downstream fan-in's cumulative ack,
                # heal a dead replica channel by redialing its address
                # (the chain supervisor respawns it on the same port)
                # and replaying the unacked window
                tx = ReplayFanOut(socks, self.next_hops,
                                  depth=self.tx_depth,
                                  codec=self.codec,
                                  gauge="node.tx_queue_depth",
                                  span=self._span_label,
                                  hist="node.tx_s",
                                  redial_timeout_s=connect_timeout_s)
            else:
                tx = FanOutSender(socks, depth=self.tx_depth,
                                  codec=self.codec,
                                  gauge="node.tx_queue_depth",
                                  span=self._span_label,
                                  hist="node.tx_s")
            tx.send_ctrl({"cmd": "stream_begin"})
        tx.sample_every = self.trace_sample_every
        self._live_tx = tx
        if self._pending_trace is not None:
            # cascade the dispatcher's trace context down the chain
            # (broadcast on fan-out) ahead of the first relayed tensor
            tx.send_ctrl(self._pending_trace)
        return tx, socks

    def _handle_ctrl(self, conn, msg: dict, recv=None) -> bool:
        """One control command; True if the connection should keep serving.

        ``recv`` supplies the follow-up frame of multi-frame commands
        (deploy/reweight blobs); the overlapped loop passes its rx-queue
        getter because the channel's rx thread owns all socket reads.

        deploy:   {"cmd": "deploy", "next": "host:port", "codec": ...}
                  followed by a K_BYTES artifact blob -> load, ACK.
                  The in-band analogue of the reference's weights+arch
                  sockets and \\x06 ACK (src/dispatcher.py:44-65).
        reweight: {"cmd": "reweight"} followed by a K_BYTES npz blob ->
                  swap weights in the already-loaded program, ACK
                  (redeploy without restart; no reference analogue).
        trace:    {"cmd": "trace", "trace_id": ..., "span_id": ...} ->
                  adopt the dispatcher's trace context (spans recorded
                  from here on carry its trace_id and parent under its
                  root span) and cascade the same context downstream when
                  the data connection opens.  One-way: no ACK — it rides
                  the data stream ahead of the first tensor.
        trace_dump: reply with this node's recorded spans as a K_CTRL
                  frame (and drain them) — the dispatcher stitches every
                  stage's spans into one exportable trace.
        clock_probe: reply with this process's tracer-timeline "now"
                  ({"cmd": "clock_probe_reply", "t_us", "echo"}) — one
                  leg of the dispatcher's min-RTT offset estimator
                  (obs/cluster.py).
        clock_adjust: {"cmd": "clock_adjust", "offset_us": d} -> shift
                  the tracer's wall anchor (buffered spans included) so
                  this process's spans land on the dispatcher's
                  timeline; ACKed.
        obs_subscribe: {"cmd": "obs_subscribe", "interval_ms": 250,
                  "spans": bool, "span_limit": N} -> start pushing
                  {"cmd": "obs_push"} telemetry frames back on THIS
                  connection every interval until it closes
                  (obs/report.py; the live-monitoring plane, no new
                  ports).  The subscriber must not send further
                  commands on the connection besides its final END —
                  pushes and replies would interleave mid-frame.
        """
        from ..utils.export import load_stage_program

        def _expect(kind):
            if recv is None:
                return recv_expect(conn, kind)
            got, value = recv()
            if got != kind:
                raise ConnectionError(
                    f"expected frame kind {kind}, got {got}")
            return value

        cmd = msg.get("cmd")
        if cmd == "deploy":
            blob = _expect(K_BYTES)
            self.prog = load_stage_program(blob)
            if msg.get("next"):
                self.next_hops = _parse_hops(msg["next"])
            if msg.get("codec"):
                self.codec = msg["codec"]
            if msg.get("fan_in"):
                self.fan_in = max(1, int(msg["fan_in"]))
            if msg.get("replica") is not None:
                self.replica = int(msg["replica"])
            # branched stage-graph role (docs/TRANSPORT.md): broadcast
            # fork, labeled branch path, or P-path join
            if msg.get("fan"):
                if msg["fan"] not in ("rr", "broadcast"):
                    raise ValueError(f"deploy: fan must be rr|broadcast, "
                                     f"got {msg['fan']!r}")
                self.fan_mode = msg["fan"]
            if msg.get("branch") is not None:
                self.branch = int(msg["branch"])
            if msg.get("join"):
                j = int(msg["join"])
                if j < 2:
                    raise ValueError(f"deploy: join must be >= 2, got {j}")
                if self.fan_in > 1:
                    raise ValueError("deploy: a node cannot be both a "
                                     "branch join and a replica fan-in")
                self.join_in = j
            if msg.get("infer_delay_ms") is not None:
                self.infer_delay_s = max(
                    0.0, float(msg["infer_delay_ms"]) / 1e3)
            # analytic capacity of this stage (dispatcher-computed
            # FLOPs/HBM bytes at the deploy batch): the denominator of
            # the node's live MFU accounting (obs/capacity.py)
            if msg.get("flops") is not None:
                self.stage_flops = float(msg["flops"])
            if msg.get("bytes_moved") is not None:
                self.stage_bytes_moved = float(msg["bytes_moved"])
            if msg.get("tier"):
                # outbound transport-tier policy rides the deploy
                # handshake, like the hop codec
                if msg["tier"] not in ("tcp", "auto", "local", "shm",
                                       "ici"):
                    raise ValueError(
                        f"deploy: tier must be tcp|auto|local|shm|ici, "
                        f"got {msg['tier']!r}")
                self.tier = msg["tier"]
            if msg.get("tier_accept") is not None:
                self.tier_accept = bool(msg["tier_accept"])
            # device residency rides the deploy handshake too: pin the
            # freshly loaded program before any frame arrives — and a
            # node booted with --device keeps its pin across an in-band
            # deploy that doesn't mention one (the program object is
            # new; the old placement must be re-applied to it)
            dev = msg["device"] if msg.get("device") is not None \
                else self.device
            if dev is not None:
                self.set_device(int(dev))
            self._check_tier_pin()
            send_ack(conn)
            return True
        if cmd == "reweight":
            if self.prog is None:
                raise ValueError("reweight before deploy")
            self.prog.reweight(_expect(K_BYTES))
            self.reweights += 1
            send_ack(conn)
            return True
        if cmd == "trace":
            tr = tracer()
            tr.adopt(msg)
            m = self.manifest
            tr.process = (f"stage{m['index']}" if m is not None
                          else f"node:{self.address[1]}")
            self._pending_trace = {k: v for k, v in msg.items()}
            # waterfall sampling rides the trace context: every process
            # of the chain samples the SAME 1-in-N wire sequences
            self.trace_sample_every = int(msg.get("sample_every", 0) or 0)
            for ch in (self._live_rx, self._live_tx):
                if ch is not None:
                    ch.sample_every = self.trace_sample_every
            return True
        if cmd == "clock_probe":
            send_ctrl(conn, {"cmd": "clock_probe_reply",
                             "t_us": tracer().now_us(),
                             "echo": msg.get("echo")})
            return True
        if cmd == "clock_adjust":
            tracer().shift_wall_anchor(int(msg.get("offset_us", 0)))
            REGISTRY.gauge("clock.offset_us").inc(
                float(msg.get("offset_us", 0)))
            send_ack(conn)
            return True
        if cmd == "obs_subscribe":
            rep = ObsReporter(
                self, conn,
                interval_s=float(msg.get("interval_ms", 250.0)) / 1e3,
                spans=bool(msg.get("spans", True)),
                span_limit=int(msg.get("span_limit", 256)))
            self._reporters = [r for r in self._reporters
                               if r.is_alive()] + [rep]
            rep.start()
            return True
        if cmd == "events_since":
            # flight-recorder query (docs/OBSERVABILITY.md): the events
            # emitted in THIS process since the caller's cursor, without
            # draining what obs_push subscribers read incrementally
            from ..obs.events import recorder
            rec = recorder()
            cursor, evs = rec.events_since(
                int(msg.get("cursor", 0)),
                limit=int(msg.get("limit", 512)))
            send_ctrl(conn, {"cmd": "events_reply", "events": evs,
                             "cursor": cursor, "dropped": rec.dropped})
            return True
        if cmd == "trace_dump":
            tr = tracer()
            send_ctrl(conn, {"spans": tr.drain()})
            # the trace is over once collected: stop recording so a node
            # that later serves untraced streams doesn't accumulate spans
            tr.enabled = False
            tr._remote_parent = None
            self._pending_trace = None
            return True
        if cmd == "profile_start":
            # on-demand phase profiling (obs/profile.py): bracket a
            # window; the matching profile_stop replies with the DELTA
            # phase breakdown.  A double start is refused LOUDLY — an
            # error reply, connection kept — because silently restarting
            # would corrupt the first caller's window arithmetic.
            from ..obs.profile import (ProfileSession, memory_watcher,
                                       recompile_watcher)
            if self._profile is not None:
                send_ctrl(conn, {
                    "cmd": "profile_err",
                    "error": "profile session already active on this "
                             "node (profile_stop it first)"})
                return True
            # session start marks warmup done: install the compile
            # listener and arm the one-event-per-episode emitter, prime
            # the memory gauge
            recompile_watcher().install().arm()
            memory_watcher().observe()
            sess = ProfileSession(
                {"dispatch": self.disp_hist, "queue": self.queue_hist,
                 "device": self.dev_hist,
                 "host_sync": self.host_sync_hist,
                 "infer": self.infer_hist},
                processed=lambda: self.processed,
                jax_trace_dir=msg.get("jax_trace_dir") or None)
            started = sess.start()
            self._profile = sess
            send_ctrl(conn, {"cmd": "profile_started",
                             "node": self._span_label(), **started})
            return True
        if cmd == "profile_stop":
            if self._profile is None:
                send_ctrl(conn, {
                    "cmd": "profile_err",
                    "error": "no active profile session on this node "
                             "(profile_start first)"})
                return True
            report = self._profile.stop()
            self._profile = None
            report["node"] = self._span_label()
            mm = self.manifest
            report["stage"] = None if mm is None else mm["index"]
            report["replica"] = self.replica
            send_ctrl(conn, {"cmd": "profile_report", "report": report})
            return True
        if cmd == "stats":
            # chain observability: what this node is and has done — the
            # per-node view the reference never had (SURVEY §5 metrics)
            from ..obs.profile import \
                device_memory_bytes as _dev_mem_bytes
            m = self.manifest
            reg = REGISTRY
            tx_live = self._live_tx
            cap = self._capacity()
            from ..obs.events import recorder as _recorder
            rec = _recorder()
            _, evs = rec.events_since(
                int(msg.get("event_cursor", 0)),
                limit=int(msg.get("event_limit", 256)))
            send_ctrl(conn, {
                "stage": None if m is None else m["index"],
                "name": None if m is None else m["name"],
                "replica": self.replica,
                "branch": self.branch,
                "join": self.join_in,
                "fan_in": self.fan_in,
                "processed": self.processed,
                "reweights": self.reweights,
                "codec": self.codec,
                # negotiated outbound transport tier ("ici"/"local"/
                # "shm"/"tcp"; the configured policy until a data path
                # negotiates) + this hop's degraded-offer count
                "tier": self.tier_out or self.tier,
                "tier_in": self.tier_in,
                "tier_fallbacks": self.tier_fallbacks,
                # device residency: this node's pinned jax device index
                # and — on an ici outbound hop — the cross-device
                # device_put count with the distinct (src, dst) device-
                # id pairs, the stats-level proof a hop moved data
                # between devices without touching the host
                "device": self.device,
                # where the work actually sat, read off the arrays'
                # shardings (not the request above): the devices that
                # hold the stage weights and the latest stage output
                "weight_device_ids": getattr(
                    self.prog, "weight_device_ids", []),
                "output_device_ids": (
                    sorted(d.id for d in self._last_sharding.device_set)
                    if self._last_sharding is not None else []),
                "ici_d2d": (tx_live.d2d
                            if isinstance(tx_live, IciSender) else 0),
                "ici_device_pairs": (sorted(
                    [list(p) for p in tx_live.device_pairs])
                    if isinstance(tx_live, IciSender) else []),
                "next": None if not self.next_hops
                else ",".join(f"{h}:{p}" for h, p in self.next_hops),
                # wire telemetry: this node's process-local transport view
                "tx_frames": reg.counter("transport.tx_frames").value,
                "tx_bytes": reg.counter("transport.tx_bytes").value,
                "rx_frames": reg.counter("transport.rx_frames").value,
                "rx_bytes": reg.counter("transport.rx_bytes").value,
                # per-NODE infer distribution (instance histogram, so
                # in-process thread chains stay attributable per node)
                "infer_latency_s":
                    (self.infer_hist.summary()
                     if self.infer_hist is not None
                     else reg.histogram("node.infer_s").summary()),
                # host-sync distribution: np.asarray materialization
                # seconds per frame — zero COUNT on ici hops (the
                # device-resident proof), calibration input for the
                # planner's host_sync term
                "host_sync_s":
                    (self.host_sync_hist.summary()
                     if self.host_sync_hist is not None
                     else reg.histogram("node.host_sync_s").summary()),
                # the infer X-ray (obs/profile.py): dispatch = the jit
                # call returning (host cost), queue = in-flight window
                # residency, device = block_until_ready — dispatch +
                # queue + device + host_sync tiles the infer interval
                "dispatch_s":
                    (self.disp_hist.summary()
                     if self.disp_hist is not None
                     else reg.histogram("node.dispatch_s").summary()),
                "queue_s":
                    (self.queue_hist.summary()
                     if self.queue_hist is not None
                     else reg.histogram("node.queue_s").summary()),
                "device_s":
                    (self.dev_hist.summary()
                     if self.dev_hist is not None
                     else reg.histogram("node.device_s").summary()),
                # compile/memory telemetry: XLA compilations observed
                # in this process (0 until a profile session or an
                # explicit recompile_watcher().install() hooks the
                # listener) and live device-array bytes (None when jax
                # never loaded here — a deploy-less relay stays cheap)
                "recompiles": reg.counter("jax.compiles").value,
                "mem_bytes": _dev_mem_bytes(),
                "profiling": self._profile is not None,
                # phase timing: per-frame recv+decode / encode+send
                # seconds of the data channels, plus the per-CHANNEL
                # codec-only costs — the live bottleneck estimate's
                # inputs (no blocking waits included)
                "rx_s": reg.histogram("node.rx_s").summary(),
                "tx_s": reg.histogram("node.tx_s").summary(),
                "encode_latency_s":
                    (self._live_tx.enc.summary()
                     if self._live_tx is not None
                     else reg.histogram("codec.encode_s").summary()),
                "decode_latency_s":
                    (self._live_rx.dec.summary()
                     if self._live_rx is not None
                     else reg.histogram("codec.decode_s").summary()),
                # overlap telemetry: queue occupancy of the async channel
                # layer and the un-synced device-dispatch window
                "overlap": self.overlap,
                "rx_queue_depth": reg.gauge("node.rx_queue_depth").value,
                "tx_queue_depth": reg.gauge("node.tx_queue_depth").value,
                "rx_depth": self.rx_depth,
                "tx_depth": self.tx_depth,
                # watermark PEEKS (no reset — obs_push owns the
                # per-interval reset cycle)
                "rx_watermark": self._chan_hi(self._live_rx),
                "tx_watermark": self._chan_hi(self._live_tx),
                "inflight": reg.gauge("node.inflight").value,
                # capacity accounting (obs/capacity.py): analytic stage
                # FLOPs from the deploy message, achieved FLOP/s over
                # the measured infer p50, and MFU against THIS chip's
                # peak (None when the deploy shipped no capacity or the
                # generation has no public peak)
                "flops": self.stage_flops,
                "mfu": cap.get("mfu"),
                "achieved_flops_s": cap.get("achieved_flops_s"),
                # seq-replay substrate (docs/ROBUSTNESS.md): channels
                # healed, frames retained for replay, duplicates the
                # fan-in absorbed inside its dedup window
                "failovers": getattr(tx_live, "failovers", 0),
                "replay_depth": (tx_live.replay_depth()
                                 if hasattr(tx_live, "replay_depth")
                                 else 0),
                "merge_duplicates": (self._merge.duplicates
                                     if self._merge is not None else 0),
                # this process's flight-recorder tail (bounded; obs_push
                # streams the same ring incrementally) — how a teardown-
                # time stats sweep sees the failover/quiesce timeline
                # without a live subscription
                "events": {"dropped": rec.dropped, "events": evs},
            })
            return True
        if cmd == "quiesce":
            # drain to a stable sequence point (docs/ROBUSTNESS.md): the
            # reply comes only once nothing is in flight on this node —
            # the per-stage half of a live replan's safe cutover
            at = msg.get("at_seq")
            processed = self._quiesce(
                None if at is None else int(at),
                float(msg.get("timeout_s", 30.0)))
            from ..obs.events import emit as emit_event
            emit_event("quiesce", hop=self._span_label(),
                       processed=processed)
            send_ctrl(conn, {"cmd": "quiesced", "processed": processed})
            return True
        if cmd == "shutdown":
            # a persistent node exits its serve loop; a one-shot node
            # ACKs harmlessly (its serve returns at stream end anyway)
            send_ack(conn)
            if self._done_q is not None:
                self._done_q.put(_SHUTDOWN)
            return True
        raise ValueError(f"unknown control command {msg!r}")

    def _quiesce(self, at_seq: int | None, timeout_s: float) -> int:
        """Block until this node's data plane is drained and stable:
        ``processed`` past ``at_seq`` (when given) and unchanged across
        consecutive samples, no dispatch in flight, live queues and the
        reorder merge empty.  Returns the stable processed count;
        TimeoutError if the node never settles (frames still arriving —
        the caller quiesced mid-segment instead of at a boundary)."""
        deadline = time.monotonic() + timeout_s
        inflight_g = REGISTRY.gauge("node.inflight")
        last = -1
        while True:
            p = self.processed
            rx, tx = self._live_rx, self._live_tx
            merge = self._merge
            idle = (
                (at_seq is None or p >= at_seq)
                and p == last
                and inflight_g.value == 0
                and (rx is None or rx.qsize() == 0)
                and (tx is None or tx.qsize() == 0)
                and (merge is None or merge.qsize() == 0))
            if idle:
                return p
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"quiesce: node did not stabilize within "
                    f"{timeout_s:.1f}s (processed {p}, at_seq {at_seq})")
            last = p
            time.sleep(0.05)

    # -- live observability (obs_push payloads) -----------------------------

    def _capacity(self) -> dict:
        """Live MFU accounting for stats/obs_push: the deploy message's
        analytic stage FLOPs against this node's own measured infer p50
        and ITS OWN chip peak.  Empty when no deploy shipped capacity;
        ``mfu`` is None — never a number — off-TPU, where there is no
        peak to divide by."""
        if self.stage_flops is None:
            return {}
        if self._peak_flops_s is None:
            import jax

            from ..utils import hw
            # the device this node computes on; a TPU kind missing
            # from utils/hw.py raises instead of reporting no MFU
            dev = self._jax_device() or jax.devices()[0]
            self._peak_flops_s = hw.peak_flops(hw.detect_chip(dev))
        hist = self.infer_hist
        p50 = hist.quantile(0.5) if hist is not None and hist.count \
            else 0.0
        from ..obs.capacity import achieved_mfu
        mfu = achieved_mfu(self.stage_flops, p50,
                           self._peak_flops_s or 0.0)
        return {
            "flops": self.stage_flops,
            "bytes_moved": self.stage_bytes_moved,
            "achieved_flops_s": (self.stage_flops / p50
                                 if p50 > 0 else None),
            "mfu": mfu,
        }

    @staticmethod
    def _chan_hi(chan) -> int:
        """Peek a channel's occupancy watermark without resetting it."""
        if chan is None:
            return 0
        try:
            return max(int(chan.hi), chan.qsize())
        except (AttributeError, TypeError):
            return 0

    def _wm(self) -> WatermarkSplit:
        with _WM_LOCK:
            if self._wm_split is None:
                self._wm_split = WatermarkSplit()
            return self._wm_split

    def obs_register(self, sid: int) -> None:
        """Register a push subscriber with the watermark splitter (one
        per :class:`ObsReporter`; see ``WatermarkSplit``)."""
        self._wm().register(sid)

    def obs_unregister(self, sid: int) -> None:
        self._wm().unregister(sid)

    def obs_snapshot(self, *, cursor: int = 0, include_spans: bool = True,
                     span_limit: int = 256,
                     subscriber: int | None = None,
                     event_cursor: int = 0, event_limit: int = 128
                     ) -> tuple[dict, int, int]:
        """One ``obs_push`` payload: identity, lifetime counters, queue
        depths + per-interval watermarks (reset on read), cumulative
        latency summaries, the flight recorder's events since
        ``event_cursor`` (obs/events.py — how node events reach the
        cluster-merged log), and — when tracing is live — the spans
        recorded since ``cursor`` (without draining what ``trace_dump``
        collects at stream end).  Called by :class:`ObsReporter` on its
        own thread; everything read here is either an attribute or a
        GIL-atomic registry instrument, so the hot path never blocks on
        the reporter.

        Watermarks are reset-on-read at the CHANNEL, but split per
        subscriber here (``subscriber`` = the reporter's id,
        :class:`~defer_tpu.obs.report.WatermarkSplit`): every
        registered subscription sees the true peak since ITS OWN last
        push, so the serve front door's shedding loop and a human
        ``monitor`` can watch the same chain without corrupting each
        other's readings (the PR 5 single-subscriber caveat, fixed)."""
        m = self.manifest
        reg = REGISTRY
        rx, tx = self._live_rx, self._live_tx
        payload = {
            "node": {"stage": None if m is None else m["index"],
                     "name": None if m is None else m["name"],
                     "replica": self.replica, "branch": self.branch,
                     "join": self.join_in, "fan_in": self.fan_in,
                     "port": self.address[1], "codec": self.codec,
                     "tier": self.tier_out or self.tier,
                     "tier_in": self.tier_in,
                     "tier_fallbacks": self.tier_fallbacks,
                     "device": self.device},
            "processed": self.processed,
            "reweights": self.reweights,
            "counters": {
                "tx_frames": reg.counter("transport.tx_frames").value,
                "tx_bytes": reg.counter("transport.tx_bytes").value,
                "rx_frames": reg.counter("transport.rx_frames").value,
                "rx_bytes": reg.counter("transport.rx_bytes").value,
            },
            "queues": {
                "rx_depth": self.rx_depth, "tx_depth": self.tx_depth,
                "rx": rx.qsize() if rx is not None else 0,
                "tx": tx.qsize() if tx is not None else 0,
                "rx_hi": self._wm().take(subscriber, "rx", rx),
                "tx_hi": self._wm().take(subscriber, "tx", tx),
                "inflight": reg.gauge("node.inflight").value,
                "merge": (self._merge.qsize()
                          if self._merge is not None
                          else self._join.qsize()
                          if self._join is not None else 0),
                # retained-frame memory of a failover fan-out (the
                # monitor's replay-window gauge, docs/ROBUSTNESS.md)
                "replay": (tx.replay_depth()
                           if hasattr(tx, "replay_depth") else 0),
            },
            "latency": {
                # per-node / per-channel instruments where they exist
                # (correct attribution even when in-process nodes share
                # the registry); process-wide registry as the fallback
                "infer_s": (self.infer_hist.summary()
                            if self.infer_hist is not None
                            else reg.histogram("node.infer_s").summary()),
                "host_sync_s": (self.host_sync_hist.summary()
                                if self.host_sync_hist is not None
                                else reg.histogram(
                                    "node.host_sync_s").summary()),
                # phase X-ray (obs/profile.py): the monitor's DISP/DEV
                # columns next to HS50
                "dispatch_s": (self.disp_hist.summary()
                               if self.disp_hist is not None
                               else reg.histogram(
                                   "node.dispatch_s").summary()),
                "queue_s": (self.queue_hist.summary()
                            if self.queue_hist is not None
                            else reg.histogram(
                                "node.queue_s").summary()),
                "device_s": (self.dev_hist.summary()
                             if self.dev_hist is not None
                             else reg.histogram(
                                 "node.device_s").summary()),
                "rx_s": reg.histogram("node.rx_s").summary(),
                "tx_s": reg.histogram("node.tx_s").summary(),
                "encode_s": (tx.enc.summary() if tx is not None
                             else reg.histogram(
                                 "codec.encode_s").summary()),
                "decode_s": (rx.dec.summary() if rx is not None
                             else reg.histogram(
                                 "codec.decode_s").summary()),
            },
            # live MFU accounting (obs/capacity.py): {} until a deploy
            # ships the stage's analytic FLOPs; mfu None without an
            # honest chip peak
            "capacity": self._capacity(),
        }
        # compile/memory telemetry (obs/profile.py): observe() updates
        # the device.mem_bytes gauge AND runs the mem_pressure
        # threshold check — push cadence, never the frame hot path
        from ..obs.profile import memory_watcher
        payload["recompiles"] = reg.counter("jax.compiles").value
        payload["mem_bytes"] = memory_watcher().observe()
        tr = tracer()
        trace_doc: dict = {"dropped": tr.dropped}
        if include_spans and tr.enabled:
            cursor, spans = tr.spans_since(cursor, limit=span_limit)
            trace_doc["spans"] = spans
        payload["trace"] = trace_doc
        from ..obs.events import recorder
        rec = recorder()
        event_cursor, evs = rec.events_since(event_cursor,
                                             limit=event_limit)
        payload["events"] = {"dropped": rec.dropped, "events": evs}
        return payload, cursor, event_cursor

    def serve(self, *, connect_timeout_s: float = 30.0) -> int:
        """Serve control/data connections until a data stream completes.

        Connections are handled CONCURRENTLY (thread per connection — the
        shape of the reference node's 4-thread design, src/node.py:110-124,
        minus the polling): control connections (deploy / reweight, each
        ACKed, ending with the dispatcher's END) may arrive before or
        *during* the upstream data stream, which is relayed through the
        stage function until its END frame.  Returns the number of tensors
        the completed data stream processed.  The END is forwarded
        downstream before closing, so shutdown cascades through the chain
        to the dispatcher's result server.
        """
        import queue as _q
        import threading

        done: _q.Queue = _q.Queue()
        self._done_q = done  # the fan-in compute loop reports here too

        def worker(conn):
            try:
                configure_socket(conn)
                n = self._serve_conn(conn, connect_timeout_s)
                if n is not None:
                    done.put(n)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                done.put(e)
            finally:
                conn.close()

        total = 0
        self._srv.settimeout(0.25)
        try:
            while True:
                try:
                    conn, _ = self._srv.accept()
                except TimeoutError:  # socket.timeout is TimeoutError >=3.10
                    conn = None
                if conn is not None:
                    threading.Thread(target=worker, args=(conn,),
                                     daemon=True).start()
                try:
                    r = done.get_nowait()
                except _q.Empty:
                    continue
                if r is _SHUTDOWN:
                    return total
                if isinstance(r, BaseException):
                    raise r
                if not self.persist:
                    return r
                # persistent node: the segment is done, keep serving
                # until a shutdown control command (live replan's
                # quiesce -> redeploy -> resume rides stream segments)
                total += r
        finally:
            self._srv.close()

    def _serve_conn(self, conn, connect_timeout_s: float) -> int | None:
        """One connection: None if it was control-only, else tensor count.

        ``overlap=True`` (default) runs the three-phase overlapped loop
        (:meth:`_serve_conn_overlapped`); ``overlap=False`` keeps the
        strictly serial recv -> infer -> send loop as the measurable
        baseline (``--no-overlap``, ``scripts/chain_overlap_smoke.py``).
        With ``fan_in > 1`` every connection instead feeds the shared
        reorder merge (:meth:`_serve_conn_fanin`) and ONE compute loop
        consumes the merged in-order stream; with ``join_in >= 2`` the
        connections feed the (path, seq) branch join
        (:meth:`_serve_conn_join`) and the compute loop applies the
        multi-input merge program to each complete sequence.
        """
        if self.join_in >= 2:
            return self._serve_conn_join(conn, connect_timeout_s)
        if self.fan_in > 1:
            return self._serve_conn_fanin(conn, connect_timeout_s)
        if self.overlap:
            return self._serve_conn_overlapped(conn, connect_timeout_s)
        return self._serve_conn_serial(conn, connect_timeout_s)

    def _serve_conn_overlapped(self, conn,
                               connect_timeout_s: float) -> int | None:
        """Three-phase overlap: rx thread -> compute loop -> tx thread.

        An :class:`AsyncReceiver` decodes upstream frames into a bounded
        queue while this thread computes, and an :class:`AsyncSender`
        encodes/sends relayed tensors from a bounded queue — so the rx of
        microbatch j+1, the compute of j, and the tx of j-1 run
        concurrently, and per-hop latency tends to max(rx, compute, tx)
        instead of their sum.  The compute loop additionally keeps up to
        ``inflight`` stage dispatches un-synced (JAX async dispatch): the
        host-side ``np.asarray`` sync of output j-1 overlaps the device
        compute of j.  Bounded queues preserve end-to-end backpressure —
        a stuck downstream fills the tx queue, stalls this loop, fills
        the rx queue, and TCP pushes back upstream.

        ``node.infer_s`` here measures issue-to-materialize (device queue
        included), matching what the overlap actually hides.

        Sequence-stamped frames (``K_TENSOR_SEQ`` — this node is a
        replica on a fan-out path) relay their sequence number onto the
        output frame unchanged, so the downstream fan-in can restore
        stream order.
        """
        out_socks = None
        tx = None
        n = 0                   # tensors relayed downstream
        seq = 0                 # tensors received
        streamed = False
        stream_marked = False   # upstream announced this conn as data path
        infer_hist = REGISTRY.histogram("node.infer_s")
        inflight_g = REGISTRY.gauge("node.inflight")
        #: issued-but-unsynced stage outputs, oldest first
        pending: collections.deque = collections.deque()
        # no gauge yet: most connections are short-lived control round
        # trips whose rx channel would clobber the data stream's reading;
        # the gauge is bound once this connection proves to be the stream
        rx = AsyncReceiver(conn, depth=self.rx_depth,
                           span=self._span_label)
        # replica half of the ack plane (docs/ROBUSTNESS.md): forward
        # the downstream fan-in's cumulative replay_acks one hop
        # upstream on this replica's own inbound connection; the lock
        # serializes those writes against the stream-end replay_done
        ack_lock = threading.Lock()
        relay_on = [False]

        def start_relay():
            if relay_on[0] or not (self.failover
                                   and self.replica is not None
                                   and out_socks):
                return
            relay_on[0] = True
            self._start_ack_relay(conn, out_socks[0], ack_lock)

        def drain_one():
            nonlocal n, streamed
            t0, t_end, s, y, relay_seq = pending.popleft()
            inflight_g.dec()
            tq = self._queue_wait(t_end, seq=relay_seq)
            if isinstance(tx, IciSender):
                # device-resident mode: the downstream hop accepts live
                # jax.Arrays, so the output is NEVER materialized to
                # host — only synced (bounding the dispatch window as
                # before).  Zero host_sync samples on this node is the
                # observable proof the round-trip is gone.
                t_done = self._device_wait(y, seq=relay_seq, t0=tq)
            else:
                # host sync of the OLDEST in-flight output
                y, t_done = self._host_sync(y, seq=relay_seq, t0=tq)
            dt = t_done - t0
            infer_hist.record(dt)
            if self.infer_hist is not None:
                self.infer_hist.record(dt)
            tr = tracer()
            if tr.enabled and _sampled(self.trace_sample_every, relay_seq):
                tr.record(
                    f"{self._span_label()}.infer", t0, dt,
                    {"seq": s if relay_seq is None else relay_seq,
                     "stage": self.manifest["index"]})
            self.processed += 1  # before the send: a stats query can
            #   race the relay of the final tensor otherwise
            tx.send(y, seq=relay_seq)
            n += 1
            streamed = True

        import queue as _q

        try:
            while True:
                if pending:
                    # compute-ahead only while input is immediately
                    # available: an idle upstream means the window must
                    # drain NOW, or the stream's tail stalls in the node
                    try:
                        kind, value = rx.get_nowait()
                    except _q.Empty:
                        drain_one()
                        continue
                else:
                    kind, value = rx.get()
                if kind == K_END:
                    while pending:
                        drain_one()
                    if streamed or stream_marked:
                        if tx is None:
                            # marked data path, zero frames (fewer inputs
                            # than replicas): still propagate the stream
                            # shape so the downstream fan-in's END count
                            # and the result server's dial-back hold
                            # (fan senders and branch-path hops already
                            # announced themselves in _make_tx)
                            tx, out_socks = self._make_tx(
                                connect_timeout_s)
                            start_relay()
                            if not isinstance(
                                    tx, (FanOutSender, BroadcastSender,
                                         ReplayFanOut)) \
                                    and self.branch is None:
                                tx.send_ctrl({"cmd": "stream_begin"})
                        # END + join: every relayed frame is on the wire
                        # before the finally block closes the socket
                        tx.close(timeout=connect_timeout_s)
                        if relay_on[0]:
                            # every frame of this replica's segment got
                            # downstream: tell the upstream fan-out the
                            # coming EOF is shutdown, not death
                            try:
                                with ack_lock:
                                    send_ctrl(conn,
                                              {"cmd": "replay_done"})
                            except OSError:
                                pass
                        from ..obs.events import emit as emit_event
                        emit_event("stream_end", hop=self._span_label(),
                                   n=n)
                        return n
                    return None  # control connection closing
                if kind == K_CTRL:
                    if isinstance(value, dict) \
                            and value.get("cmd") == "stream_begin":
                        stream_marked = True
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "tier_probe":
                        # colocated-tier handshake: an ici/local grant
                        # SWAPS the data path to the offered in-memory
                        # pipe (ici frames stay live jax.Arrays,
                        # device_put onto this node's pinned device by
                        # the sender); a shm grant wraps this socket
                        # channel into a ShmReceiver (descriptors keep
                        # riding the socket as the doorbell, payloads
                        # come out of the mapped ring); refused, the
                        # stream continues on this socket
                        from ..transport.shm import answer_tier_probe
                        self.tier_in, chan = answer_tier_probe(
                            conn, value, accept=self.tier_accept,
                            inner=rx, depth=self.rx_depth,
                            device=self._jax_device())
                        if chan is not None:
                            rx = chan
                            rx.sample_every = self.trace_sample_every
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "req_meta":
                        # serve-front-door request metadata: cascade
                        # downstream immediately (docs/SERVING.md).
                        # Relayed ahead of the still-in-flight dispatch
                        # window on purpose — a meta may only move
                        # EARLIER relative to its own frame (it is
                        # processed before the frame at every stage),
                        # never later, and the result-hop demux joins
                        # meta to frame by seq; draining the window
                        # here would cut serving traffic's compute-
                        # ahead to one frame
                        stream_marked = True
                        if tx is None:
                            tx, out_socks = self._make_tx(
                                connect_timeout_s)
                            start_relay()
                        tx.send_ctrl(value)
                        continue
                    is_trace = (isinstance(value, dict)
                                and value.get("cmd") == "trace")
                    if is_trace:
                        # relay order: everything received before this
                        # ctrl frame must reach downstream ahead of it
                        while pending:
                            drain_one()
                    self._handle_ctrl(conn, value, recv=rx.get)
                    if is_trace and tx is not None:
                        # downstream already connected (e.g. a second
                        # traced stream on a live chain): cascade the new
                        # context now, not just at connection open
                        tx.send_ctrl(self._pending_trace)
                    continue
                if kind == K_TENSOR_SEQ:
                    relay_seq, value = value
                elif kind == K_TENSOR:
                    relay_seq = None
                else:
                    raise ValueError(f"unexpected frame kind {kind}")
                if self.prog is None:
                    raise ValueError(
                        "data frame before any stage artifact (boot with "
                        "--artifact or deploy in-band first)")
                if tx is None:
                    tx, out_socks = self._make_tx(connect_timeout_s)
                    start_relay()
                if self._live_rx is not rx:
                    # first tensor on this channel (tx may already be
                    # open from a req_meta cascade): bind the live
                    # telemetry to the channel the stream actually rides
                    rx.bind_gauge("node.rx_queue_depth")
                    rx.bind_hist("node.rx_s")
                    rx.sample_every = self.trace_sample_every
                    self._live_rx = rx
                    from ..obs.events import emit as emit_event
                    emit_event("stream_begin", hop=self._span_label())
                want = tuple(self.manifest["in_shape"])
                if tuple(value.shape[1:]) != want:
                    raise ValueError(
                        f"stage {self.manifest['index']} expects sample "
                        f"shape {want}, got {tuple(value.shape[1:])}")
                if self.infer_delay_s:
                    time.sleep(self.infer_delay_s)  # bench-only device
                t0, t_end, y_disp = self._dispatch(value, seq=relay_seq)
                pending.append((t0, t_end, seq, y_disp, relay_seq))
                seq += 1
                inflight_g.inc()
                while len(pending) >= self.inflight:
                    drain_one()
        except Exception as e:  # noqa: BLE001 — see below
            if streamed:
                raise  # upstream died / corrupted mid-stream: loud
            # a connection that never became the data stream must not be
            # able to kill a serving node: port scanners and malformed
            # control peers are logged and dropped.  The remote side still
            # fails loudly — its recv gets a cut connection, no ACK/END.
            print(f"node: dropped connection before streaming: {e!r}",
                  file=sys.stderr, flush=True)
            return None
        finally:
            # reconcile the ADDITIVE gauges: an abandoned stream's
            # queued frames / un-synced dispatches are never consumed,
            # and must not inflate the shared readings forever
            if self._live_rx is rx:
                self._live_rx = None
            rx.release_gauge()
            if pending:
                inflight_g.dec(len(pending))
            if tx is not None and hasattr(tx, "detach"):
                # local-tier tx: a stream abandoned without its END must
                # fail the downstream consumer like a cut socket would
                tx.detach()
            if out_socks is not None:
                for s in out_socks:
                    s.close()

    def _serve_conn_serial(self, conn, connect_timeout_s: float) -> int | None:
        """The pre-overlap serial loop: per tensor, rx + decode, compute
        with an immediate host sync, encode + tx — phases pay their sum.
        Kept as the baseline the overlap speedup is measured against."""
        out = None
        n = 0
        streamed = False
        stream_marked = False
        infer_hist = REGISTRY.histogram("node.infer_s")
        try:
            while True:
                kind, value = recv_frame(conn)
                if kind == K_END:
                    if streamed or stream_marked:
                        if out is None:
                            if self.next_hop is None:
                                raise ValueError("no next hop configured")
                            out = _connect_retry(*self.next_hop,
                                                 timeout_s=connect_timeout_s)
                            send_ctrl(out, {"cmd": "stream_begin"})
                        send_end(out)
                        return n
                    return None  # control connection closing
                if kind == K_CTRL:
                    if isinstance(value, dict) \
                            and value.get("cmd") == "stream_begin":
                        stream_marked = True
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "tier_probe":
                        # the serial baseline loop is the measurable
                        # pure-wire reference: always refuse the fast
                        # path (the offering hop degrades to tcp)
                        from ..transport.local import answer_probe
                        answer_probe(conn, value, accept=False)
                        self.tier_in = "tcp"
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "req_meta":
                        # serve request metadata: cascade downstream in
                        # stream order (the serial loop is already
                        # strictly ordered — no window to drain)
                        stream_marked = True
                        if out is None:
                            if self.next_hop is None:
                                raise ValueError("no next hop configured")
                            out = _connect_retry(
                                *self.next_hop,
                                timeout_s=connect_timeout_s)
                            if self._pending_trace is not None:
                                send_ctrl(out, self._pending_trace)
                        send_ctrl(out, value)
                        continue
                    self._handle_ctrl(conn, value)
                    if (isinstance(value, dict)
                            and value.get("cmd") == "trace"
                            and out is not None):
                        # downstream already connected (e.g. a second
                        # traced stream on a live chain): cascade the new
                        # context now, not just at connection open
                        send_ctrl(out, self._pending_trace)
                    continue
                if kind == K_TENSOR_SEQ:
                    relay_seq, value = value
                elif kind == K_TENSOR:
                    relay_seq = None
                else:
                    raise ValueError(f"unexpected frame kind {kind}")
                if self.prog is None:
                    raise ValueError(
                        "data frame before any stage artifact (boot with "
                        "--artifact or deploy in-band first)")
                if out is None:
                    if self.next_hop is None:
                        raise ValueError("no next hop configured")
                    if self.next_hops and len(self.next_hops) > 1:
                        raise ValueError(
                            "fan-out requires the overlapped node loop "
                            "(drop --no-overlap)")
                    out = _connect_retry(*self.next_hop,
                                         timeout_s=connect_timeout_s)
                    if self._pending_trace is not None:
                        # cascade the dispatcher's trace context down the
                        # chain ahead of the first relayed tensor
                        send_ctrl(out, self._pending_trace)
                want = tuple(self.manifest["in_shape"])
                if tuple(value.shape[1:]) != want:
                    raise ValueError(
                        f"stage {self.manifest['index']} expects sample "
                        f"shape {want}, got {tuple(value.shape[1:])}")
                if self.infer_delay_s:
                    time.sleep(self.infer_delay_s)  # bench-only device
                t0, t_end, y = self._dispatch(value, seq=relay_seq)
                tq = self._queue_wait(t_end, seq=relay_seq)
                y, t_done = self._host_sync(y, seq=relay_seq, t0=tq)
                dt = t_done - t0
                infer_hist.record(dt)
                if self.infer_hist is not None:
                    self.infer_hist.record(dt)
                tr = tracer()
                if tr.enabled and _sampled(self.trace_sample_every,
                                           relay_seq):
                    tr.record(
                        f"{self._span_label()}.infer", t0, dt,
                        {"seq": n if relay_seq is None else relay_seq,
                         "stage": self.manifest["index"]})
                self.processed += 1  # before the send: a stats query can
                #   race the relay of the final tensor otherwise
                send_frame(out, y, codec=self.codec, seq=relay_seq)
                n += 1
                streamed = True
        except Exception as e:  # noqa: BLE001 — see below
            if streamed:
                raise  # upstream died / corrupted mid-stream: loud
            print(f"node: dropped connection before streaming: {e!r}",
                  file=sys.stderr, flush=True)
            return None
        finally:
            if out is not None:
                out.close()

    # -- seq-replay ack plane (docs/ROBUSTNESS.md) ---------------------------

    def _start_ack_relay(self, up_conn, down_sock, lock) -> None:
        """Replica half of the ack plane: read the downstream fan-in's
        cumulative ``replay_ack`` control frames off the data socket's
        reverse direction and forward each one hop upstream on this
        replica's own inbound connection — the fan-out's replay window
        drains end to end without a dedicated ack port.  ``lock``
        serializes the upstream writes against the stream-end
        ``replay_done``; the thread dies silently with either socket."""

        def relay():
            try:
                while True:
                    kind, value = recv_frame(down_sock)
                    if kind == K_END:
                        return
                    if kind == K_CTRL and isinstance(value, dict) \
                            and value.get("cmd") == "replay_ack":
                        with lock:
                            send_ctrl(up_conn, value)
            except (OSError, ConnectionError, ValueError):
                return

        threading.Thread(target=relay, daemon=True,
                         name="node-ack-relay").start()

    def _fanin_ack(self, merge) -> None:
        """Fan-in half of the ack plane: one cumulative ``replay_ack``
        (every seq below it merged in order) on each live upstream
        connection.  A connection that fails the write is dropped from
        the ack set — its reader thread notices the death itself."""
        with self._merge_lock:
            conns = list(self._fanin_conns or ())
        upto = merge.next_seq
        for c in conns:
            try:
                send_ctrl(c, {"cmd": "replay_ack", "seq": upto})
            except OSError:
                self._fanin_forget(c)

    def _fanin_forget(self, conn) -> None:
        with self._merge_lock:
            if self._fanin_conns and conn in self._fanin_conns:
                self._fanin_conns.remove(conn)

    def _fanin_grace(self, merge, exc: BaseException) -> None:
        """Poison ``merge`` with ``exc`` after the redial grace UNLESS
        a fresh upstream registers in the meantime (the respawned
        replica's dial-in bumps ``_fanin_epoch``) or the segment
        completes — failover tolerance with a bounded hang."""
        with self._merge_lock:
            epoch = self._fanin_epoch

        def watch():
            deadline = time.monotonic() + self.failover_grace_s
            while time.monotonic() < deadline:
                with self._merge_lock:
                    if self._fanin_epoch != epoch \
                            or self._merge is not merge:
                        return
                time.sleep(0.1)
            with self._merge_lock:
                expired = (self._merge is merge
                           and self._fanin_epoch == epoch)
            if expired:
                merge.fail(exc)

        threading.Thread(target=watch, daemon=True,
                         name="node-failover-grace").start()

    # -- fan-in (this node merges R replicated upstreams) --------------------

    def _serve_conn_fanin(self, conn, connect_timeout_s: float) -> None:
        """One upstream connection of a fan-in node: a reader loop that
        decodes frames on THIS thread (R connections = R parallel
        decoders) and feeds sequence-stamped tensors into the shared
        reorder merge.  Control connections (deploy / stats / reweight)
        are served inline exactly as before.  Always returns ``None`` —
        the merged compute loop (:meth:`_merge_compute`) is the one
        producer of the stream's tensor count."""
        registered = False
        merge = None
        try:
            while True:
                kind, value = recv_frame(conn)
                if kind == K_END:
                    if registered:
                        self._fanin_forget(conn)
                        merge.end()
                    return None
                if kind == K_CTRL:
                    if isinstance(value, dict) \
                            and value.get("cmd") == "stream_begin":
                        # the upstream fan-out marks every replica path,
                        # so even a zero-frame upstream is counted in the
                        # merge's END bookkeeping
                        if not registered:
                            registered = True
                            merge = self._ensure_merge_loop(
                                connect_timeout_s, conn=conn)
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "tier_probe":
                        # fan paths are wire-framed by design (ordered
                        # seq merge): refuse, the offer degrades to tcp
                        from ..transport.local import answer_probe
                        answer_probe(conn, value, accept=False)
                        continue
                    self._handle_ctrl(conn, value)
                    if registered and isinstance(value, dict) \
                            and value.get("cmd") == "trace":
                        # a trace context arriving MID-STREAM (second
                        # traced stream on a live chain) must still
                        # cascade past an already-open downstream
                        # connection: ride it through the merge so the
                        # compute loop re-sends it (duplicates across
                        # the R paths are harmless — adoption is
                        # idempotent and the dispatcher skips them)
                        merge.put_ctrl(dict(self._pending_trace))
                    continue
                if kind == K_TENSOR:
                    raise ValueError(
                        "fan-in node received an unsequenced tensor "
                        "frame — the upstream must fan out with "
                        "sequence numbers (K_TENSOR_SEQ)")
                if kind != K_TENSOR_SEQ:
                    raise ValueError(f"unexpected frame kind {kind}")
                seq, arr = value
                if not registered:
                    registered = True
                    merge = self._ensure_merge_loop(connect_timeout_s,
                                                    conn=conn)
                t0 = time.perf_counter()
                merge.put(seq, arr)
                tr = tracer()
                if tr.enabled:
                    tr.record(f"{self._span_label()}.merge_wait", t0,
                              time.perf_counter() - t0, {"seq": seq})
        except Exception as e:  # noqa: BLE001 — policy matches the
            # single-upstream loops: a registered data path fails loudly
            # (and poisons the merge so the compute loop fails too); a
            # connection that never streamed is logged and dropped
            if registered:
                if self.failover and isinstance(e, (ConnectionError,
                                                    OSError)):
                    # a replica died mid-stream (docs/ROBUSTNESS.md
                    # failover timeline): tolerate for one redial
                    # grace — the healed fan-out replays the dead
                    # path's unacked frames through the respawned
                    # replica's NEW connection; only an unfilled grace
                    # poisons the merge with the original error
                    from ..obs.events import emit as emit_event
                    emit_event("replica_lost", hop=self._span_label(),
                               error=repr(e))
                    self._fanin_forget(conn)
                    self._fanin_grace(merge, e)
                    return None
                merge.fail(e)
                raise
            print(f"node: dropped connection before streaming: {e!r}",
                  file=sys.stderr, flush=True)
            return None

    def _ensure_merge_loop(self, connect_timeout_s: float,
                           conn=None) -> FanInMerge:
        """Create the shared reorder merge and its single compute thread
        the first time an upstream turns out to be a data path; under
        failover, ``conn`` joins the ack set and bumps the registration
        epoch (a respawned replica's dial-in cancels the grace timer).
        Returns the segment's merge — readers hold it locally so a
        persistent node's segment reset can't yank it mid-use."""
        with self._merge_lock:
            if self.failover and conn is not None:
                if self._fanin_conns is None:
                    self._fanin_conns = []
                self._fanin_conns.append(conn)
                self._fanin_epoch += 1
            if self._merge is None:
                # capacity: every upstream gets rx_depth frames of
                # reorder slack before backpressure parks its reader
                # thread; the dedup window absorbs failover replay
                # overlaps (transport/replicate.py, docs/ROBUSTNESS.md)
                self._merge = FanInMerge(
                    self.fan_in,
                    capacity=max(self.fan_in,
                                 self.fan_in * self.rx_depth),
                    replay_window=(_REPLAY_DEDUP_WINDOW
                                   if self.failover else 0))
                t = threading.Thread(
                    target=self._merge_loop, args=(connect_timeout_s,),
                    daemon=True, name="node-merge-compute")
                t.start()
            return self._merge

    def _merge_loop(self, connect_timeout_s: float) -> None:
        done = self._done_q
        try:
            n = self._merge_compute(connect_timeout_s)
            with self._merge_lock:
                # segment complete: a persistent node's next stream
                # builds a fresh merge (and a fresh ack set)
                self._merge = None
                self._fanin_conns = None
            done.put(n)
        except BaseException as e:  # noqa: BLE001 — surfaced via serve()
            self._merge.fail(e)  # wake readers parked in put()
            done.put(e)

    def _merge_compute(self, connect_timeout_s: float) -> int:
        """The fan-in node's compute loop: consume the merged in-order
        stream, keep up to ``inflight`` dispatches un-synced (draining
        greedily whenever the merge has no in-order frame ready), relay
        downstream.  Same shape as :meth:`_serve_conn_overlapped`, with
        the reorder merge in place of the single rx channel."""
        import queue as _q

        tx = None
        out_socks = None
        n = 0
        seq = 0
        infer_hist = REGISTRY.histogram("node.infer_s")
        inflight_g = REGISTRY.gauge("node.inflight")
        merge_g = REGISTRY.gauge("node.merge_depth")
        pending: collections.deque = collections.deque()

        def drain_one():
            nonlocal n
            t0, t_end, s, y = pending.popleft()
            inflight_g.dec()
            tq = self._queue_wait(t_end)
            if isinstance(tx, IciSender):
                # the merge node's OUTBOUND hop can legitimately win
                # ici (only its inbound fan is wire-framed): keep the
                # output device-resident, zero host_sync samples
                t_done = self._device_wait(y, t0=tq)
            else:
                y, t_done = self._host_sync(y, t0=tq)
            dt = t_done - t0
            infer_hist.record(dt)
            if self.infer_hist is not None:
                self.infer_hist.record(dt)
            tr = tracer()
            if tr.enabled:
                tr.record(f"{self._span_label()}.infer", t0, dt,
                          {"seq": s, "stage": self.manifest["index"]})
            self.processed += 1
            tx.send(y)
            n += 1

        merge = self._merge
        try:
            while True:
                if pending:
                    try:
                        kind, value = merge.get_nowait()
                    except _q.Empty:
                        drain_one()
                        continue
                else:
                    kind, value = merge.get()
                merge_g.v = merge.qsize()
                if kind == K_END:
                    while pending:
                        drain_one()
                    if self.failover:
                        # final cumulative ack: release the upstream
                        # fan-out's whole retained window before the
                        # END cascades (best effort — a replica that
                        # already exited just misses one write)
                        self._fanin_ack(merge)
                    if tx is None:
                        # all upstreams were zero-frame paths: still
                        # propagate the stream downstream (see the
                        # overlapped loop's marked-but-empty branch)
                        tx, out_socks = self._make_tx(connect_timeout_s)
                        if not isinstance(
                                tx, (FanOutSender, BroadcastSender)) \
                                and self.branch is None:
                            tx.send_ctrl({"cmd": "stream_begin"})
                    tx.close(timeout=connect_timeout_s)
                    return n
                if kind == K_CTRL:
                    # the readers handled the command (trace adoption);
                    # what rides through the merge is the cascade copy
                    # for downstream — forward it if tx is already open
                    # (at open, _make_tx sends _pending_trace itself)
                    if tx is not None and value is not None:
                        tx.send_ctrl(value)
                    continue
                if self.prog is None:
                    raise ValueError(
                        "data frame before any stage artifact (boot with "
                        "--artifact or deploy in-band first)")
                if tx is None:
                    tx, out_socks = self._make_tx(connect_timeout_s)
                want = tuple(self.manifest["in_shape"])
                if tuple(value.shape[1:]) != want:
                    raise ValueError(
                        f"stage {self.manifest['index']} expects sample "
                        f"shape {want}, got {tuple(value.shape[1:])}")
                if self.infer_delay_s:
                    time.sleep(self.infer_delay_s)  # bench-only device
                t0, t_end, y_disp = self._dispatch(value)
                pending.append((t0, t_end, seq, y_disp))
                seq += 1
                inflight_g.inc()
                if self.failover and seq % ACK_EVERY == 0:
                    # cumulative ack cadence: every merged seq below
                    # merge.next_seq is in order here — the upstream
                    # fan-out can release its retained frames
                    self._fanin_ack(merge)
                while len(pending) >= self.inflight:
                    drain_one()
        finally:
            if pending:
                # reconcile: dispatches abandoned by a failed stream
                # must not inflate the shared inflight gauge forever
                inflight_g.dec(len(pending))
            if out_socks is not None:
                for s in out_socks:
                    s.close()

    # -- branch join (this node merges P labeled branch paths) ---------------

    def _serve_conn_join(self, conn, connect_timeout_s: float) -> None:
        """One upstream connection of a join node: a reader loop that
        decodes frames on THIS thread (P connections = P parallel
        decoders) and deposits sequence-stamped tensors into the shared
        (path, seq) join buffer under the path its ``stream_begin``
        announced.  Control connections (deploy / stats / trace) are
        served inline exactly as on every other loop.  Always returns
        ``None`` — the join compute loop (:meth:`_join_compute`) is the
        one producer of the stream's tensor count."""
        path: int | None = None
        try:
            while True:
                kind, value = recv_frame(conn)
                if kind == K_END:
                    if path is not None:
                        self._join.end(path)
                    return None
                if kind == K_CTRL:
                    if isinstance(value, dict) \
                            and value.get("cmd") == "stream_begin":
                        p = value.get("path")
                        if path is not None:
                            continue  # duplicate marker (zero-frame
                            # paths re-announce at END time): keep slot
                        if p is None:
                            raise ValueError(
                                "join upstream announced a stream with "
                                "no path label — every hop into a join "
                                "must ride a labeled branch path")
                        path = int(p)
                        self._ensure_join_loop(connect_timeout_s)
                        self._join.attach(path)
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "tier_probe":
                        # join paths are wire-framed by design (ordered
                        # (path, seq) merge): refuse, the offer degrades
                        from ..transport.local import answer_probe
                        answer_probe(conn, value, accept=False)
                        continue
                    if isinstance(value, dict) \
                            and value.get("cmd") == "req_meta":
                        raise ValueError(
                            "request-scoped metadata cannot cross a "
                            "branch join (P paths would reorder it); "
                            "serve over a linear chain")
                    self._handle_ctrl(conn, value)
                    if path is not None and isinstance(value, dict) \
                            and value.get("cmd") == "trace":
                        # mid-stream trace context must still cascade
                        # past an already-open downstream connection;
                        # duplicates across the P paths are harmless
                        # (adoption is idempotent)
                        self._join.put_ctrl(dict(self._pending_trace))
                    continue
                if kind == K_TENSOR:
                    raise ValueError(
                        "join node received an unsequenced tensor frame "
                        "— branch hops carry the fork's shared sequence "
                        "stamp (K_TENSOR_SEQ)")
                if kind != K_TENSOR_SEQ:
                    raise ValueError(f"unexpected frame kind {kind}")
                seq, arr = value
                if path is None:
                    raise ValueError(
                        "tensor before stream_begin on a join path — "
                        "the upstream must announce its path first")
                self._join.put(path, seq, arr)
        except Exception as e:  # noqa: BLE001 — policy matches the
            # fan-in loop: a registered branch path fails loudly (and
            # poisons the join so the compute loop fails too); a
            # connection that never streamed is logged and dropped
            if path is not None:
                self._join.fail(e)
                raise
            print(f"node: dropped connection before streaming: {e!r}",
                  file=sys.stderr, flush=True)
            return None

    def _ensure_join_loop(self, connect_timeout_s: float) -> None:
        """Create the shared (path, seq) buffer and its single compute
        thread the first time a branch path announces itself."""
        with self._merge_lock:
            if self._join is not None:
                return
            self._join = BranchJoin(
                self.join_in,
                capacity=max(2, self.rx_depth))
            t = threading.Thread(
                target=self._join_loop, args=(connect_timeout_s,),
                daemon=True, name="node-join-compute")
            t.start()

    def _join_loop(self, connect_timeout_s: float) -> None:
        done = self._done_q
        try:
            done.put(self._join_compute(connect_timeout_s))
        except BaseException as e:  # noqa: BLE001 — surfaced via serve()
            self._join.fail(e)  # wake readers parked in put()
            done.put(e)

    def _join_compute(self, connect_timeout_s: float) -> int:
        """The join node's compute loop: consume complete (all P paths)
        sequences strictly in order, run the multi-input merge program,
        relay downstream with the sequence stamp preserved.  Same shape
        as :meth:`_merge_compute`, with the (path, seq) join in place of
        the round-robin merge and ``prog(*parts)`` in place of
        ``prog(x)``."""
        import queue as _q

        tx = None
        out_socks = None
        n = 0
        infer_hist = REGISTRY.histogram("node.infer_s")
        inflight_g = REGISTRY.gauge("node.inflight")
        join_g = REGISTRY.gauge("node.merge_depth")
        pending: collections.deque = collections.deque()

        def drain_one():
            nonlocal n
            t0, t_end, s, y = pending.popleft()
            inflight_g.dec()
            tq = self._queue_wait(t_end, seq=s)
            if isinstance(tx, IciSender):
                # a join node's outbound hop can win ici too — only
                # the P inbound paths are wire-framed
                t_done = self._device_wait(y, seq=s, t0=tq)
            else:
                y, t_done = self._host_sync(y, seq=s, t0=tq)
            dt = t_done - t0
            infer_hist.record(dt)
            if self.infer_hist is not None:
                self.infer_hist.record(dt)
            tr = tracer()
            if tr.enabled:
                tr.record(f"{self._span_label()}.infer", t0, dt,
                          {"seq": s, "stage": self.manifest["index"]})
            self.processed += 1
            tx.send(y, seq=s)  # relay the region's stamp downstream
            n += 1

        def want_shapes() -> list[tuple]:
            m = self.manifest
            if m.get("in_shapes"):
                return [tuple(s) for s in m["in_shapes"]]
            return [tuple(m["in_shape"])] * self.join_in

        try:
            while True:
                if pending:
                    try:
                        kind, value = self._join.get_nowait()
                    except _q.Empty:
                        drain_one()
                        continue
                else:
                    kind, value = self._join.get()
                join_g.v = self._join.qsize()
                if kind == K_END:
                    while pending:
                        drain_one()
                    if tx is None:
                        tx, out_socks = self._make_tx(connect_timeout_s)
                        if not isinstance(
                                tx, (FanOutSender, BroadcastSender)) \
                                and self.branch is None:
                            tx.send_ctrl({"cmd": "stream_begin"})
                    tx.close(timeout=connect_timeout_s)
                    return n
                if kind == K_CTRL:
                    # the readers handled the command (trace adoption);
                    # what rides through the join is the cascade copy
                    if tx is not None and value is not None:
                        tx.send_ctrl(value)
                    continue
                seq, parts = value
                if self.prog is None:
                    raise ValueError(
                        "data frame before any stage artifact (boot with "
                        "--artifact or deploy in-band first)")
                if tx is None:
                    tx, out_socks = self._make_tx(connect_timeout_s)
                for p, (part, want) in enumerate(
                        zip(parts, want_shapes())):
                    if tuple(part.shape[1:]) != want:
                        raise ValueError(
                            f"join stage {self.manifest['index']} path "
                            f"{p} expects sample shape {want}, got "
                            f"{tuple(part.shape[1:])}")
                if self.infer_delay_s:
                    time.sleep(self.infer_delay_s)
                t0, t_end, y_disp = self._dispatch(*parts, seq=seq)
                pending.append((t0, t_end, seq, y_disp))
                inflight_g.inc()
                while len(pending) >= self.inflight:
                    drain_one()
        finally:
            if pending:
                inflight_g.dec(len(pending))
            if out_socks is not None:
                for s in out_socks:
                    s.close()


class ChainDispatcher:
    """Drives a chain of stage-node processes from one controller.

    Opens the result server (the reference dispatcher's own port 5000 role,
    src/dispatcher.py:95-105), streams inputs to node 0, and yields results
    in order.  Strictly in-flight-window'd so the chain stays full without
    unbounded buffering.
    """

    #: the ONE timeout default; also covers partially-constructed
    #: instances (tests build via __new__ around socketpairs) — as do the
    #: channel defaults below
    timeout_s: float = 180.0
    tx_depth: int = 8
    rx_depth: int = 8
    result_fan_in: int = 1
    #: outbound tier policy for the dispatcher -> stage-0 hop ("auto"
    #: walks the local-over-shm-over-tcp ladder; "shm" offers only the
    #: shared-memory rung; "tcp" never probes) — also gates whether the
    #: result server GRANTS the last node's inbound offer
    tier: str = "tcp"
    tier_accept: bool = True
    #: negotiated tiers for reporting (first hop / result hop)
    tier_out: str | None = None
    tier_in: str | None = None
    #: first-hop offers that degraded to tcp (per-hop fallback twin)
    tier_fallbacks: int = 0
    #: waterfall sampling period (docs/OBSERVABILITY.md): with tracing
    #: enabled and N >= 1, every tensor frame is stamped with its stream
    #: sequence number and only 1-in-N frames record per-frame spans —
    #: in EVERY process of the chain, keyed on the wire seq, so the
    #: sampled frame's rx-wait/infer/tx-wait path stitches end to end
    trace_sample_every: int = 0
    #: class default covers ``__new__``-built instances (tests): the
    #: first ``+=`` then creates the instance attribute
    _stream_seq: int = 0
    _tx_chan = None              # AsyncSender | FanOutSender | None
    _rx_chan: AsyncReceiver | None = None
    _send_socks: list | None = None
    _res_merge: FanInMerge | None = None

    def __init__(self, first_hop: str, *, listen: str = "127.0.0.1:0",
                 codec: str = "raw", window: int = 64,
                 timeout_s: float | None = None,
                 tx_depth: int = 8, rx_depth: int = 8,
                 result_fan_in: int = 1,
                 trace_sample_every: int = 0,
                 tier: str = "tcp", tier_accept: bool | None = None):
        if timeout_s is not None:
            self.timeout_s = timeout_s
        if tier not in ("tcp", "auto", "local", "shm", "ici"):
            raise ValueError(f"tier must be tcp|auto|local|shm|ici, "
                             f"got {tier!r}")
        self.tier = tier
        #: default: grant result-hop offers exactly when this dispatcher
        #: itself plays the colocated game ("--tier tcp" forces a pure
        #: wire chain end to end)
        self.tier_accept = (tier != "tcp") if tier_accept is None \
            else tier_accept
        self.tier_out = None
        self.tier_in = None
        self.tier_fallbacks = 0
        host, port = _parse_hostport(listen)
        self._res_srv = socket.create_server((host, port))
        # a dead chain fails, not hangs
        self._res_srv.settimeout(self.timeout_s)
        self.result_address = self._res_srv.getsockname()
        #: comma-separated list = replicated first stage: the dispatcher
        #: itself fans out round-robin with sequence numbers
        self.first_hop = first_hop
        self.codec = codec
        self.window = window
        self.tx_depth = tx_depth
        self.rx_depth = rx_depth
        #: >1 = replicated LAST stage: R replicas dial the result server
        #: back and the dispatcher merges them in sequence order
        self.result_fan_in = max(1, result_fan_in)
        self.trace_sample_every = max(0, int(trace_sample_every))
        #: wire sequence counter, continuous across stream() calls (a
        #: warm stream and a timed stream must not reuse seq numbers —
        #: sampled spans are keyed by them)
        self._stream_seq = 0
        self._send_sock: socket.socket | None = None
        self._send_socks = None
        self._res_conn: socket.socket | None = None
        self._res_conns: list[socket.socket] = []
        self._tx_chan = None
        self._rx_chan = None
        self._res_merge = None

    def _ensure_connected(self):
        if self._send_sock is None and self._send_socks is None:
            # generous: every node in the chain cold-imports jax first
            socks = [_connect_retry(*h, timeout_s=self.timeout_s)
                     for h in _parse_hops(self.first_hop)]
            if len(socks) == 1:
                self._send_sock = socks[0]
            else:
                self._send_socks = socks
        if self._tx_chan is None:
            # encode + send happen on the channel's tx thread, so the
            # feed loop's np.asarray and the wire overlap (and the END in
            # close() rides the same ordered queue)
            if self._send_socks is not None:
                self.tier_out = "tcp"  # fan-out rides the wire
                self._tx_chan = FanOutSender(self._send_socks,
                                             depth=self.tx_depth,
                                             codec=self.codec,
                                             gauge="chain.tx_queue_depth",
                                             span="chain",
                                             hist="chain.tx_s")
                self._tx_chan.send_ctrl({"cmd": "stream_begin"})
            else:
                if self.tier != "tcp":
                    # tier ladder on the stage-0 hop: local (same
                    # process) over shm (same host) over tcp; a
                    # cross-host node refuses everything and we stay
                    # on tcp with one fallback counted
                    from ..obs.events import emit as emit_event
                    from ..transport.shm import offer_tier_ladder
                    self.tier_out, self._tx_chan, fell_back = \
                        offer_tier_ladder(self._send_sock,
                                          tier=self.tier,
                                          depth=self.tx_depth,
                                          hop="chain")
                    if fell_back:
                        self.tier_fallbacks += 1
                    emit_event("tier", hop="chain",
                               tier=self.tier_out or "tcp",
                               wanted=self.tier,
                               fallback=bool(fell_back))
                if self._tx_chan is None:
                    self.tier_out = "tcp"
                    self._tx_chan = AsyncSender(
                        self._send_sock, depth=self.tx_depth,
                        codec=self.codec,
                        gauge="chain.tx_queue_depth",
                        span="chain", hist="chain.tx_s")
            self._tx_chan.sample_every = self.trace_sample_every
        # the result connection is accepted lazily in _recv_tensor: the
        # last node only dials back once its first tensor arrives, so
        # accepting before sending anything would deadlock the chain

    def stream(self, inputs) -> list[np.ndarray]:
        """Send every input through the chain; return outputs in order.

        FULL-DUPLEX: a sender thread keeps the chain fed (up to
        ``window`` in flight, released as results land) while this thread
        drains results concurrently — a slow stage applies backpressure
        through the window instead of stalling the feed loop mid-send
        (r4 verdict weakness #7).  Encoding happens on the tx channel's
        own thread and result decoding on the rx channel's, so feed,
        encode, the chain itself, and the result drain all overlap with
        bounded in-flight depth.  Per-``get`` timeouts on the result
        channel keep a dead chain failing rather than hanging.

        With tracing enabled (``defer_tpu.obs.enable_tracing``), the call
        injects its trace context as a K_CTRL frame ahead of the first
        tensor; every stage process adopts it, cascades it downstream,
        and parents its per-tensor spans under this stream's root span —
        collect them afterwards with :meth:`collect_trace`.
        """
        self._ensure_connected()
        tr = tracer()
        root_span = None
        t_start = time.perf_counter()
        if tr.enabled:
            # pre-allocate the root span id so remote stages can parent
            # under a span recorded only when the stream completes
            root_span = new_span_id()
            self._tx_chan.send_ctrl(
                {"cmd": "trace", "trace_id": tr.trace_id,
                 "span_id": root_span,
                 "sample_every": self.trace_sample_every})
        # waterfall sampling needs a wire sequence number on every frame
        # (a FanOutSender stamps its own — don't double-stamp)
        stamp_seq = (tr.enabled and self.trace_sample_every > 0
                     and not isinstance(self._tx_chan, FanOutSender))
        outs: list[np.ndarray] = []
        window = threading.Semaphore(self.window)
        sent = [0]
        tx_done = threading.Event()
        rx_failed = threading.Event()
        err: list[BaseException] = []

        def tx():
            try:
                for x in inputs:
                    if rx_failed.is_set():
                        return
                    if not window.acquire(timeout=self.timeout_s):
                        raise TimeoutError(
                            f"chain accepted no result for "
                            f"{self.timeout_s:.0f}s with {self.window} in "
                            f"flight — a stage is stuck")
                    if rx_failed.is_set():
                        return  # woken by the error path, not a result
                    self._tx_chan.send(
                        np.asarray(x),
                        seq=(self._stream_seq + sent[0]) if stamp_seq
                        else None)
                    sent[0] += 1
            except BaseException as e:  # noqa: BLE001 — surfaced below
                err.append(e)
            finally:
                self._stream_seq += sent[0]
                tx_done.set()

        t = threading.Thread(target=tx, daemon=True, name="chain-tx")
        t.start()
        try:
            while True:
                if err:
                    raise err[0]
                if len(outs) < sent[0]:
                    # something is in flight: recv (bounded by the result
                    # socket's timeout).  Never recv otherwise — a recv
                    # with nothing in flight (empty stream, or the final
                    # result landing before tx_done is set) would stall
                    # the full socket timeout for no reason.
                    outs.append(self._recv_tensor())
                    window.release()
                    continue
                if tx_done.is_set():
                    break  # everything sent has been received
                tx_done.wait(0.01)  # sender still working; let it run
        except BaseException:
            rx_failed.set()
            # a sender parked in window.acquire must wake to see the flag;
            # then give it a bounded moment so no trailing frame interleaves
            # with the caller's teardown (close() writes END on this socket)
            window.release(self.window)
            t.join(timeout=5.0)
            raise
        t.join(timeout=self.timeout_s)  # no trailing writes after return
        if err:
            raise err[0]
        if root_span is not None:
            tr.record("chain.stream", t_start,
                      time.perf_counter() - t_start,
                      {"sent": sent[0], "received": len(outs)},
                      span_id=root_span)
        return outs

    @staticmethod
    def _stage_capacity(stage, batch: int) -> dict:
        """The deploy message's capacity fields: the stage's analytic
        FLOPs and HBM bytes at the deploy ``batch``
        (:func:`defer_tpu.obs.capacity.stage_flops_bytes`) — the node
        can then report live MFU against its own chip peak without ever
        seeing the graph.  Empty for stage objects that don't carry
        their graph slice (hand-built test stubs)."""
        graph = getattr(stage, "graph", None)
        names = getattr(stage, "node_names", None)
        if graph is None or not names:
            return {}
        from ..obs.capacity import stage_flops_bytes
        flops, moved = stage_flops_bytes(graph, names, batch=batch)
        return {"flops": flops, "bytes_moved": moved}

    def deploy(self, stages, params, node_addrs: Sequence, *,
               batch: int = 1, result_hop: str | None = None,
               codecs: Sequence[str] | None = None,
               tiers: Sequence[str] | None = None,
               devices: Sequence[int | None] | None = None):
        """Ship each stage's artifact to its node(s) over the control
        channel.

        Serial, in chain order, each ACKed before the next — the in-band
        model distribution of the reference dispatcher
        (src/dispatcher.py:44-65: weights, arch JSON, next-node IP, \\x06
        ACK) collapsed to one control connection per node carrying a
        self-contained StableHLO+weights blob.  Nodes may boot with no
        pre-placed files at all.  ``result_hop`` overrides the address the
        last node relays results to (defaults to this dispatcher's result
        server, reference src/dispatcher.py:51-55).

        Replication: an entry of ``node_addrs`` may itself be a list of
        R addresses — the SAME artifact is deployed to each replica, the
        previous stage's ``next`` becomes the comma-joined replica list
        (fan-out), and the following stage is told ``fan_in=R`` (merge).
        Adjacent replicated stages are rejected — a replica cannot
        restore another fan-out's order.  ``codecs`` (per stage) sets
        each stage's OUTBOUND hop codec; default: this dispatcher's.
        ``tiers`` (per stage, ``auto``/``ici``/``local``/``shm``/
        ``tcp``) sets each stage's OUTBOUND transport-tier policy the
        same way — the deploy-time half of the tier handshake
        (docs/TRANSPORT.md): ``auto`` stages walk the
        ici-over-local-over-shm-over-tcp ladder when they open their
        downstream connection and silently degrade to tcp when no
        rung's proof holds.  ``devices`` (per stage, jax device index
        or None) pins each stage's program to a mesh device — the
        deployment half of the device-resident ici tier.

        Deploying also sweeps ``/dev/shm`` for segments leaked by a
        previous chain whose processes were killed ungracefully
        (``transport.shm.sweep_orphan_segments``).
        """
        from ..transport.shm import sweep_orphan_segments
        from ..utils.export import export_stage_bytes
        sweep_orphan_segments()
        groups = [[a] if isinstance(a, str) else list(a)
                  for a in node_addrs]
        if len(groups) != len(stages):
            raise ValueError(f"{len(stages)} stages but {len(groups)} nodes")
        for i in range(len(groups) - 1):
            if len(groups[i]) > 1 and len(groups[i + 1]) > 1:
                raise ValueError(
                    f"stages {i} and {i + 1} are both replicated; "
                    f"adjacent replication is not supported")
        result_hop = result_hop or \
            f"{self.result_address[0]}:{self.result_address[1]}"
        for i, (stage, addrs) in enumerate(zip(stages, groups)):
            nxt = ",".join(groups[i + 1]) if i + 1 < len(groups) \
                else result_hop
            blob = export_stage_bytes(stage, params, batch=batch)
            capacity = self._stage_capacity(stage, batch)
            for j, addr in enumerate(addrs):
                msg = {"cmd": "deploy", "next": nxt,
                       "codec": codecs[i] if codecs else self.codec,
                       **capacity}
                if tiers:
                    msg["tier"] = tiers[i]
                if devices and devices[i] is not None:
                    # pin stage i's program to a jax device (the
                    # deployment half of the device-resident ici tier)
                    msg["device"] = int(devices[i])
                if i > 0 and len(groups[i - 1]) > 1:
                    msg["fan_in"] = len(groups[i - 1])
                if len(addrs) > 1:
                    msg["replica"] = j
                s = _connect_retry(*_parse_hostport(addr),
                                   timeout_s=self.timeout_s)
                try:
                    send_ctrl(s, msg)
                    send_frame(s, blob)
                    recv_expect(s, K_ACK)
                    send_end(s)
                finally:
                    s.close()

    def deploy_topology(self, topology, stages, params,
                        node_addrs: Sequence[str], *, batch: int = 1,
                        result_hop: str | None = None,
                        stage_delays: dict | None = None):
        """Ship a branched stage graph: one node per topology vertex.

        ``topology`` is a :class:`~defer_tpu.runtime.topology.ChainTopology`
        whose vertices align with ``stages`` (from
        ``topology.stage_specs(graph)``) and ``node_addrs``.  Each deploy
        message carries the vertex's transport role — ``fan`` (broadcast
        fork), ``branch`` (labeled path), ``join`` (P-path merge) — on
        top of the usual next/codec pair; replicas never appear here
        (branch fan machinery and replica fan machinery own different
        sequence namespaces, and mixing them is rejected loudly at the
        node).  ``stage_delays`` (vid -> seconds) installs the bench-only
        simulated device time per vertex."""
        from ..transport.shm import sweep_orphan_segments
        from ..utils.export import export_stage_bytes
        sweep_orphan_segments()
        addrs = list(node_addrs)
        if len(addrs) != len(topology.vertices) or \
                len(stages) != len(topology.vertices):
            raise ValueError(
                f"{len(topology.vertices)} topology vertices need as "
                f"many stages ({len(stages)}) and addresses "
                f"({len(addrs)})")
        result_hop = result_hop or \
            f"{self.result_address[0]}:{self.result_address[1]}"
        for v, stage, addr in zip(topology.vertices, stages, addrs):
            nxt = ",".join(addrs[n] for n in v.next) if v.next \
                else result_hop
            msg = {"cmd": "deploy", "next": nxt,
                   "codec": v.codec or self.codec,
                   **self._stage_capacity(stage, batch)}
            if v.fan == "broadcast":
                msg["fan"] = "broadcast"
            if v.join >= 2:
                msg["join"] = v.join
            if v.branch is not None:
                msg["branch"] = v.branch
            if stage_delays and stage_delays.get(v.vid):
                msg["infer_delay_ms"] = stage_delays[v.vid] * 1e3
            blob = export_stage_bytes(stage, params, batch=batch)
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                send_ctrl(s, msg)
                send_frame(s, blob)
                recv_expect(s, K_ACK)
                send_end(s)
            finally:
                s.close()

    def reweight(self, stages, params, node_addrs: Sequence[str]):
        """Weights-only re-push: install fresh weights on every node's
        already-loaded stage program — redeploy (e.g. after more training)
        without restarting any process or resending StableHLO."""
        from ..utils.export import stage_weight_leaves, weights_blob
        node_addrs = list(node_addrs)
        if len(node_addrs) != len(stages):
            raise ValueError(
                f"{len(stages)} stages but {len(node_addrs)} nodes")
        for stage, addr in zip(stages, node_addrs):
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                send_ctrl(s, {"cmd": "reweight"})
                send_frame(s, weights_blob(
                    stage_weight_leaves(stage, params)))
                recv_expect(s, K_ACK)
                send_end(s)
            finally:
                s.close()

    def stats(self, node_addrs: Sequence[str]) -> list[dict]:
        """Per-node chain observability: query every node's stats control
        endpoint (stage identity, tensors processed, reweights, topology)
        — works mid-stream thanks to thread-per-connection nodes."""
        out = []
        for addr in node_addrs:
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                send_ctrl(s, {"cmd": "stats"})
                out.append(recv_expect(s, K_CTRL))
                send_end(s)
            finally:
                s.close()
        return out

    def _ensure_result_chan(self) -> None:
        """Accept the last node's dial-back and wrap it in the result
        :class:`AsyncReceiver` (idempotent)."""
        if self._res_conn is None:
            self._res_conn, _ = self._res_srv.accept()
            configure_socket(self._res_conn)
        if self._rx_chan is None:
            self._res_conn.settimeout(None)
            self._rx_chan = AsyncReceiver(self._res_conn,
                                          depth=self.rx_depth,
                                          gauge="chain.rx_queue_depth",
                                          span="chain",
                                          hist="chain.rx_s")
            self._rx_chan.sample_every = self.trace_sample_every

    def _result_item(self, *, timeout_s: float | None = None
                     ) -> tuple[int, Any]:
        """One frame off the result hop with the transport handshake
        handled: tier probes are answered (and the channel swapped on a
        grant), trace / stream_begin markers — which the dispatcher
        itself originated — are skipped; everything else is returned to
        the caller."""
        self._ensure_result_chan()
        t = self.timeout_s if timeout_s is None else timeout_s
        while True:
            kind, y = self._rx_chan.get(timeout=t)
            if kind == K_CTRL and isinstance(y, dict):
                cmd = y.get("cmd")
                if cmd == "tier_probe":
                    # the last node offers its fast path on the result
                    # dial-back: an ici/local grant swaps results to
                    # the in-memory pipe (the socket stays as lifetime
                    # anchor; ici frames arrive as live jax.Arrays and
                    # are host-synced HERE, exactly once per frame), a
                    # shm grant wraps the socket channel into a
                    # ShmReceiver (the socket becomes the doorbell)
                    from ..transport.shm import answer_tier_probe
                    self.tier_in, chan = answer_tier_probe(
                        self._res_conn, y, accept=self.tier_accept,
                        inner=self._rx_chan, depth=self.rx_depth)
                    if self.tier_in in ("local", "ici"):
                        old = self._rx_chan
                        self._rx_chan = chan
                        self._rx_chan.sample_every = \
                            self.trace_sample_every
                        self._rx_chan.bind_gauge("chain.rx_queue_depth")
                        old.release_gauge()
                    elif self.tier_in == "shm":
                        # the inner channel stays live (doorbell source)
                        # and keeps its gauge
                        self._rx_chan = chan
                        self._rx_chan.sample_every = \
                            self.trace_sample_every
                    continue
                if cmd in ("trace", "stream_begin"):
                    continue
            if kind in (K_TENSOR, K_TENSOR_SEQ) \
                    and self.tier_in == "ici":
                # the chain's ONE host sync per frame: device-resident
                # results materialize here, at the result edge — every
                # upstream ici hop skipped its np.asarray entirely
                t0 = time.perf_counter()
                if kind == K_TENSOR_SEQ:
                    y = (y[0], np.asarray(y[1]))
                else:
                    y = np.asarray(y)
                REGISTRY.histogram("chain.host_sync_s").record(
                    time.perf_counter() - t0)
            return kind, y

    # -- serve front door: request-scoped duplex stream --------------------

    def begin_trace(self, *, sample_every: int | None = None
                    ) -> str | None:
        """Inject the current trace context into the chain ahead of any
        request-scoped frame — the serving-path twin of what
        :meth:`stream` does per call.  A front door has no stream()
        call, so its backend calls this once at start: every stage
        adopts the trace, cascades it downstream, and samples the SAME
        1-in-N wire seqs (``sample_every`` rides the context exactly
        like ``--trace-sample``).  Returns the pre-allocated root span
        id stage spans parent under, or None when tracing is off."""
        tr = tracer()
        if not tr.enabled:
            return None
        if sample_every is not None:
            self.trace_sample_every = max(0, int(sample_every))
        self._ensure_connected()
        self._tx_chan.sample_every = self.trace_sample_every
        if self._rx_chan is not None:
            self._rx_chan.sample_every = self.trace_sample_every
        root_span = new_span_id()
        self._tx_chan.send_ctrl(
            {"cmd": "trace", "trace_id": tr.trace_id,
             "span_id": root_span,
             "sample_every": self.trace_sample_every})
        return root_span

    def send_request_frame(self, arr: np.ndarray, *, seq: int,
                           meta: dict | None = None) -> None:
        """One request-scoped frame into the chain (docs/SERVING.md):
        the frame is stamped with ``seq`` (wire protocol v2
        ``K_TENSOR_SEQ`` — every stage relays the stamp unchanged, so
        the result hop identifies the frame it answers), optionally
        preceded by a ``req_meta`` K_CTRL frame carrying its
        tenant/request composition, which stage nodes cascade
        downstream ahead of (never behind) the frame it describes.
        Requires a non-replicated chain
        (a fan-out re-stamps sequence numbers and cannot order metadata
        across paths)."""
        self._ensure_connected()
        if isinstance(self._tx_chan, FanOutSender) \
                or self.result_fan_in > 1:
            raise ValueError(
                "request-scoped streaming requires a non-replicated "
                "first/last stage (fan paths re-stamp seq numbers)")
        if meta is not None:
            msg = {"cmd": "req_meta", "seq": int(seq)}
            msg.update(meta)
            self._tx_chan.send_ctrl(msg)
        self._tx_chan.send(np.asarray(arr), seq=int(seq))

    def recv_result(self, *, timeout_s: float | None = None):
        """Next item off the result hop for a request-scoped stream:
        ``("meta", msg)`` for a cascaded ``req_meta`` frame, ``("tensor",
        (seq, arr))`` for a result (``seq`` None on unstamped frames),
        ``("end", None)`` when the chain drained."""
        kind, y = self._result_item(timeout_s=timeout_s)
        if kind == K_CTRL and isinstance(y, dict) \
                and y.get("cmd") == "req_meta":
            return "meta", y
        if kind == K_TENSOR_SEQ:
            return "tensor", (y[0], y[1])
        if kind == K_TENSOR:
            return "tensor", (None, y)
        if kind == K_END:
            return "end", None
        raise ConnectionError(
            f"unexpected frame kind {kind!r} on the result hop")

    def _recv_tensor(self) -> np.ndarray:
        """One in-order result frame; loud protocol check (not an assert:
        ``python -O`` strips asserts, and an early END from a node that died
        mid-stream must raise, not silently mis-drain).

        Results arrive through an :class:`AsyncReceiver`: the decode of
        result j+1 happens on the channel's rx thread while this thread
        hands j back to the caller.  The per-``get`` timeout keeps the
        dead-chain-fails-not-hangs contract; the socket itself stays
        blocking so an idle (but healthy) chain never desyncs mid-frame.

        With ``result_fan_in > 1`` (replicated last stage) the results
        instead come off the sequence-ordered :class:`FanInMerge` over
        the R replica dial-backs.
        """
        if self.result_fan_in > 1:
            return self._recv_tensor_fanin()
        kind, y = self._result_item()
        if kind == K_TENSOR_SEQ:
            # waterfall sampling stamps every frame end to end; the
            # result hop carries the stamp through — strip it here
            return y[1]
        if kind != K_TENSOR:
            raise ConnectionError(
                f"chain returned frame kind {kind!r} while results were "
                f"still in flight (a stage node died and cascaded END?)")
        return y

    def _ensure_result_merge(self) -> FanInMerge:
        """Start the result-side fan-in: a background acceptor takes the
        R replica dial-backs AS THEY COME (a replica that sees its first
        frame late — or only the END — dials late; blocking for all R up
        front would deadlock short streams) and one reader thread per
        connection feeds the sequence-ordered merge."""
        if self._res_merge is not None:
            return self._res_merge
        merge = FanInMerge(
            self.result_fan_in,
            capacity=max(self.result_fan_in,
                         self.result_fan_in * self.rx_depth))
        self._res_merge = merge

        def reader(c):
            try:
                while True:
                    kind, value = recv_frame(c)
                    if kind == K_END:
                        merge.end()
                        return
                    if kind == K_CTRL:
                        if isinstance(value, dict) \
                                and value.get("cmd") == "tier_probe":
                            # replica dial-backs never win the fast path
                            # (the seq merge is wire-framed); refuse so
                            # the prober degrades instead of hanging
                            from ..transport.local import answer_probe
                            answer_probe(c, value, accept=False)
                        continue  # trace / stream_begin: informational
                    if kind != K_TENSOR_SEQ:
                        raise ConnectionError(
                            f"result fan-in got frame kind {kind!r}; "
                            f"replicas must relay sequence-stamped frames")
                    merge.put(*value)
            except BaseException as e:  # noqa: BLE001 — surfaced in get()
                merge.fail(e)

        def acceptor():
            try:
                for _ in range(self.result_fan_in):
                    c, _ = self._res_srv.accept()
                    configure_socket(c)
                    c.settimeout(None)
                    self._res_conns.append(c)
                    threading.Thread(target=reader, args=(c,), daemon=True,
                                     name="chain-result-rx").start()
            except BaseException as e:  # noqa: BLE001 — surfaced in get()
                merge.fail(e)

        threading.Thread(target=acceptor, daemon=True,
                         name="chain-result-accept").start()
        return merge

    def _recv_tensor_fanin(self) -> np.ndarray:
        merge = self._ensure_result_merge()
        kind, y = merge.get(timeout=self.timeout_s)
        while kind == K_CTRL:
            kind, y = merge.get(timeout=self.timeout_s)
        if kind != K_TENSOR:
            raise ConnectionError(
                f"chain returned frame kind {kind!r} while results were "
                f"still in flight (a stage replica died and cascaded "
                f"END?)")
        return y

    def align_clocks(self, node_addrs: Sequence[str], *,
                     rounds: int = 8) -> dict:
        """Clock-align every node's tracer to this process's timeline:
        per node, a min-RTT ping-pong offset estimate over a control
        connection followed by a ``clock_adjust`` shifting the node's
        ``Tracer._wall0_us`` anchor (obs/cluster.py).  Call before
        ``stream`` when exporting cross-process traces, so every
        process's spans land on one coherent Perfetto axis.  Returns
        ``{addr: {"offset_us", "rtt_us", ...}}``."""
        from ..obs.cluster import align_clock
        out = {}
        for addr in node_addrs:
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                out[addr] = align_clock(s, rounds=rounds)
                send_end(s)
            finally:
                s.close()
        return out

    def watch(self, node_addrs: Sequence[str], *,
              interval_ms: float = 250.0, spans: bool = False,
              align_clocks: bool = False):
        """Subscribe to every node's live obs_push stream: returns a
        :class:`~defer_tpu.obs.cluster.ClusterView` aggregating pushes
        on background reader threads until ``view.close()``.  Works
        mid-stream (thread-per-connection nodes) — this is the push
        plane the ``defer_tpu monitor`` CLI renders."""
        from ..obs.cluster import ClusterView
        view = ClusterView()
        view.connect(node_addrs, interval_ms=interval_ms, spans=spans,
                     align_clocks=align_clocks, timeout_s=self.timeout_s)
        return view

    def collect_trace(self, node_addrs: Sequence[str]) -> int:
        """Fetch and merge every node's recorded spans into this process's
        tracer (``trace_dump`` control round-trip per node) so one export
        holds the stitched dispatcher -> stage0 -> ... -> stageN-1 trace.
        Returns the number of spans ingested.  Call while the nodes are
        still alive — after ``stream`` returns, before ``close``."""
        tr = tracer()
        total = 0
        for addr in node_addrs:
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                send_ctrl(s, {"cmd": "trace_dump"})
                reply = recv_expect(s, K_CTRL)
                spans = reply.get("spans", [])
                tr.ingest(spans)
                total += len(spans)
                send_end(s)
            finally:
                s.close()
        return total

    def quiesce(self, node_addrs: Sequence, *,
                at_seq: int | None = None,
                timeout_s: float | None = None) -> list[int]:
        """Drain every node to a stable sequence point (the live-replan
        barrier, docs/ROBUSTNESS.md): per node, a ``quiesce`` control
        round-trip that returns only once the node's queues are empty,
        its in-flight window has drained, and its processed count has
        stopped moving (optionally past ``at_seq``).  Returns each
        node's processed count at the quiesce point.  Entries of
        ``node_addrs`` may be replica lists — every replica is
        quiesced."""
        t = self.timeout_s if timeout_s is None else timeout_s
        flat: list[str] = []
        for a in node_addrs:
            flat.extend([a] if isinstance(a, str) else list(a))
        out: list[int] = []
        for addr in flat:
            s = _connect_retry(*_parse_hostport(addr), timeout_s=t)
            try:
                msg: dict = {"cmd": "quiesce", "timeout_s": t}
                if at_seq is not None:
                    msg["at_seq"] = int(at_seq)
                send_ctrl(s, msg)
                reply = recv_expect(s, K_CTRL)
                if not isinstance(reply, dict) \
                        or reply.get("cmd") != "quiesced":
                    raise ConnectionError(
                        f"node {addr} answered quiesce with {reply!r}")
                out.append(int(reply.get("processed", 0)))
                send_end(s)
            finally:
                s.close()
        return out

    def shutdown_nodes(self, node_addrs: Sequence) -> None:
        """Ask persistent nodes (``--persist``) to exit their serve loop
        after the current segment — the graceful half of a live-replan
        teardown (kill-free, so replay buffers and shm segments unwind
        cleanly)."""
        flat: list[str] = []
        for a in node_addrs:
            flat.extend([a] if isinstance(a, str) else list(a))
        for addr in flat:
            s = _connect_retry(*_parse_hostport(addr),
                               timeout_s=self.timeout_s)
            try:
                send_ctrl(s, {"cmd": "shutdown"})
                recv_expect(s, K_ACK)
                send_end(s)
            finally:
                s.close()

    def end_stream(self):
        """Drain the current stream segment (best effort) and drop every
        data-plane connection — but KEEP the result server listening, so
        a follow-up :meth:`stream` opens a fresh segment against nodes
        that persisted across it (``--persist``).  The wire sequence
        counter is NOT reset: seq numbers stay continuous across
        segments, which is what lets a live replan splice byte-identical
        streams (docs/ROBUSTNESS.md).

        The graceful END handshake is wrapped so a chain that already died
        mid-stream can't mask the original failure with a secondary
        BrokenPipe/EOF from the teardown itself."""
        try:
            if self._send_sock is not None or self._send_socks:
                if self._tx_chan is not None:
                    # the END rides the ordered tx queue behind any
                    # trailing frames; close() joins the tx thread so it
                    # is on the wire before we wait for the cascaded echo
                    # (a FanOutSender ENDs every replica channel)
                    self._tx_chan.close(timeout=min(10.0, self.timeout_s))
                elif self._send_sock is not None:
                    send_end(self._send_sock)
                if self.result_fan_in > 1:
                    # drain the merge until all R replica dial-backs have
                    # delivered their END (the acceptor keeps taking late
                    # dial-backs — e.g. a replica whose only frame was
                    # the cascaded END itself)
                    merge = self._ensure_result_merge()
                    while True:
                        kind, _ = merge.get(timeout=self.timeout_s)
                        if kind == K_END:
                            break
                else:
                    if self._res_conn is None:
                        # nothing was ever received: still accept the last
                        # node's dial-back so its cascaded END completes
                        try:
                            self._res_srv.settimeout(
                                min(10.0, self.timeout_s))
                            self._res_conn, _ = self._res_srv.accept()
                            self._res_conn.settimeout(self.timeout_s)
                        except OSError:
                            pass
                    if self._res_conn is not None:
                        # drain any leftover in-flight frames until the
                        # END cascades through
                        while True:
                            if self._rx_chan is not None:
                                kind, v = self._rx_chan.get(
                                    timeout=self.timeout_s)
                            else:
                                kind, v = recv_frame(self._res_conn)
                            if kind == K_CTRL and isinstance(v, dict) \
                                    and v.get("cmd") == "tier_probe":
                                # zero-result stream: the last node's
                                # offer arrives during teardown — refuse
                                # so its END cascades over plain tcp
                                from ..transport.local import answer_probe
                                answer_probe(self._res_conn, v,
                                             accept=False)
                            if kind == K_END:
                                break
        except (OSError, ConnectionError, ValueError, TimeoutError):
            pass  # teardown after failure: keep the root cause
        finally:
            if self._rx_chan is not None:
                # reconcile the additive chain.rx_queue_depth gauge: a
                # teardown after failure can abandon queued results
                self._rx_chan.release_gauge()
            if self._send_sock is not None:
                self._send_sock.close()
            for s in self._send_socks or []:
                s.close()
            if self._res_conn is not None:
                self._res_conn.close()
            for c in getattr(self, "_res_conns", None) or []:
                c.close()
            # reset to pre-connect state: the next stream() segment
            # redials the (possibly re-deployed) chain from scratch
            self._send_sock = None
            self._send_socks = None
            self._tx_chan = None
            self._rx_chan = None
            self._res_conn = None
            self._res_conns = []
            self._res_merge = None
            # tier_out/tier_in stay readable (post-run reporting); the
            # next segment's negotiation overwrites them
            srv = getattr(self, "_res_srv", None)
            if srv is not None:
                try:
                    srv.settimeout(self.timeout_s)
                except OSError:
                    pass  # already closed (end_stream after close)

    def close(self):
        """End the current segment (:meth:`end_stream`) and close the
        result server — the dispatcher is done for good."""
        try:
            self.end_stream()
        finally:
            self._res_srv.close()


def _free_ports(n: int) -> list[int]:
    """Probe n free localhost ports.  Inherently racy (probe-then-close,
    then the children bind): a concurrent process can steal a port in
    the gap.  ``run_chain`` compensates by detecting children that died
    with a bind failure and retrying the whole spawn on fresh ports —
    the race is unavoidable without fd passing, the hang it used to
    cause is not."""
    socks = [socket.create_server(("127.0.0.1", 0)) for _ in range(n)]
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


#: substrings that identify a child that lost the ``_free_ports`` race
_BIND_RACE_MARKS = ("Address already in use", "EADDRINUSE",
                    "address is already in use")


def _log_tail(lf, limit: int = 2000) -> str:
    try:
        lf.flush()
        lf.seek(0)
        return lf.read()[-limit:]
    except (OSError, ValueError):
        return "<log unavailable>"


def _kill_procs(procs, *, grace_s: float = 5.0) -> None:
    """Terminate every child NOW (SIGTERM, short grace, then SIGKILL) —
    the hardened teardown: a node that died mid-deploy/mid-stream must
    not leave its siblings (or replica processes) running."""
    for pr in procs:
        if pr.poll() is None:
            try:
                pr.terminate()
            except OSError:
                pass
    deadline = time.monotonic() + grace_s
    for pr in procs:
        try:
            pr.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            pr.kill()
    for pr in procs:
        try:
            pr.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass


def _normalize_replicas(replicas, n: int) -> list[int]:
    """``{stage: R}`` -> per-stage replica counts, validated: in range,
    >= 1, and never two adjacent replicated stages (a replica cannot
    restore another fan-out's sequence order)."""
    r_of = [1] * n
    for k, r in (replicas or {}).items():
        k, r = int(k), int(r)
        if not 0 <= k < n:
            raise ValueError(f"replicas: stage {k} out of range 0..{n - 1}")
        if r < 1:
            raise ValueError(f"replicas: stage {k} count {r} must be >= 1")
        r_of[k] = r
    for k in range(n - 1):
        if r_of[k] > 1 and r_of[k + 1] > 1:
            raise ValueError(
                f"replicas: stages {k} and {k + 1} are both replicated; "
                f"adjacent replication is not supported")
    return r_of


def _normalize_hop_tiers(hop_tiers, n: int, r_of: list[int],
                         default: str) -> list[str]:
    """Per-inter-stage-hop tier list, validated: known names, one entry
    per hop, and no colocated (local/device) hop touching a replicated
    stage — the ordered fan machinery is wire-framed by design, so a
    silent tcp downgrade there would belie the caller's topology."""
    if hop_tiers is None:
        # a global default still goes through the adjacency checks: a
        # chain-wide tier="shm" pin with a replicated stage must fail
        # as loudly as the equivalent explicit hop_tiers entry
        tiers = [default] * max(0, n - 1)
    else:
        tiers = [str(t) for t in hop_tiers]
    if len(tiers) != n - 1:
        raise ValueError(f"hop_tiers must have one entry per inter-stage "
                         f"hop ({n - 1}), got {len(tiers)}")
    for k, t in enumerate(tiers):
        if t not in ("tcp", "auto", "local", "shm", "ici", "device"):
            raise ValueError(f"hop_tiers[{k}] = {t!r}; "
                             f"use tcp|auto|local|shm|ici|device")
        if t in ("local", "shm", "ici", "device") \
                and (r_of[k] > 1 or r_of[k + 1] > 1):
            raise ValueError(
                f"hop_tiers[{k}] = {t!r} but stage {k} or {k + 1} is "
                f"replicated; fan paths ride tcp (drop the replicas or "
                f"the colocation)")
    return tiers


#: what a local chain's children run under unless the caller passes
#: ``env``: the CPU platform, one host device each.  A chip belongs to
#: one process, so N stage processes spawned on one host cannot each
#: own it — a local chain is a topology demonstration on the CPU, and
#: the on-chip forms are ``serve`` / ``Defer`` (one process, all chips)
#: and ``python -m defer_tpu node`` per host for multi-host.
LOCAL_CHAIN_ENV = {"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}


def _local_chain_env(env: dict[str, str] | None) -> dict[str, str]:
    """The environment of a local chain's children: the caller's, or
    :data:`LOCAL_CHAIN_ENV` — in which case THIS process must be on
    the CPU platform too, because the stage artifacts it exports are
    lowered for its own platform and the children must load them."""
    child_env = dict(os.environ)
    if env is None:
        import jax
        backend = jax.default_backend()
        if backend != "cpu":
            raise RuntimeError(
                f"a local chain runs its children on the CPU platform "
                f"({LOCAL_CHAIN_ENV}), but this process is on "
                f"{backend!r}: the artifacts it exports would not load "
                f"there, and the children cannot share its chip.  Put "
                f"this process on the CPU (jax.config.update("
                f"'jax_platforms', 'cpu') before the first jax call — "
                f"`python -m defer_tpu chain` does), or pass env= for "
                f"children that own their own devices.  On a chip host "
                f"use `serve` / `Defer` (one process, all chips)")
        env = LOCAL_CHAIN_ENV
    child_env.update(env)
    return child_env


def run_chain(stages: Sequence, params: dict[str, Any], inputs,
              *, batch: int = 1, codec: str = "raw",
              artifact_dir: str | None = None,
              env: dict[str, str] | None = None,
              in_band: bool = False, overlap: bool = True,
              rx_depth: int | None = None, tx_depth: int | None = None,
              inflight: int | None = None,
              replicas: dict[int, int] | None = None,
              hop_codecs: Sequence[str] | None = None,
              hop_tiers: Sequence[str] | None = None,
              tier: str = "auto",
              devices: int | None = None,
              device_map: dict[int, int] | None = None,
              stage_delays: Sequence[float] | None = None,
              stats_out: list | None = None,
              spawn_retries: int = 3,
              on_spawn=None,
              trace_sample_every: int = 0,
              plan=None, graph=None,
              report_interval_ms: float = 250.0,
              failover: bool = False,
              journal_dir: str | None = None) -> list[np.ndarray]:
    """Export, spawn one OS process per stage REPLICA, stream, tear down.

    ``failover=True`` arms the seq-replay substrate
    (docs/ROBUSTNESS.md): fan-out stages retain sent frames until the
    downstream merge acks them, replicas relay acks upstream, and a
    supervisor thread respawns any replica process that dies mid-stream
    from its original argv — the healed channel redials, replays the
    unacked window, and the fan-in dedups the overlap, so a ``kill -9``
    of a mid-chain replica yields a byte-identical stream.  Requires
    ``in_band=False`` (the respawn re-boots from command-line artifact
    paths), at least one replicated stage, and every replicated stage
    to be interior (a fan-out above it and a fan-in below it carry the
    replay/ack plane).

    The one-call analogue of the reference's whole deployment procedure
    (start N ``node.py`` processes, run the dispatcher, src/dispatcher.py:
    44-65 + test/test.py) — used by the CLI ``chain`` command and the
    multi-process integration test.

    ``in_band=True`` boots every node EMPTY (no --artifact flag, no shared
    filesystem) and ships each stage artifact over its control connection
    with an ACK handshake — full control-plane parity with the reference.
    ``in_band=False`` pre-exports artifacts to a (shared) directory and
    passes paths on the command line.

    ``replicas`` maps stage index -> R: stage k runs as R data-parallel
    processes fed round-robin with sequence numbers and merged back in
    order downstream (docs/TRANSPORT.md).  The same artifact deploys to
    every replica.  Adjacent stages cannot both be replicated.
    ``hop_codecs`` (len = num stages) sets each stage's OUTBOUND hop
    codec individually (default: ``codec`` everywhere); the dispatcher ->
    stage-0 hop always uses ``codec``.  ``stats_out`` (a list) receives
    every node's ``stats`` reply — per replica, queried before teardown
    (each row carries the hop's negotiated transport ``tier``).

    Transport tiers (docs/TRANSPORT.md): ``hop_tiers`` (len = num
    stages - 1, one entry per INTER-stage hop) classifies each boundary:

    * ``"device"`` — the two stages land on one device: they are FUSED
      into a single jit-compiled stage program before spawn
      (``partition.fuse_stages``), so the hop — frame, queue, process —
      ceases to exist.
    * ``"ici"`` — same process + same mesh: the two stages are
      COLOCATED into one OS process and the hop negotiates the
      DEVICE-RESIDENT channel — live ``jax.Array``s cross with no host
      materialization at all (zero ``host_sync`` samples), and when
      ``device_map`` pins the stages to distinct devices each frame
      pays exactly one device-to-device ``jax.device_put``.
    * ``"local"`` — same process: the two stages are COLOCATED into one
      OS process (the downstream rides the upstream's process as a
      ``--co-stage`` serve thread) and the hop negotiates the
      zero-serialization in-memory channel.  A handshake that fails
      anyway degrades to tcp and bumps ``transport.tier_fallback``.
    * ``"shm"`` — same host, separate OS processes: the hop's payload
      crosses a ``multiprocessing.shared_memory`` ring (one memcpy per
      side, no codec, no socket bytes) while the TCP socket is demoted
      to a per-frame doorbell carrying seq/ctrl/END ordering
      (``transport/shm.py``).  A failed handshake (cross-host peer,
      refusal) degrades to tcp the same way.
    * ``"auto"`` — separate processes; the hop walks the
      ici-over-local-over-shm-over-tcp ladder at connect time, so the
      standard same-host multi-process chain negotiates shm everywhere
      without being asked (and ici on any same-process hop).
    * ``"tcp"`` — the status-quo wire path, no probe.

    Neither side of a ``device``/``local``/``ici``/``shm`` hop may be
    replicated (the ordered fan machinery is wire-framed by design).
    ``tier`` is the policy for the dispatcher-edge hops (dispatcher ->
    stage 0, last stage -> result server) and the default when
    ``hop_tiers`` is omitted: ``"auto"`` (offers that degrade cleanly)
    or ``"tcp"`` (the escape hatch — a pure wire chain end to end).
    ``devices=N`` forces an N-device host mesh in every child
    (``--xla_force_host_platform_device_count``); ``device_map``
    ({stage: device index}) pins each stage's program — the deployment
    half of the ici tier's cross-device transfers.

    Children that exit with an address-in-use bind failure (the
    ``_free_ports`` probe race) are detected and the whole spawn retries
    on fresh ports, up to ``spawn_retries`` attempts; any other child
    death surfaces that node's log tail in the raised error.  On ANY
    failure every remaining child is terminated before the error
    propagates — a mid-deploy crash cannot leak live replica processes.
    ``on_spawn(procs)`` is a test/instrumentation hook called with the
    freshly spawned ``subprocess.Popen`` list of each attempt.

    Live observability (docs/OBSERVABILITY.md): with tracing enabled the
    dispatcher clock-aligns every node before streaming (min-RTT offset
    estimate + ``clock_adjust``), and ``trace_sample_every=N`` switches
    per-frame spans to 1-in-N waterfall sampling keyed on the wire
    sequence number.  ``plan`` (the deployment's solved
    :class:`~defer_tpu.plan.solver.Plan`) together with ``stats_out``
    subscribes a live :class:`~defer_tpu.obs.cluster.ClusterView` to
    every node's obs_push stream (``report_interval_ms`` cadence) and
    appends one extra ``{"obs": ...}`` row to ``stats_out`` carrying the
    live rows, the detected bottleneck stage, and any straggler flags;
    pass ``graph`` too and the row gains a ``replan`` suggestion from
    :func:`defer_tpu.plan.replan.replan` fed with the live measurements.

    ``journal_dir`` arms the black-box flight recorder
    (docs/OBSERVABILITY.md): every child boots with ``--journal-dir``
    so each stage process — and this dispatcher process — spills its
    events/snapshots/spans to a crash-safe on-disk journal under the
    directory, a failover respawn auto-assembles a postmortem bundle
    naming the first fault, and any ``run_chain`` failure does the
    same synchronously before the error propagates.

    ``env`` overrides the child environment.  By default children run
    under :data:`LOCAL_CHAIN_ENV` — the CPU platform — and this process
    must be on the CPU platform too (:func:`_local_chain_env` refuses
    otherwise): a local chain is a topology demonstration.  Real
    multi-host deployments run ``python -m defer_tpu node`` per host with
    each host's own accelerator environment instead.
    """
    from ..transport.shm import sweep_orphan_segments
    from ..utils.export import export_pipeline

    # reap /dev/shm segments leaked by a previous chain whose processes
    # were all killed ungracefully (kill -9 skips every unlink path)
    sweep_orphan_segments()
    tmp = None
    if artifact_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="defer_chain_")
        artifact_dir = tmp.name
    try:
        n = len(stages)
        r_of = _normalize_replicas(replicas, n)
        if any(r > 1 for r in r_of) and not overlap:
            raise ValueError(
                "replicas require the overlapped node loop "
                "(drop overlap=False / --no-overlap)")
        if failover:
            if in_band:
                raise ValueError(
                    "failover requires in_band=False: the supervisor "
                    "respawns a dead replica from its original argv, "
                    "which must carry the artifact path")
            if not any(r > 1 for r in r_of):
                raise ValueError(
                    "failover requires at least one replicated stage "
                    "(replicas={k: R}) — an unreplicated stage's death "
                    "has no surviving peer to absorb its slots")
            for k in range(n):
                if r_of[k] > 1 and not 0 < k < n - 1:
                    raise ValueError(
                        f"failover: replicated stage {k} must be "
                        f"interior (0 < k < {n - 1}) — the replay/ack "
                        f"plane needs a fan-out stage above it and a "
                        f"fan-in stage below it")
        if hop_codecs is not None and len(hop_codecs) != n:
            raise ValueError(
                f"hop_codecs must have one entry per stage "
                f"({n}), got {len(hop_codecs)}")
        codec_of = list(hop_codecs) if hop_codecs is not None \
            else [codec] * n
        if stage_delays is not None and len(stage_delays) != n:
            raise ValueError(
                f"stage_delays must have one entry per stage "
                f"({n}), got {len(stage_delays)}")
        delay_of = [float(d) for d in stage_delays] \
            if stage_delays is not None else [0.0] * n
        if tier not in ("tcp", "auto", "shm"):
            # "ici"/"local" are structurally impossible as the CHAIN
            # tier here: it also governs the dispatcher edges, and the
            # dispatcher is always its own process in a spawned chain —
            # the pin would silently run both edges over full codec +
            # TCP under a tier claim (the exact failure mode the
            # no-overlap and fan-role guards reject loudly)
            if tier in ("ici", "local"):
                raise ValueError(
                    f"tier={tier!r} cannot hold on the dispatcher edges "
                    f"of a spawned chain (the dispatcher is a separate "
                    f"process); pin the stage hops with "
                    f"hop_tiers=[{tier!r}, ...] and keep tier='auto'")
            raise ValueError(f"tier must be tcp|auto|shm, got {tier!r}")
        tiers = _normalize_hop_tiers(hop_tiers, n, r_of, tier)
        claimed = [t for t in tiers if t in ("local", "shm", "ici")]
        if not overlap and claimed:
            # the serial baseline loop is pure-wire by design and always
            # refuses tier offers — an EXPLICIT local/shm/ici claim
            # would silently run full codec + TCP under a tier claim,
            # so reject loudly (same rule as replicated colocated
            # hops); "auto" offers still degrade cleanly under
            # --no-overlap
            raise ValueError(
                f"hop_tiers {claimed[0]!r} requires the overlapped node "
                f"loop (drop overlap=False / --no-overlap)")
        device_map = {int(k): int(v)
                      for k, v in (device_map or {}).items()}
        for k, v in device_map.items():
            if not 0 <= k < n:
                raise ValueError(
                    f"device_map: stage {k} out of range 0..{n - 1}")
            if v < 0:
                raise ValueError(
                    f"device_map: stage {k} device {v} must be >= 0")
        if device_map and any(t == "device" for t in tiers):
            # device-tier fusion rewrites stage indices before spawn, so
            # a pre-fusion pin would land on the wrong stage (or vanish)
            # silently — the same loud-miss policy as every other
            # stage-indexed map
            raise ValueError(
                "device_map does not compose with device-tier fusion "
                "(fusion renumbers the stages); fuse first and pin the "
                "post-fusion chain, or drop the 'device' hops")
        if device_map and devices is None:
            # pinning stage programs needs the child host mesh to hold
            # the named devices
            devices = max(device_map.values()) + 1
        if devices is not None:
            bad = [v for v in device_map.values() if v >= devices]
            if bad:
                raise ValueError(
                    f"device_map names device {bad[0]} but the forced "
                    f"host mesh has only {devices} device(s)")
        if any(t == "device" for t in tiers):
            # fuse every device-tier hop: adjacent stages become ONE
            # jit-compiled stage program and the hop ceases to exist
            from ..partition.partitioner import fuse_stages
            stages, groups = fuse_stages(list(stages), tiers)
            r_of = [r_of[g[0]] for g in groups]
            codec_of = [codec_of[g[-1]] for g in groups]
            delay_of = [sum(delay_of[i] for i in g) for g in groups]
            tiers = [tiers[g[-1]] for g in groups[:-1]]
            n = len(stages)
        # colocation groups: maximal runs of stages joined by "local"
        # or "ici" hops share one OS process (co-stage serve threads —
        # both tiers need one address space to hand a live object)
        coloc = [[0]]
        for k in range(n - 1):
            if tiers[k] in ("local", "ici"):
                coloc[-1].append(k + 1)
            else:
                coloc.append([k + 1])
        #: per-stage OUTBOUND tier policy argv: explicit claims pin
        #: that single rung's offer ("local" no longer rides the auto
        #: ladder — auto's top rung is now ici, and a 'local' claim
        #: must negotiate what it claimed); "shm" keeps the stages in
        #: separate OS processes with the payload crossing the shared-
        #: memory ring
        tier_of = [(tiers[k] if tiers[k] in ("auto", "local", "shm",
                                             "ici") else "tcp")
                   for k in range(n - 1)] + [tier]

        child_env = _local_chain_env(env)
        if devices is not None:
            # the forced mesh must hold under a CALLER-supplied env too
            # (a device_map pin against a 1-device child dies at boot)
            from ..utils.compat import host_device_count_flags
            child_env["XLA_FLAGS"] = host_device_count_flags(
                child_env.get("XLA_FLAGS"), devices)

        tuning = [] if overlap else ["--no-overlap"]
        if failover:
            tuning += ["--failover"]
        for flag, v in (("--rx-depth", rx_depth), ("--tx-depth", tx_depth),
                        ("--inflight", inflight)):
            if v is not None:
                tuning += [flag, str(v)]
        paths = None
        if not in_band:
            paths = export_pipeline(stages, params, artifact_dir,
                                    batch=batch)

        started_journal = False
        if journal_dir is not None:
            # the dispatcher is a fleet member too: its events
            # (replica_respawn, watchdog, stream lifecycle) are the
            # forensic spine of a postmortem bundle
            from ..obs.journal import active_journal, start_journal
            if active_journal() is None:
                start_journal(journal_dir, "dispatcher")
                started_journal = True

        last_exc: BaseException | None = None
        try:
            for attempt in range(max(1, spawn_retries)):
                try:
                    return _chain_attempt(
                        stages, params, inputs, batch=batch, codec=codec,
                        codec_of=codec_of, r_of=r_of, paths=paths,
                        in_band=in_band, tuning=tuning,
                        child_env=child_env,
                        artifact_dir=artifact_dir, rx_depth=rx_depth,
                        tx_depth=tx_depth, stats_out=stats_out,
                        on_spawn=on_spawn,
                        trace_sample_every=trace_sample_every,
                        plan=plan, graph=graph,
                        report_interval_ms=report_interval_ms,
                        coloc=coloc, tier_of=tier_of, tier=tier,
                        delay_of=delay_of, device_map=device_map,
                        failover=failover, journal_dir=journal_dir)
                except _BindRace as e:
                    last_exc = e
                    print(f"run_chain: bind race on attempt "
                          f"{attempt + 1} ({e}); retrying on fresh "
                          f"ports", file=sys.stderr, flush=True)
            raise RuntimeError(
                f"chain spawn lost the port race {spawn_retries} times: "
                f"{last_exc}") from last_exc
        except _BindRace:
            raise
        except BaseException as e:
            if journal_dir is not None:
                # the failure IS the postmortem trigger: final-spill
                # this process's journal, then assemble the bundle
                # synchronously — the stage journals are already on
                # disk whether their processes died or were killed
                from ..obs.journal import stop_journal
                from ..obs.postmortem import maybe_autopsy
                if started_journal:
                    stop_journal()
                    started_journal = False
                maybe_autopsy(f"run_chain: {type(e).__name__}: {e}",
                              journal_dir=journal_dir, sync=True,
                              delay_s=0.0)
            raise
        finally:
            if started_journal:
                from ..obs.journal import stop_journal
                stop_journal()
    finally:
        if tmp is not None:
            tmp.cleanup()


class _BindRace(RuntimeError):
    """A chain child lost the ``_free_ports`` probe race (bound port was
    stolen before the child's bind) — the spawn should retry."""


def _await_binds(procs, labels, logs, flat_addrs, *,
                 timeout_s: float = 90.0, proc_of=None) -> None:
    """Block until every child REPORTS its bind (the ``listening on``
    line ``cmd_node`` prints right after ``StageNode`` binds), or
    diagnose the one that died trying: a bind-race death raises
    :class:`_BindRace` (retryable), anything else a ``RuntimeError``
    carrying that node's log tail.  This is what turns the old bare
    180 s connect timeout into a fast, attributed failure.  The log line
    (not a connect probe) is the signal on purpose: a stolen port still
    ACCEPTS connections — from whoever stole it.

    ``proc_of`` maps each ``flat_addrs`` index to its process index
    (default: identity) — a COLOCATED process hosts several stage
    listeners, each printing its own ``listening on <addr>`` line, so
    the wait is per-address, matched on the address itself."""
    deadline = time.monotonic() + timeout_s
    for i, addr in enumerate(flat_addrs):
        p = i if proc_of is None else proc_of[i]
        while True:
            rc = procs[p].poll()
            tail = _log_tail(logs[p], limit=8000)
            # delimited match: cmd_node always prints "... listening on
            # <addr>, next ..." — a bare prefix match would accept port
            # 50001's line while waiting on port 5000
            if f"listening on {addr}," in tail or (
                    proc_of is None and "listening on" in tail):
                break
            if rc is not None and rc != 0:
                if any(m in tail for m in _BIND_RACE_MARKS):
                    raise _BindRace(
                        f"node {labels[i]} lost the port bind race")
                raise RuntimeError(
                    f"chain node {labels[i]} exited rc={rc} during "
                    f"boot: {tail[-2000:]}")
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"chain node {labels[i]} did not bind {addr} "
                    f"within {timeout_s:.0f}s: {tail[-2000:]}")
            time.sleep(0.1)


def _chain_attempt(stages, params, inputs, *, batch, codec, codec_of,
                   r_of, paths, in_band, tuning, child_env, artifact_dir,
                   rx_depth, tx_depth, stats_out, on_spawn,
                   trace_sample_every=0, plan=None, graph=None,
                   report_interval_ms=250.0, coloc=None, tier_of=None,
                   tier="tcp", delay_of=None, device_map=None,
                   failover=False, journal_dir=None):
    """One spawn -> deploy -> stream -> teardown attempt (see
    ``run_chain``).  Raises :class:`_BindRace` when a child died with an
    address-in-use failure; any other failure surfaces the dead node's
    log tail after every remaining child has been terminated.

    ``coloc`` groups stage indices into OS processes (stages joined by
    ``local``-tier hops ride one process: the first member is the
    process's primary node, the rest board as ``--co-stage`` serve
    threads); ``tier_of`` is each stage's outbound tier-policy argv."""
    n = len(stages)
    if coloc is None:
        coloc = [[k] for k in range(n)]
    if tier_of is None:
        tier_of = [tier] * n
    total = sum(r_of)
    ports = _free_ports(total + 1)  # per-replica listen ports + result
    result_port = ports[-1]
    # stage k's replica ports, in spawn order
    addrs: list[list[str]] = []
    p = 0
    for k in range(n):
        addrs.append([f"127.0.0.1:{ports[p + j]}" for j in range(r_of[k])])
        p += r_of[k]

    def stage_label(k: int, j: int) -> str:
        return f"stage{k}" if r_of[k] == 1 else f"stage{k}.r{j}"

    def next_of(k: int) -> str:
        return ",".join(addrs[k + 1]) if k + 1 < n \
            else f"127.0.0.1:{result_port}"

    def flags_for(k: int, j: int) -> list[str]:
        if in_band:
            return []
        flags = ["--artifact", paths[k], "--next", next_of(k),
                 "--codec", codec_of[k], "--tier", tier_of[k]]
        if k > 0 and tier_of[k - 1] != "tcp" and tier_of[k] == "tcp":
            # the INBOUND hop claims a colocated tier but this stage's
            # own outbound policy is tcp: grant inbound offers anyway —
            # acceptance follows the upstream's claim, not this stage's
            # outbound (mixed maps like shm,tcp must not silently
            # degrade hop k-1)
            flags += ["--tier-accept", "1"]
        if k > 0 and r_of[k - 1] > 1:
            flags += ["--fan-in", str(r_of[k - 1])]
        if r_of[k] > 1:
            flags += ["--replica", str(j)]
        if delay_of and delay_of[k]:
            flags += ["--infer-delay-ms", str(delay_of[k] * 1e3)]
        if device_map and device_map.get(k) is not None:
            flags += ["--device", str(device_map[k])]
        if journal_dir is not None:
            flags += ["--journal-dir", journal_dir]
        return flags

    #: spawn units: one OS process each, hosting >= 1 (stage, replica)
    #: members (colocation groups always have replica counts of 1)
    units: list[list[tuple[int, int]]] = []
    for grp in coloc:
        if len(grp) == 1:
            units += [[(grp[0], j)] for j in range(r_of[grp[0]])]
        else:
            units.append([(k, 0) for k in grp])

    def argv_for(unit) -> list[str]:
        k0, j0 = unit[0]
        argv = [sys.executable, "-m", "defer_tpu", "node",
                "--listen", addrs[k0][j0]] + flags_for(k0, j0)
        for k, j in unit[1:]:
            # accept=1 always: every co-stage's INBOUND hop is the
            # local-tier boundary that put it in this process, whatever
            # its own outbound policy says
            spec = f"listen={addrs[k][j]};accept=1"
            if not in_band:
                spec += (f";artifact={paths[k]};next={next_of(k)}"
                         f";codec={codec_of[k]};tier={tier_of[k]}")
            if device_map and device_map.get(k) is not None:
                spec += f";device={device_map[k]}"
            argv += ["--co-stage", spec]
        return argv + tuning

    procs, logs = [], []
    labels: list[str] = []   # per-process labels for diagnostics
    failure: BaseException | None = None
    try:
        for unit in units:
            # log to files, not PIPEs: an undrained pipe fills and
            # deadlocks a chatty child mid-chain
            name = "node_" + "+".join(
                f"{k}" + (f"_r{j}" if r_of[k] > 1 else "")
                for k, j in unit)
            labels.append("+".join(stage_label(k, j) for k, j in unit))
            lf = open(os.path.join(artifact_dir, f"{name}.log"), "w+")
            logs.append(lf)
            procs.append(subprocess.Popen(
                argv_for(unit), env=child_env, stdout=lf,
                stderr=subprocess.STDOUT))
        if on_spawn is not None:
            on_spawn(procs)
        flat, flat_labels, proc_of = [], [], []
        for u, unit in enumerate(units):
            for k, j in unit:
                flat.append(addrs[k][j])
                flat_labels.append(stage_label(k, j))
                proc_of.append(u)
        _await_binds(procs, flat_labels, logs, flat, proc_of=proc_of)

        try:
            disp = ChainDispatcher(",".join(addrs[0]),
                                   listen=f"127.0.0.1:{result_port}",
                                   codec=codec,
                                   # the CLI depth flags tune BOTH ends:
                                   # the nodes (via argv) and the
                                   # dispatcher's own feed/drain channels
                                   tx_depth=tx_depth if tx_depth else 8,
                                   rx_depth=rx_depth if rx_depth else 8,
                                   result_fan_in=r_of[-1],
                                   trace_sample_every=trace_sample_every,
                                   tier=tier)
        except OSError as e:
            import errno
            if getattr(e, "errno", None) == errno.EADDRINUSE \
                    or any(m in str(e) for m in _BIND_RACE_MARKS):
                # the PARENT's result-port bind lost the probe race —
                # just as retryable as a child's
                raise _BindRace(
                    f"dispatcher lost the result-port bind race "
                    f"({e})") from e
            raise
        flat_addrs = flat
        view = None
        try:
            if in_band:
                disp.deploy(stages, params, addrs, batch=batch,
                            codecs=codec_of, tiers=tier_of,
                            devices=[device_map.get(k)
                                     if device_map else None
                                     for k in range(n)])
            if tracer().enabled:
                # one coherent cross-process timeline: correct every
                # node's wall anchor before any stream spans record
                try:
                    disp.align_clocks(flat_addrs)
                except (OSError, ConnectionError) as e:
                    print(f"run_chain: clock alignment failed: {e!r}",
                          file=sys.stderr)
            if plan is not None and stats_out is not None:
                # live observation loop: subscribe to every node's
                # obs_push stream for the duration of the stream
                view = disp.watch(flat_addrs,
                                  interval_ms=report_interval_ms)
            stop_super = threading.Event()
            super_thread = None
            if failover:
                def _supervise():
                    # respawn any dead REPLICA process from its original
                    # argv (same listen port: SO_REUSEADDR lets the
                    # respawn rebind immediately); the upstream replay
                    # fan-out's redial loop bridges the gap and replays
                    # the unacked window once the new process binds.
                    # procs[idx] is REPLACED so the post-stream rc check
                    # judges the respawn, not the corpse.
                    from ..obs.events import emit as emit_event
                    from ..transport.shm import sweep_orphan_segments
                    while not stop_super.wait(0.2):
                        for idx, unit in enumerate(units):
                            rc = procs[idx].poll()
                            if rc is None or rc == 0:
                                continue
                            if len(unit) != 1 or r_of[unit[0][0]] <= 1:
                                return  # not respawnable: let teardown
                                        # surface the death
                            k, j = unit[0]
                            # a kill -9 skipped every unlink path: reap
                            # shm segments before the replacement boots
                            sweep_orphan_segments()
                            procs[idx] = subprocess.Popen(
                                argv_for(unit), env=child_env,
                                stdout=logs[idx],
                                stderr=subprocess.STDOUT)
                            emit_event("replica_respawn", stage=k,
                                       replica=j, addr=addrs[k][j],
                                       rc=rc)
                            print(f"run_chain: respawned "
                                  f"{stage_label(k, j)} (rc={rc})",
                                  file=sys.stderr, flush=True)
                            if journal_dir is not None:
                                # a failover episode auto-emits its
                                # forensics bundle (rate-limited; the
                                # delay lets this respawn event reach
                                # the journals first)
                                from ..obs.postmortem import \
                                    maybe_autopsy
                                maybe_autopsy(
                                    f"failover: respawned "
                                    f"{stage_label(k, j)} rc={rc}",
                                    journal_dir=journal_dir)

                super_thread = threading.Thread(
                    target=_supervise, daemon=True,
                    name="chain-supervisor")
                super_thread.start()
            try:
                outs = disp.stream(inputs)
            finally:
                # stop BEFORE teardown: the END cascade exits every
                # node, and exits must not read as deaths to respawn
                stop_super.set()
                if super_thread is not None:
                    super_thread.join(timeout=5.0)
            if stats_out is not None:
                # per-replica observability, queried while the nodes are
                # still serving (they exit once close() cascades END)
                stats_out.extend(disp.stats(flat_addrs))
            if view is not None:
                from ..obs.cluster import (StragglerDetector,
                                           expected_stage_ms)
                det = StragglerDetector(expected_stage_ms(plan))
                obs = {"rows": view.rows(),
                       "bottleneck": view.bottleneck(),
                       "stragglers": [f.to_json()
                                      for f in det.observe(view)]}
                if graph is not None:
                    try:
                        obs["replan"] = det.suggest(
                            view, graph, plan).to_json()
                    except Exception as e:  # noqa: BLE001 — advisory
                        obs["replan_error"] = repr(e)
                stats_out.append({"obs": obs})
            if tracer().enabled:
                # stitch every stage process's spans into this process's
                # tracer while the nodes are still serving
                try:
                    disp.collect_trace(flat_addrs)
                except (OSError, ConnectionError) as e:
                    print(f"run_chain: trace collection failed: {e!r}",
                          file=sys.stderr)
        except BaseException as e:
            failure = e
            raise
        finally:
            if view is not None:
                view.close()
            if failure is not None:
                # hardened teardown: kill the children FIRST so the
                # dispatcher's drain hits dead sockets (fast) instead of
                # waiting out its timeouts against a wedged chain — and
                # so a mid-deploy crash cannot leak live replicas
                _kill_procs(procs)
            disp.close()
            if failure is None:
                for pr in procs:
                    try:
                        pr.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        pr.kill()
        for i, pr in enumerate(procs):
            if pr.returncode not in (0, None):
                raise RuntimeError(
                    f"chain node {labels[i]} exited rc={pr.returncode}: "
                    f"{_log_tail(logs[i])}")
        return outs
    except _BindRace:
        _kill_procs(procs)
        raise
    except BaseException as e:
        # diagnose: which children died, and why — surfacing each dead
        # node's log tail instead of the dispatcher's bare timeout
        _kill_procs(procs)
        dead = [(labels[i], pr.returncode, _log_tail(logs[i]))
                for i, pr in enumerate(procs)
                if pr.returncode not in (0, None)]
        races = [d for d in dead
                 if any(m in d[2] for m in _BIND_RACE_MARKS)]
        if races and all(d in races for d in dead):
            raise _BindRace(
                f"{[d[0] for d in races]} lost the port bind race") from e
        if dead and not isinstance(e, RuntimeError):
            detail = "; ".join(
                f"node {lbl} rc={rc}: ...{tail[-800:]}"
                for lbl, rc, tail in dead)
            raise RuntimeError(
                f"chain failed ({type(e).__name__}: {e}); dead nodes: "
                f"{detail}") from e
        raise
    finally:
        for lf in logs:
            lf.close()


def run_dag_chain(graph, params, inputs, *, topology, batch: int = 1,
                  codec: str = "raw", artifact_dir: str | None = None,
                  env: dict[str, str] | None = None,
                  rx_depth: int | None = None, tx_depth: int | None = None,
                  inflight: int | None = None,
                  stage_delays: dict | None = None,
                  replicas=None, hop_tiers=None,
                  stats_out: list | None = None,
                  spawn_retries: int = 3, on_spawn=None,
                  trace_sample_every: int = 0) -> "list[np.ndarray]":
    """Spawn a BRANCHED process pipeline — one OS process per topology
    vertex — stream, tear down (the DAG analogue of :func:`run_chain`).

    ``topology`` is a :class:`~defer_tpu.runtime.topology.ChainTopology`
    (typically ``ChainTopology.from_json`` of a ``plan --dag --json``
    document): trunk vertices relay as usual, a fork vertex broadcasts
    every frame to all of its region's paths with a shared sequence
    stamp, branch vertices ride labeled paths, and the join vertex
    merges all P paths per sequence before running the graph's merge op
    (docs/TRANSPORT.md).  Outputs return in order, byte-identical to the
    single-process forward.

    ``stage_delays`` (vertex id -> seconds) installs bench-only
    simulated device time per vertex (``node --infer-delay-ms``) — how
    ``scripts/dag_smoke.py`` expresses branch compute on a small host.

    Replication and colocation tiers do NOT compose with branch
    topologies (the ordered fan machineries own different sequence
    namespaces; every branch hop is wire-framed): ``replicas`` /
    ``hop_tiers`` are rejected loudly rather than silently ignored.
    """
    from ..utils.export import export_stage

    if replicas:
        raise ValueError(
            "replicas do not compose with a branched topology (a branch "
            "hop touching a replicated stage is rejected like any fan "
            "hop); drop the replicas or run a linear chain")
    if hop_tiers:
        raise ValueError(
            "hop_tiers do not compose with a branched topology yet — "
            "every branch fan-out/join hop is wire-framed by design")
    stages = topology.stage_specs(graph)
    tmp = None
    if artifact_dir is None:
        tmp = tempfile.TemporaryDirectory(prefix="defer_dag_")
        artifact_dir = tmp.name
    try:
        paths = []
        for v, stage in zip(topology.vertices, stages):
            p = os.path.join(artifact_dir, f"vertex_{v.vid}.zip")
            export_stage(stage, params, p, batch=batch)
            paths.append(p)

        child_env = _local_chain_env(env)
        tuning = []
        for flag, val in (("--rx-depth", rx_depth),
                          ("--tx-depth", tx_depth),
                          ("--inflight", inflight)):
            if val is not None:
                tuning += [flag, str(val)]

        last_exc: BaseException | None = None
        for attempt in range(max(1, spawn_retries)):
            try:
                return _dag_attempt(
                    topology, paths, inputs, codec=codec,
                    child_env=child_env, artifact_dir=artifact_dir,
                    tuning=tuning, rx_depth=rx_depth, tx_depth=tx_depth,
                    stage_delays=stage_delays or {},
                    stats_out=stats_out, on_spawn=on_spawn,
                    trace_sample_every=trace_sample_every)
            except _BindRace as e:
                last_exc = e
                print(f"run_dag_chain: bind race on attempt "
                      f"{attempt + 1} ({e}); retrying on fresh ports",
                      file=sys.stderr, flush=True)
        raise RuntimeError(
            f"dag chain spawn lost the port race {spawn_retries} times: "
            f"{last_exc}") from last_exc
    finally:
        if tmp is not None:
            tmp.cleanup()


def dag_vertex_argv(v, artifact: str, *, addrs, result_addr: str,
                    codec: str = "raw",
                    stage_delays: dict | None = None) -> list[str]:
    """argv for one topology vertex's ``defer_tpu node`` process — the
    single source of truth for the branched deployment shape
    (:func:`run_dag_chain` and ``scripts/dag_smoke.py`` both spawn
    through it, so the bench always measures what ``chain --dag``
    ships)."""
    nxt = ",".join(addrs[n] for n in v.next) if v.next else result_addr
    argv = [sys.executable, "-m", "defer_tpu", "node",
            "--listen", addrs[v.vid], "--artifact", artifact,
            "--next", nxt, "--codec", v.codec or codec,
            "--tier", "tcp"]
    if v.fan == "broadcast":
        argv += ["--fan", "broadcast"]
    if v.branch is not None:
        argv += ["--branch", str(v.branch)]
    if v.join >= 2:
        argv += ["--join", str(v.join)]
    if stage_delays and stage_delays.get(v.vid):
        argv += ["--infer-delay-ms", str(stage_delays[v.vid] * 1e3)]
    return argv


def _dag_attempt(topology, paths, inputs, *, codec, child_env,
                 artifact_dir, tuning, rx_depth, tx_depth, stage_delays,
                 stats_out, on_spawn, trace_sample_every=0):
    """One spawn -> stream -> teardown attempt of a branched topology
    (see :func:`run_dag_chain`); same bind-race/teardown discipline as
    :func:`_chain_attempt`."""
    vs = topology.vertices
    ports = _free_ports(len(vs) + 1)
    result_port = ports[-1]
    addrs = [f"127.0.0.1:{ports[i]}" for i in range(len(vs))]

    def argv_for(v, path):
        return dag_vertex_argv(
            v, path, addrs=addrs,
            result_addr=f"127.0.0.1:{result_port}", codec=codec,
            stage_delays=stage_delays) + tuning

    procs, logs = [], []
    labels = [v.label for v in vs]
    failure: BaseException | None = None
    try:
        for v, path in zip(vs, paths):
            lf = open(os.path.join(artifact_dir,
                                   f"node_{v.label.replace('.', '_')}"
                                   f".log"), "w+")
            logs.append(lf)
            procs.append(subprocess.Popen(
                argv_for(v, path), env=child_env, stdout=lf,
                stderr=subprocess.STDOUT))
        if on_spawn is not None:
            on_spawn(procs)
        # identity proc_of: exact per-address "listening on" matching
        _await_binds(procs, labels, logs, addrs,
                     proc_of=list(range(len(vs))))

        try:
            disp = ChainDispatcher(addrs[0],
                                   listen=f"127.0.0.1:{result_port}",
                                   codec=codec,
                                   tx_depth=tx_depth if tx_depth else 8,
                                   rx_depth=rx_depth if rx_depth else 8,
                                   trace_sample_every=trace_sample_every,
                                   tier="tcp")
        except OSError as e:
            import errno
            if getattr(e, "errno", None) == errno.EADDRINUSE \
                    or any(m in str(e) for m in _BIND_RACE_MARKS):
                raise _BindRace(
                    f"dispatcher lost the result-port bind race "
                    f"({e})") from e
            raise
        try:
            if tracer().enabled:
                try:
                    disp.align_clocks(addrs)
                except (OSError, ConnectionError) as e:
                    print(f"run_dag_chain: clock alignment failed: "
                          f"{e!r}", file=sys.stderr)
            outs = disp.stream(inputs)
            if stats_out is not None:
                stats_out.extend(disp.stats(addrs))
            if tracer().enabled:
                try:
                    disp.collect_trace(addrs)
                except (OSError, ConnectionError) as e:
                    print(f"run_dag_chain: trace collection failed: "
                          f"{e!r}", file=sys.stderr)
        except BaseException as e:
            failure = e
            raise
        finally:
            if failure is not None:
                _kill_procs(procs)
            disp.close()
            if failure is None:
                for pr in procs:
                    try:
                        pr.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        pr.kill()
        for i, pr in enumerate(procs):
            if pr.returncode not in (0, None):
                raise RuntimeError(
                    f"dag node {labels[i]} exited rc={pr.returncode}: "
                    f"{_log_tail(logs[i])}")
        return outs
    except _BindRace:
        _kill_procs(procs)
        raise
    except BaseException as e:
        _kill_procs(procs)
        dead = [(labels[i], pr.returncode, _log_tail(logs[i]))
                for i, pr in enumerate(procs)
                if pr.returncode not in (0, None)]
        races = [d for d in dead
                 if any(m in d[2] for m in _BIND_RACE_MARKS)]
        if races and all(d in races for d in dead):
            raise _BindRace(
                f"{[d[0] for d in races]} lost the port bind race") from e
        if dead and not isinstance(e, RuntimeError):
            detail = "; ".join(
                f"node {lbl} rc={rc}: ...{tail[-800:]}"
                for lbl, rc, tail in dead)
            raise RuntimeError(
                f"dag chain failed ({type(e).__name__}: {e}); dead "
                f"nodes: {detail}") from e
        raise
    finally:
        for lf in logs:
            lf.close()
