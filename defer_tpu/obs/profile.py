"""Stage-interior profiling plane (docs/OBSERVABILITY.md §Profiling).

Four instruments that compose with the live observability plane
instead of replacing it:

* **Phase decomposition** — the compute loops split each frame's
  opaque ``infer`` interval into named phases (``dispatch``: the jit
  call returning, ``device``: ``block_until_ready``, ``host_sync``:
  ``np.asarray``); this module owns the phase NAME table and the
  session arithmetic over the per-node histograms the loops feed.
* **Recompile telemetry** — :class:`RecompileWatcher` is the
  program's one ``jax.monitoring`` listener: it says of every program
  jax builds what kind of time it took (trace, lower, compile, load
  from the persistent cache) and whose (the event's ``fun_name``),
  counts XLA's backend events per process and emits ONE ``recompile``
  flight-recorder event per compile episode — the same
  emit-once/re-arm discipline as ``model_drift``, so a recompile storm
  is one log line per burst, not thousands.
* **Set-up telemetry** — :class:`SetupLog` keeps the intervals of a
  process's start-up (the ``setup`` spans and the listener's events)
  until set-up is over; :func:`setup_breakdown` turns them into seconds
  by kind that sum to the elapsed time.
* **Memory telemetry** — :func:`device_memory_bytes` prices the live
  device arrays (``jax.live_arrays``) without importing jax into a
  process that never used it; :class:`MemoryWatcher` turns it into the
  ``device.mem_bytes`` gauge plus a thresholded ``mem_pressure`` event
  (hysteresis re-arm at 90% of the threshold).
* **Pause telemetry** — :class:`PauseWatcher` is fed by the program
  spans themselves (``obs/trace.py::span``): an occurrence of a phase
  that took far longer than the phase usually does is a *pause*, and
  leaves a counter, a histogram, one ``host_pause`` event that says
  what the thread was doing, and a line on stderr.

:class:`ProfileSession` is the on-demand half: a node's
``profile_start``/``profile_stop`` control commands bracket a window
and reply with the DELTA phase breakdown (counts and summed seconds
per phase over exactly that window), the recompiles inside it, and the
live-memory reading — the machine-readable row the ``defer_tpu
profile`` CLI merges across nodes.  Everything here is off until
asked for but the compile listener, which the package installs when it
is imported (unarmed: it fires only when jax traces or builds a
program): the other watchers are installed lazily and the phase
histograms are the same always-on-cheap instruments the stats plane
already pays for.
"""

from __future__ import annotations

import gc
import heapq
import os
import resource
import sys
import threading
import time

from .events import emit as emit_event
from .registry import REGISTRY
from .trace import ANNOTATION_PREFIX, PAUSE_BEHIND_KEY, ROUND_ARGS

#: the named phases of one frame through a stage node's compute loop,
#: in wall order.  ``dispatch`` + ``queue`` + ``device`` + ``host_sync``
#: tiles ``infer``, which stays the issue-to-materialize total — the
#: invariant ``scripts/profile_smoke.py`` asserts.  ``queue`` is the
#: frame's residency in the async in-flight window between its dispatch
#: returning and its drain turn: ~0 in the serial loop, and in the
#: overlapped loop the latency the pipeline HIDES (a large queue share
#: on a fast stage is overlap working, not time lost).
NODE_PHASES = ("dispatch", "queue", "device", "host_sync")

#: the decode engine's per-step phases (serve/engine.py), each once a
#: step: host-side gather of the per-slot rows, jit dispatch, device
#: wait, host sync of the sampled ids, and per-slot delivery/bookkeeping.
#: Sampling and the KV write happen INSIDE the fused step program, so
#: they are part of ``device`` here; splitting them needs
#: ``jax.profiler`` (the profile CLI's --jax-trace), not host timers.
#: The engine keeps one step launched ahead of the one it reads, so one
#: call of ``ContinuousBatchEngine.step`` holds the first two phases of
#: step n+1 and then the last three of step n — in this order, under
#: one root ``step``, which opens once a step around the call that
#: launches it.  A busy period's first call holds the first two alone
#: (nothing was in flight), its last the last three with no root (nothing
#: is left to launch).  ``sync`` carries ``{"ahead": 0|1}``: whether a
#: later step was running on the device under the wait; ``device``
#: ``{"passes": k, "step": n}``: the joined prompts' passes launched in
#: front of the step it waits for (the pause watch keeps the wait's
#: typical time a count: ``_PhaseWatch.behind``) and that step's number.
ENGINE_PHASES = ("gather", "dispatch", "device", "sync", "delivery")

#: inside the engine's ``dispatch``, in wall order: ``upload`` is the
#: one ``jax.device_put`` of the step's five per-slot rows (host to
#: device; the ids a slot sampled the step before stay on the device),
#: ``launch`` the jitted step's call alone
ENGINE_DISPATCH_PHASES = ("upload", "launch")

#: what the engine's scheduling thread does between two steps
#: (serve/engine.py ``EngineLoop.run``): ``join`` is the sweep that
#: applies cancellations and moves queued requests into free slots —
#: host work beside the step in flight; ``park`` is the one blocking pop
#: taken with no active slot — the engine has nothing to do; ``prefill``
#: is one joined slot's prompt through the prefill programs, LAUNCHED
#: and not waited for: it closes when its launches have returned, and
#: the pass's device time is the ``jit_engine_prefill`` runs of a
#: profiler trace (``ContinuousBatchEngine.step`` runs it in front of
#: the launch that takes the slot in, behind the step in flight: a
#: sibling of ``join`` and outside ``step``)
ENGINE_LOOP_PHASES = ("join", "park", "prefill")

#: ``PipelinedDecoder.generate`` (runtime/decode.py), in wall order:
#: ``init`` is what a generation sets up on the host before its first
#: chunk, in two parts around the one ``prefill`` (the prompt's upload
#: and the zero state; then the first tokens' upload, the schedule and
#: the result buffer).  Then once a chunk: ``dispatch`` is the chunk
#: program's call returning (the enqueue; no sync), ``sync`` the
#: ``np.asarray`` that waits for its ids, ``scatter`` the host copy
#: into the result, ``emit`` the caller's ``on_tokens``.  A steady round
#: is ``dispatch(n+1)``, ``sync(n)``, ``scatter(n)``, ``emit(n)``: the
#: loop keeps one chunk launched ahead of the one it reads, so only
#: during init and a generation's first dispatch has the device nothing
#: queued.
DECODE_PHASES = ("init", "prefill", "dispatch", "sync", "scatter", "emit")

#: inside the ring's ``dispatch``, in wall order: ``upload`` is the
#: chunk's host-to-device scalars (where the chunk starts and stops,
#: to every stage's device), ``launch`` the jitted chunk program's call
#: alone
DECODE_DISPATCH_PHASES = ("upload", "launch")

#: after a generation's last chunk, where the ring's blocks sow per-step
#: statistics (``DecoderBlock.decode_stats``; today the routed experts'
#: ``decode.moe.*`` counters, the retention blocks'
#: ``decode.retention.updates`` and the state-space blocks'
#: ``decode.ssm.updates``; a graph whose blocks both route and keep a
#: state, ``models/granite_hybrid.py``'s, sows all five;
#: ``models/kimi_k2.py``'s two kinds of block the four ``moe.*`` names,
#: the dense one zeros; ``models/longcat_flash.py``'s those four and
#: ``moe.zero_assignments`` / ``moe.real_assignments``, the pairs that
#: fell to zero-compute experts and to routed ones): their sums,
#: which came to the host a chunk at a time with the chunk's ids, go to
#: the counters
DECODE_STATS_PHASES = ("moe_stats",)

#: the front door's per-request phase on the client's reader thread
#: (serve/frontdoor.py): prompt frame received -> queued or shed
DOOR_PHASES = ("admit",)

#: a process's start-up, each once a call and none inside a loop:
#: ``import`` is ``import defer_tpu`` (and ``import jax`` where the
#: package was the first to ask for it: ``args["jax_preloaded"]``),
#: ``place`` one placement of a model's weights on its devices,
#: ``relay`` one leaf laid out anew on the device (inside ``place``),
#: ``state`` the zero caches and states' allocation, ``first_call`` the
#: first call of a compiled program through its return — the launch, not
#: the device's run — with the listener's trace, lower and
#: compile-or-load of ``args["program"]`` inside it
SETUP_PHASES = ("import", "place", "relay", "state", "first_call")

#: every program span (obs/trace.py ``span``): layer -> (registry
#: prefix, root, phases).  ``span(layer, phase)`` is named
#: ``<layer>.<phase>`` and feeds the histogram ``<prefix>.<phase>_s``;
#: the root encloses one round of the first phases and feeds none
#: (``serve.decode.step_s`` is the engine's round: from one step's ids
#: reaching the host to the next step's; launch to ids for a step
#: launched onto an empty queue).
#: A name that is not spelled here does not exist.
SPAN_LAYERS = {
    "decode": ("decode", "generate", DECODE_PHASES + DECODE_DISPATCH_PHASES
               + DECODE_STATS_PHASES),
    "engine": ("serve.decode", "step", ENGINE_PHASES
               + ENGINE_DISPATCH_PHASES + ENGINE_LOOP_PHASES),
    "door": ("serve.door", None, DOOR_PHASES),
    "setup": ("setup", None, SETUP_PHASES),
}

#: phases the pause watch never judges: ``dispatch`` is tiled by its two
#: children, which are judged (a pause would count twice), and ``park``
#: is a blocking pop by design.  A root encloses a whole round and is
#: not judged either, nor any phase of set-up: a 9 s compile is no pause.
PAUSE_UNJUDGED = {"decode": ("dispatch",), "engine": ("dispatch", "park"),
                  "setup": SETUP_PHASES}

#: where a layer's pause baseline is taken (:meth:`PauseWatcher.rebase`):
#: a generation begins, the engine leaves ``park`` — so that nothing is
#: read per round
PAUSE_BASELINE_AT = {"decode": ("generate", "enter"),
                     "engine": ("park", "exit")}

#: where set-up ends (:meth:`SetupLog.close`), at the other end of the
#: baseline's span: the process's first generation is over (the ring's
#: warm-up has built every program its loop uses), the engine parks
#: after its first busy period.  One comparison a generation or a busy
#: period, nothing a chunk or a step
SETUP_CLOSE_AT = {"decode": ("generate", "exit"),
                  "engine": ("park", "enter")}

#: the key of a span's ``args`` that numbers the layer's rounds: a
#: ``host_pause`` event names the newest one its thread has seen
PAUSE_ROUND_KEY = {"decode": "steps_run", "engine": "step"}

#: ``chipbench:<layer>.pause``: the marker a pause leaves at its phase's
#: end while a profiler session is live (the one annotation name that is
#: no phase)
PAUSE_MARKER = "pause"

#: an occurrence is a pause when its wall time is at least this much
#: over the phase's typical time AND at least PAUSE_TIMES times it
PAUSE_OVER_S = 0.010
PAUSE_TIMES = 3.0
#: occurrences of a phase that only found its typical time: the first
#: one judged is the one after (a warm-up's compiling dispatch is the
#: phase's first, and the median of these leaves it out)
PAUSE_UNJUDGED_FIRST = 7
#: a phase keeps a typical time for each count of what rode in front of
#: it (``obs/trace.py::PAUSE_BEHIND_KEY``: the engine's ``device`` behind
#: 0, 1, or this many and more of joined prompts' passes)
PAUSE_BEHIND_MOST = 2

#: the jax.monitoring duration event around ``compile_or_get_cached``
#: (jax 0.9.0, ``pxla.py``): it fires once a program jax builds — never
#: on a hit in the in-memory program cache, but on a hit in the
#: persistent cache too, which fires _RETRIEVAL_EVENT inside it first
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
#: the listener's events -> the kind of time each is.  A backend event
#: that held a retrieval is a ``cache_load``, whole, and no ``compile``
_JAX_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    _COMPILE_EVENT: "compile",
}
JAX_KINDS = ("trace", "lower", "compile", "cache_load")
#: programs the listener's table names; the cheaper ones are summed
#: under ``"other"``
PROGRAM_TABLE_SIZE = 64

#: the kinds of :func:`setup_breakdown`: the ``setup`` spans, the
#: listener's, and ``warm_run`` — what of the first generation (the
#: engine's first busy period) lies under neither: the prefill's and the
#: first chunks' runs on the device.  In the set-up line's order
SETUP_KINDS = ("import", "place", "relay", "state") + JAX_KINDS \
    + ("first_call", "warm_run")
#: intervals a :class:`SetupLog` keeps (a cell's set-up holds a few
#: hundred); what comes after is counted in ``setup.dropped_intervals``
#: and its time stays unnamed
SETUP_MAX_INTERVALS = 16384


class RecompileWatcher:
    """The program's one ``jax.monitoring`` listener: what kind of time
    building a program took, and whose.

    Every duration event of :data:`_JAX_EVENT_KINDS` feeds a histogram
    (``jax.trace_s``, ``jax.lower_s``, ``jax.cache_load_s`` beside
    ``jax.compile_s``, which holds every backend event, loads included,
    as ``jax.compiles`` counts them), a row of the table
    :meth:`programs` under the event's ``fun_name``, and ``intervals``
    (the process's watcher: its :class:`SetupLog`).  An event is called
    at its end, so its interval is ``(now - duration, now)``; an inner
    ``jit`` traced inside an outer one's trace fires inside the outer's
    time, and a thread's events of one kind are not summed twice: jax
    records a scalar under the same name where such a time *begins*
    (:meth:`on_start`), so the listener keeps a depth a thread and a
    kind, and an event's histogram and row take its time less that of
    the events of its kind directly inside it.

    It also emits ONE ``recompile`` flight-recorder event per compile
    EPISODE.  An episode is a burst of backend events separated from the
    previous burst by at least ``episode_gap_s`` of quiet: the first of
    a burst emits (naming its ``program``), the rest only count — so an
    injected shape change on a hot loop produces exactly one event, and
    warmup compiles before :meth:`arm` produce none.  Counting is always
    on once installed; event emission starts at :meth:`arm` (call it
    after warmup, or never for a silent counter).
    """

    def __init__(self, *, episode_gap_s: float = 5.0, intervals=None):
        self.episode_gap_s = float(episode_gap_s)
        self._lock = threading.Lock()
        self._local = threading.local()
        self._installed = False
        self._armed = False
        self._last_t: float | None = None
        self._intervals = intervals     # callable (kind, t0, t1) or None
        self._compiles = REGISTRY.counter("jax.compiles")
        self._compile_s = REGISTRY.histogram("jax.compile_s")
        self._hists = {kind: REGISTRY.histogram(f"jax.{kind}_s")
                       for kind in JAX_KINDS if kind != "compile"}
        self._programs: dict[str, dict] = {}

    @property
    def count(self) -> int:
        return self._compiles.value

    def install(self) -> "RecompileWatcher":
        """Register the ``jax.monitoring`` listener (idempotent)."""
        with self._lock:
            if self._installed:
                return self
            try:
                import jax.monitoring as _mon
                _mon.register_event_duration_secs_listener(
                    self.on_duration)
                _mon.register_scalar_listener(self.on_start)
            except Exception as e:  # noqa: BLE001 — a build without the
                # monitoring events counts nothing, loudly on stderr once
                print(f"profile: jax.monitoring unavailable ({e!r}); "
                      f"no program is timed or counted",
                      file=sys.stderr, flush=True)
            self._installed = True
            return self

    def arm(self) -> None:
        """Start (or restart) event emission: the NEXT compile opens a
        fresh episode and emits.  Call after warmup."""
        with self._lock:
            self._armed = True
            self._last_t = None

    def disarm(self) -> None:
        """Stop event emission (counting continues — it is always on
        once installed).  A later :meth:`arm` restarts episodes."""
        with self._lock:
            self._armed = False

    def _open_events(self, kind: str) -> list:
        """The calling thread's events of ``kind`` that have begun and
        not ended, outermost first: for each, the seconds of the events
        directly inside it that have ended (a list of one)."""
        stacks = self._local.__dict__
        stack = stacks.get(kind)
        if stack is None:
            stack = stacks[kind] = []
        return stack

    def on_start(self, name: str, _value, **_kw) -> None:
        """The listener's other ear: the scalar jax records where a
        timed event begins (``dispatch.log_elapsed_time``)."""
        kind = _JAX_EVENT_KINDS.get(name)
        if kind is not None:
            self._open_events(kind).append([0.0])

    def on_duration(self, name: str, dur: float, fun_name=None,
                    **_kw) -> None:
        """The listener: one ``jax.monitoring`` duration event."""
        local = self._local
        if name == _RETRIEVAL_EVENT:
            # inside the backend event that follows on this thread
            local.retrieved = True
            return
        kind = _JAX_EVENT_KINDS.get(name)
        if kind is None:
            return
        now = time.perf_counter()
        # the trace event names the function, the others its module
        program = str(fun_name or "?")
        if program.startswith("jit(") and program.endswith(")"):
            program = program[4:-1]
        # less the events of its kind directly inside it; its own time
        # is inside the one around it, if any
        opened = self._open_events(kind)
        own = max(dur - opened.pop()[0], 0.0) if opened else dur
        if opened:
            opened[-1][0] += dur
        if kind == "compile":
            self._backend_event(dur, program)
            if getattr(local, "retrieved", False):
                local.retrieved = False
                kind = "cache_load"
        if kind != "compile":
            self._hists[kind].record(own)
        with self._lock:
            row = self._programs.get(program)
            if row is None:
                if len(self._programs) >= 2 * PROGRAM_TABLE_SIZE:
                    self._programs = _costliest_rows(self._programs)
                row = self._programs[program] = _program_row()
            row[kind + "_s"] += own
            row["count"] += kind in ("compile", "cache_load")
        if self._intervals is not None:
            self._intervals(kind, now - dur, now)

    def programs(self) -> dict:
        """Program name -> ``{trace_s, lower_s, compile_s, cache_load_s,
        count}``: seconds of each kind jax spent on the programs of that
        name (a load from the persistent cache is ``cache_load_s`` and
        no ``compile_s``) and how many it built or loaded.  The
        :data:`PROGRAM_TABLE_SIZE` costliest names; the rest are summed
        under ``"other"``."""
        with self._lock:
            return _costliest_rows(self._programs)

    def _backend_event(self, dur: float, program: str) -> None:
        self._compiles.inc()
        if dur:
            self._compile_s.record(dur)
        now = time.monotonic()
        with self._lock:
            quiet = (self._last_t is None
                     or now - self._last_t >= self.episode_gap_s)
            self._last_t = now
            # episode discipline: only the first compile after
            # episode_gap_s of quiet emits; the rest of the burst just
            # counts (re-arming is lazy — no timer thread)
            fire = self._armed and quiet
        if fire:
            emit_event("recompile", count=self._compiles.value,
                       program=program)


def _program_row() -> dict:
    return {**{kind + "_s": 0.0 for kind in JAX_KINDS}, "count": 0}


def _row_seconds(row: dict) -> float:
    return sum(row[kind + "_s"] for kind in JAX_KINDS)


def _costliest_rows(rows: dict) -> dict:
    """A copy of a program table, costliest first, at most
    :data:`PROGRAM_TABLE_SIZE` names and the others under ``"other"``."""
    rows = {name: dict(row) for name, row in rows.items()}
    other = rows.pop("other", None)
    names = sorted(rows, key=lambda nm: -_row_seconds(rows[nm]))
    out = {nm: rows[nm] for nm in names[:PROGRAM_TABLE_SIZE]}
    for nm in names[PROGRAM_TABLE_SIZE:]:
        other = other or _program_row()
        for key, v in rows[nm].items():
            other[key] += v
    if other is not None:
        out["other"] = other
    return out


class SetupLog:
    """The intervals ``(kind, thread, t0, t1)`` of a process's set-up,
    on ``perf_counter``'s clock: every ``setup`` span and every event of
    the compile listener, until set-up is over (:meth:`close`).  Later
    intervals are not kept: a program compiled after the close goes to
    the listener's table and the ``recompile`` events, and is no set-up.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._dropped = REGISTRY.counter("setup.dropped_intervals")
        self.clear()

    def clear(self) -> None:
        """Forget everything and open again (a process has one set-up;
        a test has several)."""
        with self._lock:
            self._intervals: list = []
            self._open = True
            #: open, and a compiled program has been called: the next
            #: generation's end or park ends set-up
            self._ripe = False
            #: :func:`setup_breakdown` as it stood at the close, and the
            #: listener's table then, costliest first (the programs of
            #: set-up, and none built later)
            self.done: dict | None = None
            self.programs: dict = {}

    def add(self, kind: str, t0: float, t1: float) -> None:
        if not self._open:
            return
        with self._lock:
            if len(self._intervals) >= SETUP_MAX_INTERVALS:
                self._dropped.inc()
                return
            self._intervals.append((kind, threading.get_ident(), t0, t1))
            if kind == "first_call":
                self._ripe = self._open

    def intervals(self) -> list:
        with self._lock:
            return list(self._intervals)

    def close(self, layer: str) -> None:
        """Set-up is over, if a compiled program has run: called where
        :data:`SETUP_CLOSE_AT` says, on the thread that ran ``layer``'s
        first round.  What of that round lies under no other interval
        is ``warm_run``: the round began where the layer's pause
        baseline was taken.  Freezes the breakdown, says it in one line
        on stderr and emits it as one ``setup_done`` event."""
        if not self._ripe:
            return
        now = time.perf_counter()
        base = pause_watcher()._bases().get(layer)
        with self._lock:
            if not self._ripe:
                return
            self._ripe = self._open = False
            if base is not None:
                self._intervals.append(
                    ("warm_run", threading.get_ident(), base[0], now))
            intervals = list(self._intervals)
        done = setup_breakdown(intervals, now)
        self.programs = recompile_watcher().programs()
        self.done = done
        costliest = ",".join(
            f"{name}:{_row_seconds(row):.3f}"
            for name, row in list(self.programs.items())[:3]
            if name != "other")
        emit_event("setup_done", **done, costliest=costliest,
                   threads=len({thread for _, thread, _, _ in intervals}))
        print("defer_tpu: setup " + " ".join(
            f"{k}={v:.6f}" for k, v in done.items())
            + f" costliest={costliest}", file=sys.stderr, flush=True)


def setup_breakdown(intervals=None, end: float | None = None) -> dict | None:
    """Where set-up's time went: ``elapsed_s`` (the first interval's
    start to set-up's end), a ``<kind>_s`` for each of
    :data:`SETUP_KINDS` and ``unnamed_s``, which sum to ``elapsed_s``.

    The seconds are exclusive: each instant goes to the innermost
    interval over it — the one that ended first, as a child ends before
    its parent and an event is listed at its end — so a re-laying
    program's compile is ``compile`` and not also ``relay`` and
    ``place``, and an inner ``jit``'s trace is counted once.  What no
    interval of any thread covers is ``unnamed_s``.  (The histograms
    ``setup.<phase>_s`` stay inclusive: ``setup.relay_s`` is what
    re-laying costs, its compile included.)

    Without arguments: of this process's :class:`SetupLog` — as frozen
    at the close once set-up is over, so far before that; ``None`` while
    the log is empty.  With ``intervals`` (and ``end``, default the
    last interval's): of that list."""
    if intervals is None:
        log = setup_log()
        if log.done is not None:
            return dict(log.done)
        intervals = log.intervals()
    if not intervals:
        return None
    cuts = sorted({t for _, _, t0, t1 in intervals for t in (t0, t1)})
    if end is None:
        end = cuts[-1]
    by_start = sorted(range(len(intervals)), key=lambda i: intervals[i][2])
    seconds = dict.fromkeys(SETUP_KINDS, 0.0)
    live: list = []         # (t1, -t0, index): the innermost first
    nxt, covered = 0, 0.0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(by_start) and intervals[by_start[nxt]][2] <= a:
            i = by_start[nxt]
            heapq.heappush(live, (intervals[i][3], -intervals[i][2], i))
            nxt += 1
        while live and live[0][0] <= a:
            heapq.heappop(live)
        if live:
            kind = intervals[live[0][2]][0]
            seconds[kind] = seconds.get(kind, 0.0) + (b - a)
            covered += b - a
    elapsed = end - cuts[0]
    return {"elapsed_s": elapsed,
            **{kind + "_s": s for kind, s in seconds.items()},
            "unnamed_s": elapsed - covered}


def device_memory(ensure: bool = False) -> tuple[int, int] | None:
    """(total bytes, array count) of this process's live device arrays
    — ``None`` when jax was never imported here (``ensure=True`` forces
    the import) or the backend has no ``live_arrays``.  Cheap enough
    for the obs_push cadence, not for the per-frame hot path."""
    if "jax" not in sys.modules and not ensure:
        return None
    import jax
    try:
        arrs = jax.live_arrays()
    except Exception:  # noqa: BLE001 — backend without live_arrays
        return None
    total = 0
    for a in arrs:
        try:
            total += int(a.nbytes)
        except Exception:  # noqa: BLE001 — deleted/donated buffers
            pass
    return total, len(arrs)


def device_memory_bytes(ensure: bool = False) -> int | None:
    mem = device_memory(ensure)
    return None if mem is None else mem[0]


class MemoryWatcher:
    """Publishes live device-array bytes as the ``device.mem_bytes``
    gauge and emits a ``mem_pressure`` event when a threshold is
    crossed (one per excursion: re-arms below 90% of the threshold).

    The threshold, first match wins: :meth:`set_threshold`, the
    ``DEFER_MEM_PRESSURE_BYTES`` env var (absolute bytes — the testable
    knob on backends without memory_stats), or
    ``DEFER_MEM_PRESSURE_FRAC`` (default 0.9) of the device's
    ``memory_stats()['bytes_limit']`` where the backend reports one.
    No threshold -> gauge only, no events.
    """

    def __init__(self):
        self._threshold: float | None = None
        self._armed = True
        self._gauge = REGISTRY.gauge("device.mem_bytes")

    def set_threshold(self, n_bytes: float | None) -> None:
        self._threshold = None if n_bytes is None else float(n_bytes)

    def threshold_bytes(self) -> float | None:
        if self._threshold is not None:
            return self._threshold
        env = os.environ.get("DEFER_MEM_PRESSURE_BYTES")
        if env:
            return float(env)
        if "jax" not in sys.modules:
            return None
        import jax
        try:
            stats = jax.devices()[0].memory_stats() or {}
        except Exception:  # noqa: BLE001 — cpu backend: no stats
            return None
        limit = stats.get("bytes_limit")
        if not limit:
            return None
        frac = float(os.environ.get("DEFER_MEM_PRESSURE_FRAC", "0.9"))
        return limit * frac

    def observe(self) -> int | None:
        """One reading: update the gauge, check the threshold.  Called
        from obs_snapshot (per push), never per frame."""
        mem = device_memory()
        if mem is None:
            return None
        n, arrs = mem
        self._gauge.v = float(n)
        thr = self.threshold_bytes()
        if thr:
            if self._armed and n > thr:
                self._armed = False
                emit_event("mem_pressure", bytes=n,
                           threshold=int(thr), live_arrays=arrs)
            elif not self._armed and n < 0.9 * thr:
                self._armed = True
        return n


class _PhaseWatch:
    """One phase's typical wall time, kept by its :class:`PauseWatcher`
    and fed by every span of the phase (``obs/trace.py``)."""

    __slots__ = ("layer", "phase", "typ", "_first", "_streak", "_owner")

    def __init__(self, owner: "PauseWatcher", layer: str, phase: str):
        self._owner = owner
        self.layer, self.phase = layer, phase
        #: seconds; ``None`` while the phase's first occurrences, which
        #: are never judged, are still being collected
        self.typ: float | None = None
        self._first: list[float] = []
        self._streak = 0        # occurrences over PAUSE_TIMES x, in a row

    def behind(self, count: int) -> "_PhaseWatch":
        """The phase's watch for occurrences that waited behind ``count``
        passes (at most ``PAUSE_BEHIND_MOST`` are told apart): itself for
        none, else a watch of its own in the owner's table, so that a
        wait behind a pass is compared with waits behind a pass — neither
        a pause for being longer than a plain one, nor hiding one by
        lifting the plain waits' typical time."""
        if not count:
            return self
        return self._owner.phase(self.layer, self.phase,
                                 min(count, PAUSE_BEHIND_MOST))

    def feed(self, sp, dur: float) -> None:
        """One occurrence's wall time, from the span ``sp`` at its exit.
        The estimate follows the recent occurrences (an eighth of the
        way each); one counts for at most PAUSE_TIMES x typical in it,
        so a pause barely moves it.  Three such in a row are no pause:
        the phase has become longer (another ``token_chunk``, say) and
        finds its typical time anew."""
        typ = self.typ
        if typ is None:
            self._first.append(dur)
            if len(self._first) >= PAUSE_UNJUDGED_FIRST:
                self.typ = sorted(self._first)[len(self._first) // 2]
                self._first = []
        elif dur < PAUSE_TIMES * typ:
            self.typ = typ + 0.125 * (dur - typ)
            if self._streak:
                self._streak = 0
        elif self._streak >= 2:
            self.typ, self._first, self._streak = None, [dur], 0
        else:
            self._streak += 1
            self.typ = max(typ + 0.125 * (PAUSE_TIMES - 1.0) * typ, 1e-9)
            if dur - typ >= PAUSE_OVER_S:
                self._owner.pause(self, sp, dur, typ)


class PauseWatcher:
    """Says which phase of which round froze, and what its thread was
    doing meanwhile.

    ``span`` feeds every judged phase's wall time to its
    :class:`_PhaseWatch`; an occurrence at least ``PAUSE_OVER_S`` over
    and ``PAUSE_TIMES`` times the phase's typical time is a *pause*:

    * counter ``<prefix>.pauses`` and histogram ``<prefix>.pause_s``
      (the time over typical);
    * one ``host_pause`` flight-recorder event: the span's own numbers
      (wall, typical, this thread's and the process's CPU time inside
      the phase) and, where the layer has a baseline on this thread
      (:meth:`rebase`), how far :meth:`_thread_counts` moved since it —
      over ``since_ms``, not inside the phase alone: nothing is read per
      round;
    * one line on stderr, at most one a second (the event is never
      dropped for it);
    * while a profiler session is live, a marker
      ``chipbench:<layer>.pause`` right behind the phase's end, which
      puts the pause on the device trace's clock.
    """

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._watches: dict = {}
        self._last_line = float("-inf")
        self._lacks: set = set()    # count sources this platform lacks

    def phase(self, layer: str, phase: str,
              behind: int = 0) -> _PhaseWatch | None:
        """The watch of ``<layer>.<phase>`` for occurrences ``behind``
        that many passes (``_PhaseWatch.behind``; 0: the phase's plain
        ones); ``None`` for a phase that is never judged
        (``PAUSE_UNJUDGED``, and every root)."""
        root = SPAN_LAYERS[layer][1]
        if phase == root or phase in PAUSE_UNJUDGED.get(layer, ()):
            return None
        key = (layer, phase, behind) if behind else (layer, phase)
        with self._lock:
            w = self._watches.get(key)
            if w is None:
                w = self._watches[key] = _PhaseWatch(self, layer, phase)
                if behind > 1:
                    # rounds behind two passes are a handful a run, too
                    # few to find a typical time among: it starts at the
                    # waits behind one and a pass (what those take over
                    # the plain waits) for each more, and is judged from
                    # its first occurrence
                    one = self._watches.get((layer, phase, 1))
                    none = self._watches.get((layer, phase))
                    if one and none and one.typ and none.typ:
                        w.typ = one.typ + (behind - 1) * max(
                            one.typ - none.typ, 0.0)
            return w

    def _thread_counts(self) -> dict:
        """What the kernel and the interpreter have counted for the
        calling thread so far, under the names a ``host_pause`` event
        gives their differences.  A source this platform lacks (no
        ``/proc``; a sandbox whose files are missing or read all zero,
        as the TPU host's do) leaves its names out, and is not asked
        again."""
        out, lacks = {}, self._lacks
        if "rusage" not in lacks:
            try:
                ru = resource.getrusage(resource.RUSAGE_THREAD)
                out["vol_switches"] = ru.ru_nvcsw
                out["invol_switches"] = ru.ru_nivcsw
                out["major_faults"] = ru.ru_majflt
            except (AttributeError, ValueError, OSError):
                lacks.add("rusage")     # RUSAGE_THREAD is Linux's
        if "schedstat" not in lacks:
            try:
                with open("/proc/thread-self/schedstat") as f:
                    fields = [int(x) for x in f.read().split()]
                if len(fields) < 2 or not any(fields):
                    raise ValueError(fields)
                out["runq_wait_ms"] = fields[1] * 1e-6  # runnable, not run
            except (OSError, ValueError):
                lacks.add("schedstat")
        if "stat" not in lacks:
            try:
                with open("/proc/stat") as f:
                    cpu = f.readline().split()
                ticks = [int(x) for x in cpu[1:]]
                if cpu[0] != "cpu" or len(ticks) < 8 or not any(ticks):
                    raise ValueError(cpu)
                out["steal_ms"] = ticks[7] * 1e3 / os.sysconf("SC_CLK_TCK")
            except (OSError, ValueError, IndexError):
                lacks.add("stat")
        out["gc_collections"] = sum(
            g["collections"] for g in gc.get_stats())
        return out

    def _bases(self) -> dict:
        """The calling thread's baselines: layer -> (when, counts)."""
        try:
            return self._local.bases
        except AttributeError:
            bases = self._local.bases = {}
            return bases

    def rebase(self, layer: str) -> None:
        """Take the calling thread's baseline for ``layer`` anew."""
        self._bases()[layer] = (time.perf_counter(), self._thread_counts())

    def pause(self, watch: _PhaseWatch, sp, dur: float, typ: float) -> None:
        layer, phase = watch.layer, watch.phase
        jax = sys.modules.get("jax")
        if jax is not None:     # first: the marker belongs at sp's end
            with jax.profiler.TraceAnnotation(
                    f"{ANNOTATION_PREFIX}{layer}.{PAUSE_MARKER}"):
                pass
        prefix = SPAN_LAYERS[layer][0]
        count = REGISTRY.counter(f"{prefix}.pauses")
        over = REGISTRY.histogram(f"{prefix}.pause_s")
        count.inc()
        over.record(dur - typ)
        data = {"layer": layer, "phase": phase,
                "wall_ms": round(dur * 1e3, 3),
                "typical_ms": round(typ * 1e3, 3),
                "cpu_ms": round(sp.cpu_s * 1e3, 3),
                "proc_cpu_ms": round(sp.proc_cpu_s * 1e3, 3)}
        rnd = ROUND_ARGS.get(layer, {}).get(PAUSE_ROUND_KEY.get(layer))
        if rnd is not None:
            data["round"] = rnd
        if sp.args and PAUSE_BEHIND_KEY in sp.args:
            # the count whose typical time the occurrence was held to
            data[PAUSE_BEHIND_KEY] = sp.args[PAUSE_BEHIND_KEY]
        base = self._bases().get(layer)
        if base is not None:
            t_base, then = base
            now = self._thread_counts()
            data["since_ms"] = round((sp.t1 - t_base) * 1e3, 3)
            for key, v in now.items():
                if key in then:
                    data[key] = round(v - then[key], 3)
        emit_event("host_pause", **data)
        with self._lock:
            say = sp.t1 - self._last_line >= 1.0
            if say:
                self._last_line = sp.t1
        if say:
            # the layer's totals so far ride the line, so a log that the
            # limit thinned still accounts for every pause
            print("defer_tpu: host_pause " + " ".join(
                f"{k}={v}" for k, v in data.items())
                + f" pauses={count.value} pause_s_sum={over.sum:.6f}",
                file=sys.stderr, flush=True)
        self.rebase(layer)


class ProfileSession:
    """One ``profile_start`` .. ``profile_stop`` window on a node: a
    baseline snapshot of the phase histograms at start, a delta
    breakdown at stop.

    The phase histograms are cumulative (they feed stats/obs_push for
    the process lifetime); the session subtracts its start snapshot so
    the reply prices exactly the profiled window.  Window percentiles
    are not derivable from two cumulative snapshots — the reply carries
    per-phase ``count``/``sum_s``/``mean_ms`` (exact over the window)
    and the cumulative p50 for context."""

    def __init__(self, hists: dict, *, processed=None,
                 jax_trace_dir: str | None = None):
        #: name -> LatencyHistogram | None (absent phases stay None)
        self._hists = dict(hists)
        self._processed = processed  # callable -> int, or None
        self._jax_trace_dir = jax_trace_dir
        self._jax_tracing = False
        self._t0: float | None = None
        self._base: dict | None = None

    @staticmethod
    def _snap(h) -> tuple[int, float]:
        if h is None:
            return 0, 0.0
        s = h.summary()
        return int(s.get("count", 0)), float(s.get("sum", 0.0))

    def _trace_failed(self, what: str, e: Exception) -> None:
        """A host backend without a profiler still answers with the
        phase breakdown (a note on stderr); on the chip the device
        trace IS what was asked for, so its failure ends the session
        and raises."""
        import jax
        if jax.default_backend() == "tpu":
            self._t0 = None
            raise e
        print(f"profile: jax.profiler.{what} failed ({e!r})",
              file=sys.stderr, flush=True)

    def start(self) -> dict:
        if self._t0 is not None:
            raise RuntimeError("profile session already started")
        watcher = recompile_watcher().install()
        self._base = {name: self._snap(h)
                      for name, h in self._hists.items()}
        self._base_compiles = watcher.count
        self._base_processed = (self._processed()
                                if self._processed else 0)
        self._t0 = time.perf_counter()
        if self._jax_trace_dir:
            import jax
            try:
                jax.profiler.start_trace(self._jax_trace_dir)
                self._jax_tracing = True
            except Exception as e:  # noqa: BLE001 — see _trace_failed
                self._trace_failed("start_trace", e)
        return {"t0_unix": time.time()}

    def stop(self) -> dict:
        if self._t0 is None:
            raise RuntimeError("profile session never started")
        dt = time.perf_counter() - self._t0
        if self._jax_tracing:
            import jax
            try:
                jax.profiler.stop_trace()
            except Exception as e:  # noqa: BLE001 — symmetric guard
                self._trace_failed("stop_trace", e)
        watcher = recompile_watcher()
        phases = {}
        for name, h in self._hists.items():
            c1, s1 = self._snap(h)
            c0, s0 = self._base[name]
            dc, ds = c1 - c0, s1 - s0
            phases[name] = {
                "count": dc,
                "sum_s": round(ds, 6),
                "mean_ms": round(ds / dc * 1e3, 4) if dc else None,
                "p50_ms_cum": (round(float(h.summary().get(
                    "p50", 0.0)) * 1e3, 4) if h is not None else None),
            }
        doc = {
            "duration_s": round(dt, 6),
            "phases": phases,
            "recompiles": watcher.count - self._base_compiles,
            "mem_bytes": device_memory_bytes(),
            "jax_trace_dir": (self._jax_trace_dir
                              if self._jax_tracing else None),
        }
        if self._processed is not None:
            doc["processed"] = (self._processed()
                                - self._base_processed)
        self._t0 = None
        return doc


_WATCHER: RecompileWatcher | None = None
_SETUP = SetupLog()
_MEM: MemoryWatcher | None = None
_PAUSES: PauseWatcher | None = None
_LOCK = threading.Lock()


def recompile_watcher() -> RecompileWatcher:
    """This process's compile listener, which feeds its
    :class:`SetupLog` (``import defer_tpu`` installs it, unarmed)."""
    global _WATCHER
    with _LOCK:
        if _WATCHER is None:
            _WATCHER = RecompileWatcher(intervals=_SETUP.add)
        return _WATCHER


def setup_log() -> SetupLog:
    """This process's set-up log (the one the ``setup`` spans feed)."""
    return _SETUP


def memory_watcher() -> MemoryWatcher:
    global _MEM
    with _LOCK:
        if _MEM is None:
            _MEM = MemoryWatcher()
        return _MEM


def pause_watcher() -> PauseWatcher:
    """This process's pause watch (the one ``span`` feeds)."""
    global _PAUSES
    with _LOCK:
        if _PAUSES is None:
            _PAUSES = PauseWatcher()
        return _PAUSES
