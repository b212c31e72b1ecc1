"""From the profiler's trace to numbers: busy and idle time of the
device, the operations that took most of it, and what the host was
doing in the longest idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
``jax.profiler.ProfileData``.  On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
operation and whose line ``XLA Modules`` holds one per program run; the
harness's spans are ``TraceAnnotation`` events named ``chipbench:<name>``
on the host plane's thread lines, on the same clock.  The arithmetic is
on plain ``(start, end)`` intervals so that it can be checked without a
trace (``chipbench/tests/``).
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "chipbench:"
#: operations that only enclose others (a ``scan`` is one ``while`` event
#: around its body's events): their time is their children's
PARENT_KINDS = ("while", "conditional", "call")
#: idle gaps shorter than this are pauses between operations of one
#: program, not something the host could fill
SHORT_GAP_S = 20e-6

Interval = tuple[float, float]


# -- interval arithmetic ----------------------------------------------------

def union(intervals) -> list[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: list[list[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: list[Interval], lo: float, hi: float) -> list[Interval]:
    """The parts of ``[lo, hi]`` that the disjoint, sorted ``busy`` leaves."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


# -- names ------------------------------------------------------------------

def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(event_name: str) -> str:
    """``fusion.12`` -> ``fusion``, ``fusion.12.remat`` -> ``fusion.remat``:
    the operation without its numbers."""
    return re.sub(r"\.\d+", "", op_name(event_name))


# -- the reduction ------------------------------------------------------------

class DeviceTrace:
    """One chip's events, in seconds on the trace's clock."""

    def __init__(self, name: str):
        self.name = name
        self.ops: list[tuple[str, float, float]] = []      # (name, s, e)
        self.modules: list[tuple[str, float, float]] = []


class TraceReduction:
    """Busy and idle time over the traced window, per chip and averaged.

    The window is the harness's ``chipbench:window`` span where the trace
    holds one, else first to last device event."""

    def __init__(self, devices: list[DeviceTrace],
                 spans: list[tuple[str, float, float]]):
        self.devices = devices
        self.spans = spans
        win = [s for s in spans if s[0] == "window"]
        if win:
            self.window = (win[0][1], win[0][2])
        else:
            evs = [(s, e) for d in devices for _n, s, e in d.ops]
            self.window = (min(s for s, _ in evs), max(e for _, e in evs)) \
                if evs else (0.0, 0.0)
        lo, hi = self.window
        self.window_s = hi - lo
        self.busy_by_device = [
            clip(union((s, e) for _n, s, e in d.ops), lo, hi)
            for d in devices]
        self.busy_s_by_device = [total(b) for b in self.busy_by_device]
        self.busy_s = sum(self.busy_s_by_device) / max(len(devices), 1)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s else 0.0

    def idle_share_by_device(self) -> list[float]:
        return [1.0 - b / self.window_s if self.window_s else 0.0
                for b in self.busy_s_by_device]

    # -- programs and operations -------------------------------------------

    def module_runs(self, pattern: str, device: int = 0) -> list[float]:
        """Device seconds of every run inside the window of the programs
        whose name matches ``pattern``."""
        lo, hi = self.window
        rx = re.compile(pattern)
        return [e - s for n, s, e in self.devices[device].modules
                if rx.search(n) and s >= lo and e <= hi]

    def top_ops(self, n: int = 10, device: int = 0):
        """``[[kind, seconds], ...]``: operations by total time inside the
        window, grouped by kind, enclosing parents left out (they would
        count their children's time twice)."""
        lo, hi = self.window
        by: dict[str, float] = {}
        for name, s, e in self.devices[device].ops:
            kind = op_kind(name)
            if kind in PARENT_KINDS:
                continue
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by[kind] = by.get(kind, 0.0) + d
        return [[k, v] for k, v in
                sorted(by.items(), key=lambda kv: -kv[1])[:n]]

    # -- idle gaps -----------------------------------------------------------

    def idle_gaps(self, n: int = 10, device: int = 0):
        """``[[what, seconds], ...]``: idle time of one chip by what the
        host was doing in it (:func:`idle_split`): the innermost span
        over each instant, so a ``breakdown`` names the loops' phases
        (``decode.sync``, ``engine.device``, ``engine.park``) and not the
        span around the whole call."""
        return idle_split(self, n, device)

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def idle_split(red, n: int = 10, device: int = 0):
    """``[[what, seconds], ...]``: idle time of one chip by what the
    host was doing in it.  A gap is split among the spans over it, each
    instant going to the innermost span there (the shortest; spans of
    several threads count alike); what no span covers goes to the gap's
    largest sharer, ``unattributed`` where no span touches the gap."""
    lo, hi = red.window
    by: dict[str, float] = {}
    spans = sorted((s, e, name) for name, s, e in red.spans
                   if name != "window" and e > s)
    nxt, over = 0, []       # spans[:nxt] were opened; ``over`` may touch
    for g0, g1 in gaps(red.busy_by_device[device], lo, hi):
        if g1 - g0 < SHORT_GAP_S:
            by["between_ops_under_20us"] = \
                by.get("between_ops_under_20us", 0.0) + g1 - g0
            continue
        while nxt < len(spans) and spans[nxt][0] < g1:
            over.append(spans[nxt])
            nxt += 1
        over = [sp for sp in over if sp[1] > g0]    # gaps come in order
        cuts = sorted({g0, g1} | {t for s, e, _n in over
                                  for t in (s, e) if g0 < t < g1})
        shares: dict[str, float] = {}
        for a, b in zip(cuts, cuts[1:]):
            inner = min(((e - s, -s, name) for s, e, name in over
                         if s <= a and e >= b), default=None)
            what = inner[2] if inner else ""
            shares[what] = shares.get(what, 0.0) + b - a
        bare = shares.pop("", 0.0)
        if shares:
            shares[max(shares, key=shares.get)] += bare
        else:
            shares["unattributed"] = bare
        for what, d in shares.items():
            by[what] = by.get(what, 0.0) + d
    return [[k, v] for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])[:n]]


def _events(line) -> list[tuple[str, float, float]]:
    """A trace line's events as ``(name, start, end)`` in seconds."""
    return [(ev.name, ev.start_ns * 1e-9,
             (ev.start_ns + ev.duration_ns) * 1e-9) for ev in line.events]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, n_devices: int | None = None, *,
         host_ops_as_device: bool = False) -> TraceReduction:
    """Reduce one ``.xplane.pb``.  ``host_ops_as_device`` is for the CPU
    rehearsals of this package only: with no TPU plane in the trace, the
    XLA:CPU worker threads' events stand in for one device."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = DeviceTrace(plane.name)
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev.ops = _events(line)
                elif line.name == "XLA Modules":
                    dev.modules = _events(line)
            devices.append((int(m.group(1)), dev))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(n[len(SPAN_PREFIX):], s, e)
                          for n, s, e in _events(line)
                          if n.startswith(SPAN_PREFIX)]
    devices = [d for _i, d in sorted(devices, key=lambda t: t[0])]
    if not devices and host_ops_as_device:
        dev = DeviceTrace("/host:CPU (stand-in)")
        for plane in data.planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    if line.name.startswith("tf_XLA"):
                        dev.ops += [ev for ev in _events(line)
                                    if ev[2] > ev[1]]
        devices = [dev]
    if n_devices is not None:
        devices = devices[:n_devices]
    return TraceReduction(devices, spans)


def reduce_trace(trace_dir: str, n_devices: int | None = None, *,
                 host_ops_as_device: bool = False) -> TraceReduction:
    red = load(find_xplane(trace_dir), n_devices,
               host_ops_as_device=host_ops_as_device)
    if not red.devices or not any(d.ops for d in red.devices):
        raise RuntimeError(
            f"the trace under {trace_dir} holds no operation on a TPU "
            f"plane: nothing ran on the device inside the traced window")
    return red
