"""Idle time of the device by what the host's decode loop was doing, a
round of the loop; the pauses the program itself recorded; and whether
the trace's host and device planes read one clock.

Three things over ``chipbench/trace.py::idle_split`` and
``chipbench/spans.py::durations``,
for the readers ``decode_idle_*_ms``, ``decode_upload_ms``,
``decode_pause_share`` and the engine's four:

* :func:`split` — a window's idle seconds by the innermost program span
  over each instant, the mean over the cell's chips (as
  ``*_device_idle_share`` is); :func:`per_round_ms` sums some of its
  names over the window's rounds.
* :func:`pause_share` — the program's pause watch
  (``defer_tpu/obs/profile.py::PauseWatcher``) leaves a marker
  ``<layer>.pause`` right behind a phase that took far longer than the
  phase does; the time that phase took over the window's median of it,
  summed over the markers, over the window.
* :func:`causality_bracket` — no host span that waited for a program
  can end before the program's end, and no program can begin before the
  host entered the call that launched it.  With ``skew`` = what the
  device plane's clock reads over the host plane's at one instant, every
  round gives ``skew >= -(wait end - program end)`` and ``skew <=
  program start - launch start``; the window's tightest two bracket it.
  Which program a launch began and a wait read is taken from their
  order, not from what lies nearest in time (both loops keep a program
  queued ahead of the one they read), and the upper end only from
  launches that found the chip idle.
  Where the bracket holds 0 (within :data:`SKEW_SLACK_S`) the distances
  between the planes may all be latencies; where it does not, some of
  them are skew for certain.  Either way :func:`split` first moves the
  host spans by the bracket's midpoint (:func:`skew_shift` says why),
  and :func:`note` prints the bracket and the move.

A program without the launch spans (the parent of the PR that added
them) gives ``None`` everywhere.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import statistics
import types
import weakref

from chipbench.spans import durations
from chipbench.trace import clip, gaps, idle_split, total, union

#: a bracket this close to 0 holds it: the annotation's own clock reads
#: lie a fraction of a microsecond from the span's, a program's first
#: operation a few microseconds from its module event
SKEW_SLACK_S = 50e-6


@dataclasses.dataclass(frozen=True)
class Loop:
    """One decode loop's span names (``defer_tpu/obs/profile.py``)."""

    layer: str          #: the spans' prefix, and the pause marker's
    round: str          #: one of these a round
    wake: tuple         #: the chip is done, the host is not back
    launch: tuple       #: the host is in the launch, the chip has not begun
    wait: str           #: the span that waits for the round's program
    call: str           #: the span around the jitted call alone
    upload: str
    program: str        #: pattern of the round's program (module) name
    #: the span around one generation, whose end leaves a launch unread
    generation: str | None = None
    #: spans around the whole loop: idle time they keep has no phase
    outer: tuple = ("generate", "loadgen", "unattributed")


DECODE = Loop(
    layer="decode", round="decode.dispatch", wake=("decode.sync",),
    launch=("decode.dispatch", "decode.upload", "decode.launch"),
    wait="decode.sync", call="decode.launch", upload="decode.upload",
    program=r"jit_device_decode", generation="decode.generate",
    outer=("generate", "decode.generate", "loadgen", "unattributed"))
ENGINE = Loop(
    layer="engine", round="engine.step",
    wake=("engine.device", "engine.sync"),
    launch=("engine.dispatch", "engine.upload", "engine.launch"),
    wait="engine.device", call="engine.launch", upload="engine.upload",
    program=r"jit_step")


def has_spans(red, loop: Loop) -> bool:
    """Whether the traced program names its launch (the parent does not)."""
    return bool(red) and bool(durations(red, loop.call))


def _inside(red, name: str) -> list[tuple[float, float]]:
    lo, hi = red.window
    return sorted((s, e) for n, s, e in red.spans
                  if n == name and s >= lo and e <= hi)


#: a program that began this long before the window's first launch was
#: launched before the window (a program cannot begin before its launch,
#: and no session's skew has read half of this): it is no launch's of
#: the window and is left out of the count
MATCH_SLACK_S = 3e-3


def _rounds(red, loop: Loop):
    """``(calls, waited)``: the window's launches ``(start, end)`` in
    order, and for some of their indices the end of the wait that read
    that launch's program.  Both loops read their programs in the order
    they launched them, so the k-th wait of a generation read its k-th
    launch; a generation that was stopped leaves its last launch unread,
    which is why the count starts anew with each (``loop.generation``;
    the engine discards no step, its window is one generation)."""
    calls, waits = _inside(red, loop.call), _inside(red, loop.wait)
    gens = _inside(red, loop.generation) if loop.generation else []
    waited: dict[int, float] = {}
    for g0, g1 in gens or [red.window]:
        mine = [i for i, (s, _e) in enumerate(calls) if g0 <= s <= g1]
        ends = [e for s, e in waits if g0 <= s <= g1]
        waited.update(zip(mine, ends))
    return calls, waited


def causality_bracket(red, loop: Loop):
    """``(lo, hi, rounds)``: the skew between the planes lies in
    ``[lo, hi]`` seconds (see the module's text), from ``rounds`` waits;
    ``None`` where the window holds no wait, no launch that found the
    chip idle, or no program.

    The k-th launch of the window is held against the k-th run of the
    loop's program on a chip, and a wait against the run its launch
    began (:func:`_rounds`), never against the run nearest in time: with
    a program queued ahead the nearest is the one before.  ``hi`` is
    taken only from launches that found the chip idle (their start lies
    in no program's run but, at most, their own): behind a running
    program the distance from launch to start is the rest of that
    program, no latency, and a bracket that wide would move the spans by
    half of it.  On several chips the host waits for one of them, not known
    here: the earliest end bounds it (the weakest ``lo`` is the valid
    one), and every chip's start follows the launch (the tightest
    ``hi``)."""
    calls, waited = _rounds(red, loop)
    if not calls or not waited:
        return None
    rx = re.compile(loop.program)
    los, his = [], []
    for dev in red.devices:
        runs = sorted((s, e) for n, s, e in dev.modules if rx.search(n)
                      and s >= calls[0][0] - MATCH_SLACK_S)
        # the chip's programs of any name, back to back ones as one
        stretches = union((s, e) for _n, s, e in dev.modules)
        heads = [s for s, _e in stretches]
        wake, begin = [], []
        for i, ((s, _e), (r0, r1)) in enumerate(zip(calls, runs)):
            j = bisect.bisect_right(heads, s)
            # idle: no program ran at ``s``, or none but the launch's
            # own, which a skew below 0 shows ahead of its launch
            if not j or stretches[j - 1][1] <= s \
                    or stretches[j - 1][0] >= r0 - SKEW_SLACK_S:
                begin.append(r0 - s)
            if i in waited:
                wake.append(waited[i] - r1)
        if not wake or not begin:
            return None
        los.append(-min(wake))
        his.append(min(begin))
    if not los:
        return None
    return min(los), min(his), len(waited)


def skew_shift(red, loop: Loop) -> float:
    """Seconds to add to the host spans before idle time is split: the
    bracket's midpoint (0 where there is no bracket, or one that
    contradicts itself: no skew explains it).

    The planes' offset moves from one profiler session to the next (PR
    36's traces of one cell: brackets of one width, 1.47 ms, around -1.2
    and around -0.35 ms), so leaving the spans where a bracket happens
    to hold 0 would move idle time between a round's wake and its
    launch from run to run.  The midpoint takes the least wake and the
    least launch latency for equal: wrong by half the bracket's width at
    most, and the same in every run."""
    br = causality_bracket(red, loop)
    if br is None or br[0] > br[1]:
        return 0.0
    return 0.5 * (br[0] + br[1])


#: reduction -> {layer: its split}: four readers and the note read one
_SPLITS: weakref.WeakKeyDictionary = weakref.WeakKeyDictionary()


def split(red, loop: Loop) -> dict[str, float]:
    """Idle seconds of the window by span, the mean over the chips."""
    done = _SPLITS.setdefault(red, {})
    if loop.layer not in done:
        done[loop.layer] = _split(red, loop)
    return done[loop.layer]


def aligned(red, loop: Loop):
    """``red`` with its host spans (the window's own aside) moved by
    :func:`skew_shift`: what the split, the pauses and the note read."""
    shift = skew_shift(red, loop)
    return types.SimpleNamespace(
        window=red.window, window_s=red.window_s,
        busy_by_device=red.busy_by_device,
        spans=[(n, s, e) if n == "window" else (n, s + shift, e + shift)
               for n, s, e in red.spans])


def _split(red, loop: Loop) -> dict[str, float]:
    red = aligned(red, loop)
    chips = len(red.busy_by_device)
    by: dict[str, float] = {}
    for d in range(chips):
        for what, sec in idle_split(red, n=1 << 30, device=d):
            by[what] = by.get(what, 0.0) + sec / chips
    return by


def per_round_ms(red, loop: Loop, names) -> float | None:
    """Idle milliseconds a round under the spans ``names``."""
    if not has_spans(red, loop):
        return None
    rounds = len(durations(red, loop.round))
    by = split(red, loop)
    return 1e3 * sum(by.get(n, 0.0) for n in names) / rounds


def upload_ms(red, loop: Loop) -> float | None:
    """Mean of the upload span, milliseconds."""
    ups = durations(red, loop.upload) if red else []
    return 1e3 * sum(ups) / len(ups) if ups else None


def pauses(red, loop: Loop) -> list[tuple[str, float, float, float]]:
    """``(phase span, its seconds, seconds over the window's median of
    that span, the share of it the chips were busy)`` for every pause
    marker inside the window: the phase is the layer's span that ended
    last before the marker began."""
    red = aligned(red, loop)
    mark = loop.layer + ".pause"
    mine = sorted((e, s, n) for n, s, e in red.spans
                  if n.startswith(loop.layer + ".") and n != mark)
    ends = [e for e, _s, _n in mine]
    out = []
    for m, _e in _inside(red, mark):
        i = bisect.bisect_right(ends, m + 1e-6)
        if not i:
            continue
        e, s, name = mine[i - 1]
        usual = durations(red, name)
        over = e - s - statistics.median(usual) if usual else 0.0
        busy = sum(total(clip(b, s, e)) for b in red.busy_by_device) \
            / (len(red.busy_by_device) * (e - s))
        out.append((name, e - s, max(over, 0.0), busy))
    return out


def pause_share(red, loop: Loop) -> float | None:
    """The program's own pauses inside the window over the window, %."""
    if not has_spans(red, loop) or not red.window_s:
        return None
    return 100.0 * sum(p[2] for p in pauses(red, loop)) / red.window_s


def unnamed(red, loop: Loop, n: int = 3, device: int = 0):
    """``(seconds, the phase that ended before it, the phase that began
    after it)`` for the ``n`` longest stretches of one chip's idle time
    that no phase of the loop covers: what a next span would name."""
    red = aligned(red, loop)
    lo, hi = red.window
    phases = sorted((s, e, name) for name, s, e in red.spans
                    if "." in name and name not in loop.outer)
    bare = gaps(union(list(red.busy_by_device[device])
                      + [(s, e) for s, e, _n in phases]), lo, hi)
    out = []
    for g0, g1 in sorted(bare, key=lambda g: g[0] - g[1])[:n]:
        before = max((p for p in phases if p[1] <= g0 + 1e-9),
                     key=lambda p: p[1], default=(0, 0, "-"))
        after = min((p for p in phases if p[0] >= g1 - 1e-9),
                    key=lambda p: (p[0], -p[1]), default=(0, 0, "-"))
        out.append((g1 - g0, before[2], after[2]))
    return out


def note(red, loop: Loop) -> None:
    """Print, as earlier lines of a traced run: the bracket, the idle
    time a round by phase, and each pause the window held."""
    def say(text):
        print("chipbench:", text, flush=True)

    br = causality_bracket(red, loop)
    if br is not None:
        shift = skew_shift(red, loop)
        say(f"planes {loop.layer}: skew in [{1e3 * br[0]:.4f}, "
            f"{1e3 * br[1]:.4f}] ms over {br[2]} rounds (wait end - "
            f"program end >= {-1e3 * br[0]:.4f}, program start - launch "
            f"start >= {1e3 * br[1]:.4f}): " + (
                "contradicts itself, nothing moved" if br[0] > br[1] else
                ("holds 0, the distances may be latencies"
                 if br[0] <= SKEW_SLACK_S and br[1] >= -SKEW_SLACK_S else
                 "does not hold 0, skew for certain")
                + f"; host spans moved to its middle, {1e3 * shift:.4f} ms"))
    rounds = len(durations(red, loop.round))
    by = split(red, loop)
    idle = sum(by.values())
    if not rounds or not idle:
        return
    named = {k: v for k, v in by.items() if k not in loop.outer
             and k != "between_ops_under_20us"}
    say(f"idle {loop.layer}: {1e3 * idle / rounds:.4f} ms a round over "
        f"{rounds} rounds, of the window {100 * idle / red.window_s:.3f}%; "
        f"outer spans keep "
        f"{100 * sum(by.get(k, 0.0) for k in loop.outer) / idle:.3f}% of "
        f"it; ms a round by phase " + ", ".join(
            f"{k} {1e3 * v / rounds:.4f}" for k, v in
            sorted(by.items(), key=lambda kv: -kv[1])))
    say(f"idle {loop.layer}: named phases "
        f"{100 * sum(named.values()) / idle:.3f}% of the idle time")
    say(f"idle {loop.layer}: longest stretches under no phase: " + ", ".join(
        f"{1e3 * d:.3f} ms between {a} and {b}"
        for d, a, b in unnamed(red, loop)))
    for name, dur, over, busy in pauses(red, loop):
        say(f"pause {name} {1e3 * dur:.3f} ms, {1e3 * over:.3f} over the "
            f"window's median, the chips busy {100 * busy:.1f}% of it")
