"""Run one cell once and print the contract's result line.

    python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Order of a run: check the device, build and warm the cell (``setup_s``
ends here), measure for ``--seconds`` (untraced) or for the traffic
file's ``trace_seconds`` under the profiler (traced), then — outside
the window — compare the program's outputs with the plain reference.
Earlier lines of stdout carry the readings and observations; the last
line is the one JSON object the contract names.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import time

from .manifest import Manifest
from .readings import TooFewReadings, describe
from .trace import SPAN_PREFIX

#: jax.monitoring duration events that mean "a program was built"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def say(*parts) -> None:
    print("chipbench:", *parts, flush=True)


class CompileCounter:
    """Counts XLA backend compiles (cache loads included) by phase."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.counts: dict[str, int] = {}
        self.seconds: dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.counts[self.phase] = self.counts.get(self.phase, 0) + 1
            self.seconds[self.phase] = \
                self.seconds.get(self.phase, 0.0) + duration


class Context:
    """What a driver and a metric reader get from the harness."""

    def __init__(self, *, cell, seed: int, devices, trace: bool, peaks):
        self.cell = cell
        self.seed = seed
        self.devices = devices        #: the chips this cell computes on
        self.trace = trace
        self.peaks = peaks            #: peaks.json row, None off the chip
        self.say = say

    def span(self, name: str):
        """A harness span around a call into a layer: a
        ``TraceAnnotation`` named ``chipbench:<name>`` in the profiler's
        trace, where the reduction reads it on the device events' own
        clock (outside a traced run it costs nothing)."""
        import jax
        return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class Run:
    """Everything a per-layer metric reader may read."""

    def __init__(self, ctx: Context, measured: dict, trace):
        self.cell = ctx.cell
        self.peaks = ctx.peaks
        self.trace = trace                       #: TraceReduction or None
        self.counters = measured.get("counters", {})
        self.readings = measured.get("readings", [])


def _device_doc(devices) -> dict:
    d0 = devices[0]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def _profiler_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # no per-call Python events
    opts.host_tracer_level = 2        # TraceAnnotations (the spans)
    opts.enable_hlo_proto = False
    return opts


def run_cell(*, workload: str, seed: int, seconds: float, trace: bool,
             t_start: float, root: str | None = None,
             require_tpu: bool = True) -> dict:
    """Run one cell; returns the result document (the last line's JSON).

    ``require_tpu=False`` exists for the CPU tests of this package only:
    the command line never passes it, so a run without a TPU fails."""
    manifest = Manifest(root)
    cell = manifest.cell(workload)
    driver = manifest.driver(cell)

    import jax
    # cache every program, the sub-second ones too: a second run of a
    # cell must find all of them (the directory itself is set by
    # defer_tpu/utils/compile_cache.py when the package is imported)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import defer_tpu  # noqa: F401 — sets the compile cache directory

    devices = jax.devices()
    d0 = devices[0]
    say(f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"count={len(devices)} cell={cell.name} chips={cell.chips} "
        f"seed={seed} seconds={seconds} trace={int(trace)}")
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"chipbench: no TPU: jax found {len(devices)} x "
                         f"{d0.platform}; this benchmark never falls back")
    if len(devices) < cell.chips:
        raise SystemExit(f"chipbench: cell {cell.name} needs {cell.chips} "
                         f"chips, jax found {len(devices)}")
    peaks = None
    if d0.platform == "tpu":
        from .roofline import peaks_for
        peaks = peaks_for(d0.device_kind)   # unknown kind: an error
    ctx = Context(cell=cell, seed=seed, devices=devices[:cell.chips],
                  trace=trace, peaks=peaks)
    compiles = CompileCounter()

    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    say(f"setup_s={setup_s:.3f} compiles_or_cache_loads="
        f"{compiles.counts.get('setup', 0)} "
        f"compile_s={compiles.seconds.get('setup', 0.0):.2f}")

    window_s = float(cell.traffic["trace_seconds"] if trace else seconds)
    reduction, enough = None, True
    compiles.phase = "window"
    try:
        if trace:
            trace_dir = os.path.join(manifest.root, ".chipbench_trace",
                                     f"{cell.name}.{os.getpid()}")
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_profiler_options())
            try:
                with ctx.span("window"):
                    measured = driver.measure(state, window_s, ctx)
            finally:
                jax.profiler.stop_trace()
            from .trace import reduce_trace
            reduction = reduce_trace(trace_dir, n_devices=cell.chips,
                                     host_ops_as_device=not require_tpu)
            shutil.rmtree(trace_dir, ignore_errors=True)
            say("idle share by chip " + json.dumps(
                reduction.idle_share_by_device()))
        else:
            measured = driver.measure(state, window_s, ctx)
    except TooFewReadings as e:
        say(f"FAIL: {e}")
        measured, enough = {}, False
    compiles.phase = "after"
    in_window = compiles.counts.get("window", 0)
    # the peak of set-up and window: the check below holds the plain
    # reference's own buffers, which are not the cell's
    device = _device_doc(ctx.devices)

    readings = measured.get("readings", [])
    say("readings " + json.dumps(describe(readings)) + " work_over_wall "
        + json.dumps(measured.get("work_over_wall")))
    for note in measured.get("notes", []):
        say(note)

    correct, detail = driver.check(state, ctx)
    say("check " + json.dumps(detail))
    if in_window:
        say(f"FAIL: {in_window} program(s) were compiled or loaded inside "
            f"the window; warm-up must cover every shape the window uses")
        correct = False
    correct = correct and enough
    driver.close(state)

    metrics: dict = {}
    if trace:
        run = Run(ctx, measured, reduction)
        for name in cell.per_layer:
            value = manifest.reader(name).read(run)
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": manifest.metric(name)["unit"]}
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s
    else:
        values = dict(measured.get("end_to_end", {}), setup_s=setup_s)
        for name in cell.end_to_end:
            if name in values:
                metrics[name] = {"value": float(values[name]),
                                 "unit": manifest.metric(name)["unit"]}
    doc = {"correct": bool(correct),
           "attempted": int(measured.get("attempted", 0)),
           "failed": int(measured.get("failed", 0)),
           "metrics": metrics, "device": device}
    if trace:
        doc["breakdown"] = reduction.breakdown()
    return doc


def main(argv, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="python -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    doc = run_cell(workload=args.workload, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   t_start=t_start)
    print(json.dumps(doc), flush=True)
    return 0
