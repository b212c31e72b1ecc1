"""Failure detection: health checks, error propagation, watchdog.

The reference's only failure behavior is a forever-hang (SURVEY.md §5: no
retry, no health check; a dead node stalls the chain).  These tests pin the
opposite contract: failures surface as errors, readers are unblocked, and a
deployment can be probed before serving.
"""

import queue
import time

import numpy as np
import pytest

import jax

from defer_tpu import Defer, DeferConfig, END_OF_STREAM
from defer_tpu.models import resnet_tiny


@pytest.fixture(scope="module")
def tiny():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


def test_health_check_ok(tiny):
    g, p = tiny
    rep = Defer(config=DeferConfig(microbatch=1, chunk=2)).health_check(
        g, p, num_stages=4)
    assert rep["ok"] and rep["stages"] == 4
    assert rep["mesh"] == {"data": 1, "stage": 4}
    assert rep["error"] is None


def test_health_check_reports_failure(tiny):
    g, _ = tiny
    # missing parameters: every stage program fails at trace time — the
    # "bad deployment caught before serving" case
    rep = Defer(config=DeferConfig(microbatch=1, chunk=2)).health_check(
        g, {}, num_stages=1)
    assert not rep["ok"]
    assert rep["error"] is not None


def test_run_defer_propagates_stage_error(tiny):
    g, p = tiny
    in_q, out_q = queue.Queue(), queue.Queue()
    h = Defer(config=DeferConfig(microbatch=1, chunk=2)).run_defer(
        g, p, None, in_q, out_q, num_stages=2)
    # wrong input shape: the dispatch raises inside the serve thread
    in_q.put(np.zeros((1, 7), np.float32))
    # reader is unblocked by the sentinel instead of hanging forever
    assert out_q.get(timeout=120) is END_OF_STREAM
    assert not h.healthy
    with pytest.raises(RuntimeError, match="dispatcher thread failed"):
        h.join(timeout=60)


def test_watchdog_declares_hung_dispatch(tiny, monkeypatch):
    g, p = tiny
    # detection-only mode (max_recoveries=0): first fire is fatal
    defer = Defer(config=DeferConfig(microbatch=1, chunk=2,
                                     watchdog_s=0.5, max_recoveries=0))
    in_q, out_q = queue.Queue(), queue.Queue()
    h = defer.run_defer(g, p, None, in_q, out_q, num_stages=2)
    # simulate a wedged device dispatch AFTER the
    # compile warmup: the serve thread reports busy and never finishes
    h._dispatches = 1
    h._busy_since = time.monotonic() - 10.0
    assert out_q.get(timeout=30) is END_OF_STREAM
    assert isinstance(h.error, TimeoutError)
    assert not h.healthy
    h.stop()


def test_failure_detection_defaults_on():
    """r1 shipped watchdog_s=None — the reference's forever-hang as the
    default config.  Pin the new contract: detection on out of the box."""
    cfg = DeferConfig()
    assert cfg.watchdog_s == 60.0
    assert cfg.preflight is True
    assert cfg.max_recoveries == 1  # recovery, not just detection (r5)


def test_watchdog_recovery_replays_unemitted(tiny):
    """VERDICT r4 #7: poison a dispatch mid-stream; the watchdog rebuilds
    the pipeline (fresh jit, same weights), replays the fed-but-unemitted
    microbatches from the resubmit log, and the output queue completes
    with no gaps — in order, matching the single-program oracle."""
    import threading

    g, p = tiny
    defer = Defer(config=DeferConfig(microbatch=1, chunk=2, watchdog_s=0.5,
                                     gather_timeout_s=0.01))
    in_q, out_q = queue.Queue(), queue.Queue()
    h = defer.run_defer(g, p, None, in_q, out_q, num_stages=2)

    # poison the FIRST pipeline instance: its 3rd push (warmup is #1)
    # wedges forever — the simulated dead-device dispatch
    first_pipe = h.pipeline
    real_push = first_pipe.push
    wedge = threading.Event()
    calls = {"n": 0}

    def poisoned(xs, n_real=None, **kw):
        calls["n"] += 1
        if calls["n"] == 3:
            wedge.wait()  # never set: this generation is stuck for good
        return real_push(xs, n_real=n_real, **kw)

    first_pipe.push = poisoned

    rng = np.random.default_rng(7)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(8)]
    for x in xs:
        in_q.put(x)
    in_q.put(END_OF_STREAM)

    outs = []
    while len(outs) < 8:
        o = out_q.get(timeout=180)
        assert o is not END_OF_STREAM, \
            f"stream aborted after {len(outs)} outputs (error: {h.error!r})"
        outs.append(o)
    assert h.healthy
    assert h.recoveries == 1
    assert h.pipeline is not first_pipe  # fresh engine, same weights
    fwd = jax.jit(g.apply)
    for x, y in zip(xs, outs):  # no gaps, original feed order
        np.testing.assert_allclose(y, np.asarray(fwd(p, x)),
                                   rtol=2e-4, atol=2e-4)
    h.stop()
    wedge.set()  # let the abandoned generation's thread exit


def test_watchdog_recovery_after_end_consumed(tiny):
    """A wedge in the final-drain dispatch — AFTER the caller's
    END_OF_STREAM was consumed — must still recover: the new generation
    must not wait for a second END (none is coming); it replays, flushes,
    and completes the stream."""
    import threading

    g, p = tiny
    defer = Defer(config=DeferConfig(microbatch=1, chunk=2, watchdog_s=0.5,
                                     gather_timeout_s=0.01))
    in_q, out_q = queue.Queue(), queue.Queue()
    h = defer.run_defer(g, p, None, in_q, out_q, num_stages=2)

    first_pipe = h.pipeline
    real_push = first_pipe.push
    wedge = threading.Event()
    seen = {"real": 0, "wedged": False}

    def poisoned(xs, n_real=None, **kw):
        # wedge the first all-bubble push that FOLLOWS real input: the
        # final drain.  (Not "the 4th call": the warm-up push — also all
        # bubbles — may run before or after this patch lands, depending
        # on how fast the compile was.)
        if n_real is None or n_real > 0:
            seen["real"] += 1
        elif seen["real"] and not seen["wedged"]:
            seen["wedged"] = True
            wedge.wait()
        return real_push(xs, n_real=n_real, **kw)

    first_pipe.push = poisoned

    rng = np.random.default_rng(13)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(4)]
    for x in xs:
        in_q.put(x)
    in_q.put(END_OF_STREAM)

    outs = []
    while len(outs) < 4:
        o = out_q.get(timeout=180)
        assert o is not END_OF_STREAM, \
            f"aborted after {len(outs)} (error: {h.error!r})"
        outs.append(o)
    assert h.healthy and h.recoveries == 1
    fwd = jax.jit(g.apply)
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(y, np.asarray(fwd(p, x)),
                                   rtol=2e-4, atol=2e-4)
    h.stop()
    wedge.set()


def test_join_raises_immediately_when_error_set():
    """join() must re-raise a recorded error even while the serve thread is
    permanently wedged in a dead dispatch (it polls, never blocks forever)."""
    import threading
    from defer_tpu.runtime.dispatcher import DeferHandle

    release = threading.Event()
    th = threading.Thread(target=release.wait, daemon=True)
    th.start()
    h = DeferHandle(th, None, threading.Event())
    h.error = TimeoutError("deployment declared dead")
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="dispatcher thread failed"):
        h.join()  # unbounded join would hang here before the fix
    assert time.monotonic() - t0 < 5
    release.set()


def test_preflight_surfaces_compile_failure_without_input(tiny):
    """With preflight on, a deployment that cannot compile reports its error
    and unblocks readers before any input is ever enqueued."""
    g, p = tiny
    # structurally valid params with broken shapes: building the pipeline
    # succeeds, but the stage programs fail at trace time — exactly the
    # failure class preflight exists to catch before traffic
    bad = jax.tree.map(
        lambda a: np.zeros(np.shape(a)[:-1] + (np.shape(a)[-1] + 1,),
                           np.float32) if np.ndim(a) else a, p)
    in_q, out_q = queue.Queue(), queue.Queue()
    h = Defer(config=DeferConfig(microbatch=1, chunk=2)).run_defer(
        g, bad, None, in_q, out_q, num_stages=2)
    assert out_q.get(timeout=120) is END_OF_STREAM
    assert not h.healthy
