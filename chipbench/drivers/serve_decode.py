"""Driver ``serve_decode``: open-loop chat traffic through the front door.

``ServeFrontDoor(engine=ContinuousBatchEngine(...))`` runs in this
process; one client thread plays a Poisson arrival trace over loopback,
one connection a request, and never waits for an answer before sending
the next.  A request's time runs from when it was *due*
to be sent until its answer is in host memory; how late the generator
ran is reported beside it.  Lengths come from the traffic file's fixed
table and arrival times from one fixed draw; a seed changes the contents.

Traffic file keys: ``width``, ``max_len``, ``params_dtype``,
``rate_hz``, ``arrival_seed``, ``lengths`` ([prompt, answer] pairs),
``warm_requests``, ``check_requests``, ``drain_timeout_s``, ``trace_seconds``.
Configuration file keys: ``model_args``, ``reference``.
"""

from __future__ import annotations

import dataclasses
import selectors
import time

import numpy as np

from chipbench import readings as rd
from chipbench.agreement import logit_gaps
from chipbench.arrivals import arrival_times
from chipbench.weights import init_on_device

#: as ``batch_decode.GAP_TOL``, for float32 weights multiplied at the
#: TPU's default precision (bfloat16 passes): the same rounding, so the
#: same bound
GAP_TOL = 0.03

_ENGINE_PHASES = ("gather", "dispatch", "device", "sync", "delivery")


@dataclasses.dataclass
class Request:
    due_s: float
    prompt: np.ndarray
    answer_len: int
    sent_s: float | None = None
    done_s: float | None = None
    answer: np.ndarray | None = None
    failed: str | None = None


def make_requests(traffic: dict, vocab: int, seed: int,
                  seconds: float) -> list[Request]:
    """The window's requests: ``round(rate x seconds)`` of them, at the
    arrival times of a Poisson process that had that many arrivals in the
    window (``chipbench/arrivals.py``), with lengths from the table in a
    cyclic order.  Times and order are one draw fixed by the traffic
    file's ``arrival_seed`` — a replayed trace; the run's seed makes the
    prompts' contents (and the weights).  At four fifths of the knee the
    tail depends on which lengths meet in which burst: six seeded
    schedules read p90 from 124 to 133 ms/token on the chip, each
    repeating to 1%, so a schedule a seed would be six benchmarks."""
    fixed = np.random.default_rng(traffic["arrival_seed"])
    table = traffic["lengths"]
    order = fixed.permutation(len(table))
    n = max(1, round(traffic["rate_hz"] * seconds))
    dues = arrival_times(n, seconds, fixed)
    rng = np.random.default_rng(seed)
    out = []
    for i, due in enumerate(dues):
        plen, alen = table[order[i % len(table)]]
        out.append(Request(float(due), rng.integers(0, vocab, (plen,)
                                                    ).astype(np.int32), alen))
    return out


def play(address, requests: list[Request], *, tenant: str,
         drain_timeout_s: float) -> float:
    """Send every request at its due time, open loop, from this one
    thread; fills in ``sent_s``/``done_s``/``answer``/``failed`` (seconds
    from the start of play).  Returns the seconds play lasted."""
    from defer_tpu.transport.framed import (K_CTRL, K_END, K_TENSOR_SEQ,
                                            connect_retry, recv_frame,
                                            send_ctrl, send_end, send_frame)
    host, port = address
    sel = selectors.DefaultSelector()
    t0 = time.perf_counter()
    last_due = requests[-1].due_s if requests else 0.0
    i, live = 0, 0

    def finish(sock, req, why=None):
        nonlocal live
        if why and req.done_s is None:
            req.failed = why
        sel.unregister(sock)
        sock.close()
        live -= 1

    try:
        while i < len(requests) or live:
            now = time.perf_counter() - t0
            if i < len(requests) and now >= requests[i].due_s:
                req = requests[i]
                i += 1
                try:
                    sock = connect_retry(host, port, 30.0)
                    send_ctrl(sock, {"cmd": "hello", "tenant": tenant,
                                     "max_new_tokens": req.answer_len})
                    kind, msg = recv_frame(sock)
                    if kind != K_CTRL or msg.get("cmd") != "welcome":
                        raise ConnectionError(f"no welcome: {msg!r}")
                    send_frame(sock, req.prompt)
                    send_end(sock)
                except OSError as e:
                    req.failed = f"send: {e}"
                    continue
                req.sent_s = time.perf_counter() - t0
                sel.register(sock, selectors.EVENT_READ, req)
                live += 1
                continue
            if now > last_due + drain_timeout_s:
                for key in list(sel.get_map().values()):
                    finish(key.fileobj, key.data, "no answer in time")
                break
            wait = requests[i].due_s - now if i < len(requests) else 0.25
            for key, _ in sel.select(timeout=max(0.0, min(wait, 0.25))):
                sock, req = key.fileobj, key.data
                try:
                    kind, value = recv_frame(sock)
                except (OSError, ValueError) as e:
                    finish(sock, req, f"recv: {e}")
                    continue
                if kind == K_TENSOR_SEQ:
                    req.done_s = time.perf_counter() - t0
                    req.answer = np.asarray(value[1])
                elif kind == K_CTRL and value.get("cmd") == "shed":
                    req.failed = f"shed: {value.get('reason')}"
                elif kind == K_END:
                    finish(sock, req, "ended without an answer")
                else:
                    finish(sock, req, f"unexpected frame kind {kind}")
    finally:
        for key in list(sel.get_map().values()):
            key.fileobj.close()
        sel.close()
    return time.perf_counter() - t0


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import models
    from defer_tpu.serve.engine import ContinuousBatchEngine
    from defer_tpu.serve.frontdoor import ServeFrontDoor

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    graph = models.gpt(**cfg["model_args"])
    with ctx.span("weights"):
        params = init_on_device(graph, ctx.seed,
                                jnp.dtype(tr["params_dtype"]))
    with ctx.span("build"):
        engine = ContinuousBatchEngine(graph, params, num_stages=1,
                                       width=tr["width"],
                                       max_len=tr["max_len"])
        door = ServeFrontDoor(listen="127.0.0.1:0", engine=engine)
        door.start()
    state = {"params": params, "door": door, "traffic": tr, "config": cfg,
             "vocab": cfg["model_args"]["vocab"]}
    with ctx.span("warmup"):
        # the one step program serves every batch composition; a few
        # short requests compile it and fill the batch once
        warm = make_requests(
            dict(tr, lengths=[[4, 4]], rate_hz=float(tr["warm_requests"])),
            state["vocab"], ctx.seed + 1, 1.0)
        play(door.address, warm, tenant="warm", drain_timeout_s=600.0)
        bad = [r.failed for r in warm if r.answer is None]
        if bad:
            raise RuntimeError(f"warm-up requests failed: {bad}")
    return state


def _engine_counters() -> dict:
    from defer_tpu.obs import REGISTRY
    out = {}
    for name in ("step",) + _ENGINE_PHASES:
        h = REGISTRY.histogram(f"serve.decode.{name}_s")
        out[f"{name}_s_sum"] = float(h.sum)
        out[f"{name}_count"] = int(h.count)
    # slots the launched steps' cache kernels visited (serve_visited_share)
    out["rows_launched"] = int(
        REGISTRY.counter("serve.decode.rows.launched").n)
    return out


def measure(state, seconds, ctx):
    door, tr = state["door"], state["traffic"]
    reqs = make_requests(tr, state["vocab"], ctx.seed, seconds)
    before = _engine_counters()
    with ctx.span("loadgen"):
        wall = play(door.address, reqs, tenant="bench",
                    drain_timeout_s=tr["drain_timeout_s"])
    door.healthcheck()
    after = _engine_counters()
    state["served"] = reqs
    ok = [r for r in reqs if r.answer is not None]
    failed = len(reqs) - len(ok)
    # a request that failed or was shed misses every limit: it stands in
    # the tail with the whole of the run as its time
    per_token = [1e3 * ((r.done_s if r.answer is not None else wall)
                        - r.due_s) / r.answer_len for r in reqs]
    late = [1e3 * (r.sent_s - r.due_s) for r in reqs if r.sent_s is not None]
    if not ctx.trace:
        rd.require_readings(per_token)
    tokens = sum(r.answer_len for r in ok)
    adm = door.attrib.summary().get("bench", {}).get("admission", {})
    counters = {k: after[k] - before[k] for k in after}
    counters.update(
        admission_wait_ms_mean=adm.get("mean"),
        late_ms_p95=rd.quantile(late, 0.95) if late else None,
        # a prompt goes through the prefill call and takes no step
        rows=float(sum(r.answer_len for r in ok))
        / max(counters["step_count"], 1),
        width=tr["width"],
        live_positions=float(np.mean(
            [(r.prompt.size + r.answer_len) / 2 for r in reqs])),
        model_args=state["config"]["model_args"],
        weight_bytes=int(np.dtype(tr["params_dtype"]).itemsize),
        kv_bytes=4)
    return {
        "end_to_end": {} if not per_token else {
            "answer_ms_per_token_p50": rd.quantile(per_token, 0.5),
            "answer_ms_per_token_p90": rd.quantile(per_token, 0.9)},
        "attempted": len(reqs), "failed": failed, "readings": per_token,
        "notes": [f"loadgen late_ms p50 {rd.quantile(late, 0.5):.3f} p95 "
                  f"{rd.quantile(late, 0.95):.3f} max {max(late):.3f}; "
                  f"engine steps {counters['step_count']}, mean rows a "
                  f"step {counters['rows']:.2f} of {tr['width']}"]
        if late else [],
        "work_over_wall": {"requests": len(ok), "tokens": tokens,
                           "wall_s": wall, "tokens_per_s": tokens / wall,
                           "offered_hz": len(reqs) / max(seconds, 1e-9)},
        "counters": counters,
    }


def check(state, ctx):
    """Served answers equal to the same requests run alone through the
    same door, and their tokens against the plain reference's logits."""
    tr, door = state["traffic"], state["door"]
    served = [r for r in state.get("served", []) if r.answer is not None]
    if not served:
        return False, {"error": "no request was answered"}
    n = min(tr["check_requests"], len(served))
    picks = [served[(len(served) * k) // n] for k in range(n)]
    worst, exact = 0.0, []
    for r in picks:
        alone = Request(0.0, r.prompt, r.answer_len)
        play(door.address, [alone], tenant="check", drain_timeout_s=600.0)
        if alone.answer is None or not np.array_equal(alone.answer, r.answer):
            return False, {"error": "a served answer differs from the same "
                                    "request run alone",
                           "alone_failed": alone.failed}
        if not np.array_equal(r.answer[:r.prompt.size], r.prompt) \
                or r.answer.size != r.prompt.size + r.answer_len:
            return False, {"error": "answer does not echo prompt + tokens"}
        gaps = logit_gaps(state["params"], r.answer[None], r.prompt.size,
                          state["config"]["reference"])
        worst = max(worst, float(gaps.max()))
        exact.append(float((gaps <= 0).mean()))
    return worst <= GAP_TOL, {
        "served_equal_alone": n, "worst_logit_gap_share": worst,
        "tolerance": GAP_TOL, "exact_argmax_share": float(np.mean(exact))}


def close(state):
    door = state.get("door")
    if door is not None:
        door.stop()
    state.clear()

