"""Where a served request's time goes, request by request: one untraced
window of the serving cell through its own driver
(``chipbench/drivers/serve_decode.py``, imported through the manifest),
then the window's ``decode_done`` events laid out — what the four readers
over them reduce to one number each (PERF.md section 3) and, what a traced
run's 8 s hold too few records for, the tail.  Chip only.

    python scripts/serve_request_table.py [SEED [SECONDS [OUT.json]]]

Prints, for the window's tenant: the cell's two end-to-end tails as the
client saw them; the four per-layer metrics; the door's decode buckets
(p50 and mean, and their means' sum beside ``e2e``'s) with the stamps'
order checked on every record; the rounds (``step_s`` beside
``pass_round_s``: how many held a join's pass, and what those cost); the
pauses the watch named; and two tables of ``new_tokens``, ``prompt``,
``forced_steps``, ``first_ms``, ``pass_rounds``, ``worst_gap_ms``,
``delivered_ms`` and ``delivered_ms`` / ``new_tokens`` — the requests
beyond the p90 of that last column, and as many around its median — each
with the share of its requests' time that is not token gaps.  ``OUT.json``
keeps every record.
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])
from chipbench.readings import quantile  # noqa: E402

CELL = "gpt2xl_chat_serve"
STAMPS = ("popped_ms", "prefill_ms", "first_ms", "last_ms", "delivered_ms")
METRICS = ("engine_first_token_ms", "engine_token_gap_ms",
           "engine_worst_gap_ms", "door_result_edge_ms")
COLUMNS = ("new_tokens", "prompt", "forced_steps", "first_ms",
           "pass_rounds", "worst_gap_ms", "delivered_ms")


def per_token_ms(ev: dict) -> float:
    """Admitted to answered over the answer's length: what the cell's
    end-to-end metrics read from the client's side, less the
    connection."""
    return ev["delivered_ms"] / ev["new_tokens"]


def fixed_share(ev: dict) -> float:
    """How much of a request's time is not token gaps, in percent:
    admission, join, the way to the first token and the result edge over
    the whole."""
    return 100.0 * (1.0 - (ev["last_ms"] - ev["first_ms"])
                    / ev["delivered_ms"])


def _hists(names) -> dict:
    from defer_tpu.obs import REGISTRY
    out = {}
    for name in names:
        h = REGISTRY.histogram(f"serve.decode.{name}_s")
        out[name] = (h.count, h.sum)
    return out


def _table(title: str, rows: list) -> None:
    print(f"-- {title} ({len(rows)} requests)")
    print(" ".join(f"{c:>12}" for c in COLUMNS + ("ms_per_token",)))
    for ev in sorted(rows, key=per_token_ms):
        print(" ".join(f"{ev[c]:>12.3f}" if isinstance(ev[c], float)
                       else f"{ev[c]:>12}" for c in COLUMNS)
              + f" {per_token_ms(ev):>12.3f}")
    med = {c: quantile([ev[c] for ev in rows], 0.5) for c in COLUMNS}
    print(" ".join(f"{med[c]:>12.3f}" for c in COLUMNS)
          + f" {quantile([per_token_ms(e) for e in rows], 0.5):>12.3f}   (medians)")
    print(f"not token gaps: {quantile([fixed_share(e) for e in rows], 0.5):.2f}% "
          f"of a request's time at the median of these")


def run(seed: int, seconds: float, out_path: str | None = None) -> int:
    import jax
    # as the harness: every program into the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import defer_tpu  # noqa: F401 — sets the compile cache directory
    from chipbench import request_events
    from chipbench.harness import Context
    from chipbench.manifest import Manifest
    from defer_tpu.obs.attrib import DECODE_BUCKETS
    from defer_tpu.obs.events import recorder

    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        raise SystemExit(f"serve_request_table: no TPU (jax found "
                         f"{d0.platform}): a request's times come from "
                         f"the chip alone")
    manifest = Manifest()
    cell = manifest.cell(CELL)
    driver = manifest.driver(cell)
    ctx = Context(cell=cell, seed=seed, devices=jax.devices()[:cell.chips],
                  trace=False, peaks=None)
    t0 = time.perf_counter()
    state = driver.setup(ctx)
    print(f"set-up {time.perf_counter() - t0:.1f} s; window {seconds} s, "
          f"seed {seed}, {d0.device_kind}")
    names = ("step", "pass_round")
    before, seen = _hists(names), recorder().cursor()
    measured = driver.measure(state, seconds, ctx)
    after = _hists(names)
    buckets = state["door"].attrib.summary().get(
        request_events.WINDOW_TENANT, {})
    pauses = [e["data"] for e in recorder().events_since(seen)[1]
              if e["kind"] == "host_pause"]
    done = request_events.finished()
    driver.close(state)
    if not done:
        raise SystemExit("serve_request_table: no decode_done event in "
                         f"the window (events dropped: "
                         f"{recorder().dropped})")

    print("end to end (client's side): " + json.dumps(
        measured["end_to_end"]) + f" attempted {measured['attempted']} "
        f"failed {measured['failed']}")
    metrics = {name: manifest.reader(name).read(None) for name in METRICS}
    print("metrics: " + json.dumps(metrics))

    # every record's stamps in order; the buckets tile e2e
    disorder = [ev["rid"] for ev in done
                if not 0 <= ev[STAMPS[0]] or any(
                    ev[a] > ev[b] for a, b in zip(STAMPS, STAMPS[1:]))]
    print(f"records {len(done)}, stamps out of order in {len(disorder)}"
          + (f": rids {disorder}" if disorder else ""))
    print("-- the door's decode buckets, ms")
    print(f"{'bucket':>12} {'p50':>10} {'mean':>10} {'p99':>10} {'count':>6}")
    for name in DECODE_BUCKETS + ("e2e",):
        b = buckets.get(name, {})
        print(f"{name:>12} {b.get('p50', 0):>10.4f} {b.get('mean', 0):>10.4f}"
              f" {b.get('p99', 0):>10.4f} {b.get('count', 0):>6}")
    total = sum(buckets.get(n, {}).get("sum", 0.0) for n in DECODE_BUCKETS)
    e2e = buckets.get("e2e", {})
    if e2e.get("count"):
        print(f"buckets' means sum to {total / e2e['count']:.6f} ms, e2e's "
              f"mean {e2e['mean']:.6f} ms: "
              f"{1e3 * (total - e2e['sum']) / e2e['count']:+.4f} us a request")

    rounds = {n: (after[n][0] - before[n][0], after[n][1] - before[n][1])
              for n in names}
    steps, step_s = rounds["step"]
    held, held_s = rounds["pass_round"]
    plain = (step_s - held_s) / max(steps - held, 1)
    print(f"rounds {steps}, mean {1e3 * step_s / max(steps, 1):.4f} ms; "
          f"{held} behind a pass ({100.0 * held / max(steps, 1):.2f}%), "
          f"mean {1e3 * held_s / max(held, 1):.4f} ms; the others' mean "
          f"{1e3 * plain:.4f} ms")
    print(f"host_pause events in the window: {len(pauses)}")
    for p in pauses:
        print("  " + " ".join(f"{k}={p[k]}" for k in (
            "layer", "phase", "round", "passes", "wall_ms", "typical_ms",
            "cpu_ms") if k in p))

    by = sorted(done, key=per_token_ms)
    cut = quantile([per_token_ms(e) for e in done], 0.9)
    tail = [e for e in by if per_token_ms(e) > cut]
    lo = max(0, (len(by) - len(tail)) // 2)
    _table("beyond the p90 of delivered_ms / new_tokens", tail)
    _table("around its median", by[lo:lo + len(tail)])
    whole = {c: quantile([ev[c] for ev in done], 0.5) for c in COLUMNS}
    print("whole window medians: " + json.dumps(whole))
    if out_path:
        with open(out_path, "w") as f:
            json.dump({"seed": seed, "seconds": seconds,
                       "end_to_end": measured["end_to_end"],
                       "metrics": metrics, "buckets": buckets,
                       "rounds": rounds, "pauses": pauses,
                       "records": done}, f, indent=1)
    return 0


def main(argv) -> int:
    return run(int(argv[0]) if argv else 20261005,
               float(argv[1]) if len(argv) > 1 else 40.0,
               argv[2] if len(argv) > 2 else None)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
