"""Decode steady-state X-ray: zero recompiles, accounted dispatches.

The mb64 bf16 decode cliff (docs/DECODE_CLIFF.md, DECODE_r05.json:
560 ms/token-step against 26 ms for the int8-KV variant of the SAME
shapes, with a 96.8 s first call) was a device-side pathology (the
compiler's whole-stack copies around the stacked cache's write-back,
cured in PR 25), so the guard this smoke pins down is the mechanism a
cliff would have to break through on the host side:

1. ZERO STEADY-STATE RECOMPILES: after one warmup ``generate``, a
   second ``generate`` with identical arguments must reach XLA ZERO
   times (the decode program cache is keyed by
   ``(chunk_steps, sample, top_k)`` — ``runtime/decode.py``), measured
   by the ``jax.monitoring`` compile listener, and must emit no
   ``recompile`` flight-recorder event while armed.
2. ACCOUNTED DISPATCHES: the steady-state run performs EXACTLY
   ``ceil(num_steps / chunk_steps)`` scan dispatches (the count of
   the ``decode.dispatch_s`` histogram) — no hidden per-token host round
   trips — and the summed ``decode.dispatch_s`` stays a sane share of
   the generation wall (<= ~1: dispatch cannot exceed the wall it is
   part of).

Shapes are the CPU-smoke geometry of ``scripts/bench_decode.py``
(gpt 4L / d=64 / 2 heads / vocab 128, mb=4, 16 new tokens,
token_chunk=32), so this is the same program family the TPU bench
drives — only the backend differs.  Exit 0 on success; one JSON row on
stdout (the ``decode_profile`` row of ``benchmarks/run.py``).
"""

import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="accepted for CI symmetry; the smoke is "
                         "already the small CPU geometry")
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--microbatch", type=int, default=4)
    ap.add_argument("--token-chunk", type=int, default=32)
    ap.add_argument("--max-dispatch-share", type=float, default=1.02,
                    help="summed dispatch seconds / generation wall "
                         "upper bound (dispatch is part of the wall; "
                         "> 1 means double counting)")
    args = ap.parse_args()

    import numpy as np

    import jax

    from defer_tpu.models import gpt
    from defer_tpu.obs import recompile_watcher, recorder
    from defer_tpu.obs.registry import REGISTRY
    from defer_tpu.runtime.decode import PipelinedDecoder

    layers, d, heads, vocab = 4, 64, 2, 128
    max_len, plen = 48, 8
    mb, new = args.microbatch, args.new_tokens

    graph = gpt(layers, d, heads, max_len, vocab=vocab)
    params = graph.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, vocab, size=(mb, plen)).astype(np.int32)

    watcher = recompile_watcher()
    watcher.install()
    watcher.disarm()
    rec = recorder()

    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=mb,
                           max_len=max_len)
    kw = dict(max_new_tokens=new, token_chunk=args.token_chunk)

    t0 = time.perf_counter()
    toks = dec.generate(prompt, **kw)           # compile + run
    first_call_s = time.perf_counter() - t0
    assert toks.shape == (mb, plen + new), toks.shape
    c_warm = watcher.count
    assert c_warm > 0, (
        "warmup generate reached XLA zero times — the compile "
        "listener is not hooked, so the zero-recompile claim below "
        "would be vacuous")

    # steady state: identical args -> pure program-cache hits
    watcher.arm()
    ev0 = sum(1 for e in rec.snapshot() if e["kind"] == "recompile")
    d_hist = REGISTRY.histogram("decode.dispatch_s")
    n0, s0 = d_hist.count, d_hist.sum
    t0 = time.perf_counter()
    toks2 = dec.generate(prompt, **kw)
    wall_s = time.perf_counter() - t0
    recompiles = watcher.count - c_warm
    events = sum(1 for e in rec.snapshot()
                 if e["kind"] == "recompile") - ev0
    assert recompiles == 0, (
        f"steady-state generate hit XLA {recompiles} time(s) — the "
        f"decode program cache is not keying these calls identically")
    assert events == 0, f"{events} recompile event(s) in steady state"
    np.testing.assert_array_equal(toks, toks2)

    # dispatch accounting: the schedule's chunk count, nothing more
    dispatches = d_hist.count - n0
    num_steps, chunk_steps = dec._schedule(plen + new, 0,
                                           args.token_chunk)
    want = math.ceil(num_steps / chunk_steps)
    assert dispatches == want, (
        f"steady-state generate made {dispatches} dispatches, "
        f"schedule says {want} ({num_steps} steps / {chunk_steps} "
        f"per chunk)")
    disp_s = d_hist.sum - s0
    share = disp_s / wall_s
    assert share <= args.max_dispatch_share, (
        f"dispatch share {share:.3f} exceeds "
        f"{args.max_dispatch_share} — dispatch seconds larger than "
        f"the wall they live in")

    tps = mb * new / wall_s
    log(f"decode steady state: {tps:.1f} tok/s ({wall_s * 1e3:.1f} ms "
        f"for {new} tokens x mb{mb}), {dispatches} dispatches "
        f"(schedule {want}), dispatch share {share:.3f}, warmup "
        f"{c_warm} compiles in {first_call_s:.2f}s, steady recompiles "
        f"0, events 0")
    row = {"metric": "decode_profile", "value": round(tps, 2),
           "unit": "tokens/sec",
           "recompiles_steady": recompiles,
           "recompile_events_steady": events,
           "warmup_compiles": c_warm,
           "first_call_s": round(first_call_s, 3),
           "wall_s": round(wall_s, 4),
           "dispatches": dispatches,
           "chunk_steps": chunk_steps,
           "dispatch_share": round(share, 4),
           "config": {"layers": layers, "d_model": d, "heads": heads,
                      "vocab": vocab, "prompt_len": plen,
                      "new_tokens": new, "microbatch": mb,
                      "token_chunk": args.token_chunk},
           "cpu_count": os.cpu_count() or 1}
    print(json.dumps(row))
    log("decode profile smoke: OK")


if __name__ == "__main__":
    main()
