"""TPU benchmark for the pipelined KV-cache decoder.

Measures greedy autoregressive generation throughput of the GPT-2-small
geometry (12 layers, d=768, 50257 vocab) on the available chip(s):
tokens/sec across a microbatch sweep, plus an approximate model-FLOPs
utilisation from the per-token cost model

    flops/token ~= L * (24 d^2 + 4 pos_avg d) + 2 d V

(qkv+proj+mlp matmuls per layer, attention against the growing cache,
lm_head).  The whole generation runs as ONE scan dispatch per
``token_chunk`` tokens, so the host sync is paid once per chunk, not
per token.

Prints one JSON dict on stdout.  If ``DEFER_DECODE_OUT`` is set, the
(partial) artifact is also rewritten after EVERY row — a wall-clock
timeout then costs the remaining rows, not the whole run (the r4/r5
lesson: the 30-row sweep once timed out at row 26 and left nothing).
``DEFER_DECODE_ROWS`` (comma-separated substrings) restricts the sweep
to matching row tags, e.g. ``DEFER_DECODE_ROWS=w8,mb64`` for a re-run
of just the missing rows.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from defer_tpu.models import gpt
    from defer_tpu.runtime.decode import PipelinedDecoder
    from defer_tpu.utils.hw import identify_chip, peak_flops

    devices = jax.devices()
    on_tpu = devices[0].platform != "cpu"
    out = {
        "metric": "gpt_small_pipelined_decode",
        "platform": devices[0].platform,
        "device_kind": str(getattr(devices[0], "device_kind", "")),
    }
    if on_tpu:
        layers, d, heads, vocab = 12, 768, 12, 50257
        max_len, plen, new = 512, 32, 128
        mbs = (8, 32, 64)
        cd = jnp.bfloat16
        gen = identify_chip(devices[0])
        peak = peak_flops(gen)
        out["tpu_generation"] = gen
    else:  # CPU smoke
        layers, d, heads, vocab = 4, 64, 2, 128
        max_len, plen, new = 48, 8, 16
        mbs = (4,)
        cd = None
        peak = 0.0

    graph = gpt(layers, d, heads, max_len, vocab=vocab)
    params = graph.init(jax.random.key(0))
    gqa_kv = max(1, heads // 6)  # GQA variant: 6-way query groups
    graph_gqa = gpt(layers, d, heads, max_len, vocab=vocab, kv_heads=gqa_kv)
    params_gqa = graph_gqa.init(jax.random.key(0))
    rng = np.random.default_rng(0)

    pos_avg = plen + new / 2

    def per_token_flops(kv):
        # per layer: qkv (d + 2*kv*hd cols) + proj (d) + mlp (8d) matmuls
        # at 2*d each, plus attention against the pos_avg-deep cache
        qkv_cols = d + 2 * kv * (d // heads)
        return (layers * (2 * d * (qkv_cols + d + 8 * d)
                          + 4 * pos_avg * d) + 2 * d * vocab)

    flops_tok = per_token_flops(heads)
    out["flops_per_token_model"] = flops_tok
    out["flops_per_token_gqa"] = per_token_flops(gqa_kv)
    out["config"] = {"layers": layers, "d_model": d, "vocab": vocab,
                     "prompt_len": plen, "new_tokens": new,
                     "max_len": max_len, "num_stages": 1}

    # token_chunk keeps ONE compiled program across warmup and the timed
    # run (the decode program cache is keyed by chunk length); the first
    # call compiles, the timed second call is dispatch-only
    token_chunk = 32
    sweep = {}
    variants = [("", graph, params, "buffer", None)]
    if on_tpu:
        variants.append((f"_gqa{gqa_kv}kv", graph_gqa, params_gqa,
                         "buffer", None))
        variants.append(("_int8kv", graph, params, "int8", None))
        # W8A16: int8 weights halve the dominant HBM stream vs bf16 —
        # the decode-side memory-bandwidth lever
        variants.append(("_w8", graph, params, "buffer", "int8"))
        variants.append(("_w8_int8kv", graph, params, "int8", "int8"))
    from defer_tpu.utils.artifact import flush_artifact

    row_filter = [s for s in os.environ.get("DEFER_DECODE_ROWS", ""
                                            ).split(",") if s]
    out_path = os.environ.get("DEFER_DECODE_OUT")

    def flush_partial():
        out["decode_sweep"] = sweep
        out["token_chunk"] = token_chunk
        out.setdefault("value", 0.0)
        out["unit"] = "tokens/sec"
        # merge keeps rows from a timed-out earlier run when re-running
        # with DEFER_DECODE_ROWS over the same DEFER_DECODE_OUT; the
        # headline value is recomputed over the merged rows
        return flush_artifact(out_path, dict(out),
                              merge_key="decode_sweep",
                              merge_prior=bool(row_filter))

    for mb in mbs:
        for vtag, vgraph, vparams, vcache, vwq in variants:
            for use_prefill in ((False, True) if on_tpu else (False,)):
                tag = f"mb{mb}{vtag}" + ("_prefill" if use_prefill else "")
                if row_filter and not any(s in tag for s in row_filter):
                    continue
                try:
                    dec = PipelinedDecoder(vgraph, vparams, num_stages=1,
                                           microbatch=mb, max_len=max_len,
                                           compute_dtype=cd,
                                           kv_cache=vcache,
                                           weight_dtype=vwq)
                    prompt = rng.integers(0, vocab,
                                          size=(mb, plen)).astype(np.int32)
                    kw = dict(max_new_tokens=new, token_chunk=token_chunk,
                              prefill=use_prefill)
                    t0 = time.perf_counter()
                    dec.generate(prompt, **kw)          # compile + run
                    compile_s = time.perf_counter() - t0
                    t0 = time.perf_counter()
                    toks = dec.generate(prompt, **kw)   # warm
                    dt = time.perf_counter() - t0
                    assert toks.shape == (mb, plen + new)
                    tps = mb * new / dt
                    row = {"tokens_per_s": round(tps, 2),
                           "ms_per_token_step": round(1e3 * dt / new, 3),
                           "wall_s": round(dt, 3),
                           "first_call_s": round(compile_s, 3)}
                    if peak:
                        ft = per_token_flops(
                            gqa_kv if "gqa" in vtag else heads)
                        row["mfu_decode"] = round(ft * tps / peak, 5)
                    sweep[tag] = row
                    print(f"{tag}: {tps:.1f} tok/s "
                          f"({1e3 * dt / new:.1f} ms/token-step, "
                          f"first call {compile_s:.1f}s)",
                          file=sys.stderr, flush=True)
                    del dec
                except Exception as e:  # noqa: BLE001 — OOM data point
                    sweep[tag] = {"error": repr(e)[:200]}
                    print(f"{tag}: {e!r}", file=sys.stderr, flush=True)
                flush_partial()
    final = flush_partial()
    print(json.dumps(final))


if __name__ == "__main__":
    main()
