"""Driver ``batch_decode``: an offline batch through ``PipelinedDecoder``.

``batch`` seeded prompts of ``prompt_len`` tokens, ``new_tokens`` greedy
tokens each, ``generate(prefill=True, token_chunk=k, on_tokens=cb)``.
Generations run back to back until the window is over; the generation
in flight is then stopped at its next chunk boundary by an exception its
own callback raises, and the window ends there.  ``tokens_per_s`` is
every token handed over in the window (the prefills' first tokens
included) over the whole of the window's time (the prefills included).
A generation need not finish inside the window: at today's step time
one takes minutes.

A *reading* is the time between two consecutive ``on_tokens`` calls
inside one generation (``k`` decode steps of the whole batch, ending
where the tokens are in host memory).  Readings check the unit's size
(``chipbench/readings.py``) and feed ``decode_chunk_ms``; the interval
that holds the prefill is not one, and is printed as ``prefill_ms``.

Traffic file keys: ``batch``, ``prompt_len``, ``new_tokens``,
``token_chunk``, ``max_len``, ``compute_dtype``, ``kv_cache``,
``check_sequences``, ``trace_seconds``.  Configuration file keys:
``model_args`` (for ``defer_tpu.models.gpt``), ``reference``.
"""

from __future__ import annotations

import time

import numpy as np

from chipbench import readings as rd
from chipbench.agreement import logit_gaps
from chipbench.weights import init_on_device

#: how far under the plain reference's best logit the program's token may
#: sit, as a share of the spread (max - mean) of that position's
#: reference logits.  The program multiplies in bfloat16 and the
#: reference in true float32, so near-ties break differently: the first
#: v5e runs read a worst gap of 0.0025 here and 0.0041 in the serving
#: cell (96-98% of tokens the reference's own argmax).  The bound leaves
#: ~7x over that; a wrong cache row, position or weight picks a token
#: about one whole spread down (a share near 1).  Tokens are all the
#: program hands out, so a precision one step lower than stated could
#: pass: logits out of the decoders are an open question (PERF.md).
GAP_TOL = 0.03


class _WindowOver(Exception):
    """Raised by the harness's own callback to stop a generation."""


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    graph = models.gpt(**cfg["model_args"])
    with ctx.span("weights"):
        params = init_on_device(graph, ctx.seed,
                                jnp.dtype(tr["compute_dtype"]))
    with ctx.span("build"):
        dec = PipelinedDecoder(
            graph, params, num_stages=ctx.cell.chips,
            microbatch=tr["batch"] // ctx.cell.chips, max_len=tr["max_len"],
            compute_dtype=jnp.dtype(tr["compute_dtype"]),
            kv_cache=tr["kv_cache"])
    rng = np.random.default_rng(ctx.seed)
    vocab = cfg["model_args"]["vocab"]
    prompts = rng.integers(0, vocab, (tr["batch"], tr["prompt_len"])
                           ).astype(np.int32)
    state = {"params": params, "dec": dec, "prompts": prompts,
             "traffic": tr, "config": cfg}
    with ctx.span("warmup"):
        # the window's own programs: the prefill is keyed by the prompt
        # length and the decode program by token_chunk, so two chunks of
        # a short generation compile everything a long one runs
        dec.generate(prompts, 2 * tr["token_chunk"] + 1, prefill=True,
                     token_chunk=tr["token_chunk"],
                     on_tokens=lambda *a, **k: None)
    return state


def measure(state, seconds, ctx):
    from defer_tpu.obs import REGISTRY

    dec, tr = state["dec"], state["traffic"]
    launch = REGISTRY.histogram("decode.dispatch_s")
    launch.clear()
    reads, reached, prefill_s, tokens, gens = [], [], [], 0, 0
    t_start = time.perf_counter()
    t_end = t_start + seconds
    first: list = []   # the first generation's tokens, as they arrive
    while time.perf_counter() < t_end:
        t_prev, calls = time.perf_counter(), 0
        keep = first if gens == 0 else None

        def on_tokens(lo, hi, toks, rows):
            nonlocal tokens, t_prev, calls
            now = time.perf_counter()
            if calls == 0:
                prefill_s.append(now - t_prev)    # holds the prefill
            else:
                reads.append(now - t_prev)
                reached.append(hi)
            t_prev, calls = now, calls + 1
            tokens += toks.shape[0] * (hi - lo)
            if keep is not None:
                keep.append(toks)
            if now >= t_end:
                raise _WindowOver

        gens += 1
        try:
            with ctx.span("generate"):
                dec.generate(state["prompts"], tr["new_tokens"],
                             prefill=True, token_chunk=tr["token_chunk"],
                             on_tokens=on_tokens)
        except _WindowOver:
            pass
    wall = time.perf_counter() - t_start
    # what the first generation handed over, whether or not it finished:
    # the check judges these tokens, so it needs no generation of its own
    state["sample"] = np.concatenate([state["prompts"]] + first, axis=1)
    if not ctx.trace:
        rd.require_readings(reads)
    notes = []
    if prefill_s:
        notes.append(f"prefill_ms {1e3 * rd.quantile(prefill_s, 0.5):.3f} "
                     f"(median of {len(prefill_s)}; {tr['batch']} x "
                     f"{tr['prompt_len']} tokens, not a reading)")
    return {
        # all the work over all the time: a stall anywhere in the window,
        # the prefills included, shows
        "end_to_end": {"tokens_per_s": tokens / wall},
        "attempted": gens, "failed": 0, "readings": reads, "notes": notes,
        "work_over_wall": {"tokens": tokens, "wall_s": wall,
                           "tokens_per_s": tokens / wall,
                           "generations_started": gens},
        "counters": {
            "launch_s_sum": float(launch.sum),
            "launch_count": int(launch.count),
            "steps_per_reading": tr["token_chunk"],
            "rows": tr["batch"],
            # a step attends over the positions written so far
            "live_positions": float(np.mean(reached)) if reached
            else float(tr["prompt_len"]),
            "model_args": state["config"]["model_args"],
            "weight_bytes": int(np.dtype(tr["compute_dtype"]).itemsize),
            "kv_bytes": int(np.dtype(tr["compute_dtype"]).itemsize),
            # leaves the ring placed otherwise than the device's default
            "relaid_leaves": float(
                REGISTRY.gauge("decode.weights.relaid_leaves").value),
        },
    }


def check(state, ctx):
    """Prefill-then-decode tokens of ``check_sequences`` sequences against
    the plain reference's logits."""
    tr = state["traffic"]
    out = state["sample"]
    n = tr["check_sequences"]
    plen = tr["prompt_len"]
    if not np.array_equal(out[:, :plen], state["prompts"]):
        return False, {"error": "prompts not echoed"}
    gaps = logit_gaps(state["params"], out[:n], plen,
                      state["config"]["reference"])
    worst = float(gaps.max())
    return worst <= GAP_TOL, {
        "worst_logit_gap_share": worst, "tolerance": GAP_TOL,
        "exact_argmax_share": float((gaps <= 0).mean()),
        "tokens_compared": int(gaps.size)}


def close(state):
    state.clear()
