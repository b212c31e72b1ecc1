"""sha256 of the lowered text of both decode engines' programs, to show
that a refactor compiles what its parent compiled — no chip needed, not
part of the tests.

The ring's decode and prefill programs (``gpt_tiny`` and ``olmoe_tiny``;
``kv_cache`` buffer and int8; ``beam_width`` 1 and 2; 1 stage and
several; ``olmoe_tiny``'s widths at four layers for four stages;
``brumby_tiny``, whose state has neither int8 rows nor beams;
``cohere_moe_tiny``, a format a layer; ``jamba_tiny``, two kinds of
memory in one graph, a period a stage; ``granite_hybrid_tiny``, the
same with a state of heads and routed experts in every layer;
``kimi_k2_tiny``, a latent cache, and at two stages a dense block at
the place of the other stage's routed one; ``mellum_tiny``, two
rotations by layer kind; ``longcat_flash_tiny``, two latent caches a
block and two turns around them a step, two blocks a stage;
``lfm2_moe_tiny``, layers that keep a convolution window and nothing
else beside attention layers' caches, a dense block at the place of the
other stage's routed one; ``solar_open2_tiny``, layers whose state
their own write reads beside attention layers' caches, a share of the
experts held; ``nemotron_h_tiny``, layers that are a mixer or a
feed-forward part alone — one kind in three keeping no memory at all —,
a state of heads in two B/C groups, two-matrix relu² experts on latent
rows) and
the engine's step (greedy and sampling) and prefill, lowered on the CPU
mesh at toy sizes.  Run it in two trees and compare the lines:

    env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python scripts/lowered_text_hashes.py [DIR]

One ``name sha256 bytes`` line a program; with ``DIR`` each text is also
written to ``DIR/<name>.txt`` for ``diff``.  It reads only what both
engines have always had: ``_init_state``, ``_get_decode_fn``,
``_build_prefill_fn``, ``_step_fn``, ``_caches`` (and the engine's
``_prefill_fns`` and ``_blocks``, which it has had since PR 39, and its
``_prev_ids`` and ``_blank_rows``, the step's inputs since PR 42; since
PR 46 ``_blank_rows`` ends with a sixth host row, the list of live
slots the step's cache kernels walk, ``ops/kv_cache.py::live_slots``,
so both ``engine.step.*`` lines are new there and no other line is).
"""

import hashlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from defer_tpu.models import (brumby_tiny, cohere_moe_tiny,
                              granite_hybrid_tiny, gpt_tiny, jamba_tiny,
                              kimi_k2_tiny, lfm2_moe_tiny, longcat_flash_tiny,
                              mellum_tiny, nemotron_h_tiny, olmoe,
                              olmoe_tiny, solar_open2_tiny)
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine

PLEN, CHUNK = 5, 2


def ring_configurations():
    """``(name, graph, stages, kv_caches, beams)`` of every ring
    configuration listed (``scripts/ring_tokens.py`` generates over the
    same)."""
    every, plain = (("buffer", "int8"), (1, 2)), (("buffer",), (1,))
    yield "gpt_tiny", gpt_tiny(), (1, 4), *every
    yield "olmoe_tiny", olmoe_tiny(), (1, 2), *every
    # olmoe_tiny's widths at four layers, for four stages
    yield "olmoe_4l", olmoe(4, 64, 4, 16, vocab=211, num_experts=8,
                            experts_per_tok=2, expert_hidden=32), (4,), *every
    # a retention state has neither int8 rows nor beams
    yield "brumby_tiny", brumby_tiny(), (1, 2), *plain
    # window layers' ring buffers beside full layers' caches: one period
    # a stage
    yield "cohere_moe_tiny", cohere_moe_tiny(), (1, 2), *every
    # state-space layers' windows and states beside attention layers'
    # caches: a state has neither int8 rows nor beams
    yield "jamba_tiny", jamba_tiny(), (1, 2), *plain
    # the state-space state's second shape, and every layer routing
    yield "granite_hybrid_tiny", granite_hybrid_tiny(), (1, 2), *plain
    # a latent cache has neither int8 rows nor beams; two stages put the
    # dense block beside a routed one
    yield "kimi_k2_tiny", kimi_k2_tiny(), (1, 2), *plain
    # two rotations by layer kind, ring buffers beside full layers'
    # caches, kernels named by kind: one period a stage
    yield "mellum_tiny", mellum_tiny(), (1, 2), *every
    # a block of two latent-attention sublayers: two row buffers a
    # layer, the shortcut's output carried across the second turn; a
    # stage cut between blocks
    yield "longcat_flash_tiny", longcat_flash_tiny(), (1, 2), *plain
    # a window-only memory (neither int8 rows nor beams) beside caches,
    # three kinds of block on one ledger: one period a stage
    yield "lfm2_moe_tiny", lfm2_moe_tiny(), (1, 2), *plain
    # a delta-rule state and its window (neither int8 rows nor beams)
    # beside caches, a share of every layer's experts held: one period
    # a stage
    yield "solar_open2_tiny", solar_open2_tiny(), (1, 2), *plain
    # a block a published layer, a mixer or a feed-forward part: the
    # feed-forward blocks keep no memory and the ring holds no buffer
    # for them (a state beside them: neither int8 rows nor beams); one
    # period a stage
    yield "nemotron_h_tiny", nemotron_h_tiny(), (1, 2), *plain


def ring_programs(name, graph, stages, kv_caches, beams):
    params = graph.init(jax.random.key(0))
    for n in stages:
        for kv_cache in kv_caches:
            for beam in beams:
                dec = PipelinedDecoder(
                    graph, params, num_stages=n, microbatch=2, max_len=16,
                    kv_cache=kv_cache, beam_width=beam)
                tag = f"ring.{name}.{kv_cache}.beam{beam}.stages{n}"
                mb = dec.microbatch
                prompt = jnp.zeros((n, mb, PLEN), jnp.int32)
                a, caches = dec._init_state()
                i32 = jnp.int32(0)
                for sample in (False, True) if beam == 1 else (False,):
                    mode = "sample" if sample else "greedy"
                    top_k = 3 if sample else None
                    yield f"{tag}.decode.{mode}", dec._get_decode_fn(
                        n * CHUNK, sample, top_k).lower(
                        dec._w, prompt, i32, i32, i32, jnp.uint32(0),
                        jnp.float32(0), jnp.zeros((n, mb), jnp.int32), i32,
                        i32, a, caches)
                    if beam == 1:   # beam search has no fused prefill
                        yield f"{tag}.prefill.{mode}", \
                            dec._build_prefill_fn(PLEN, sample, top_k).lower(
                                dec._w, prompt, jnp.uint32(0),
                                jnp.float32(0), caches)


def engine_programs():
    graph = gpt_tiny()
    eng = ContinuousBatchEngine(graph, graph.init(jax.random.key(0)),
                                num_stages=2, width=3, top_k=3)
    for sample in (False, True):
        yield f"engine.step.{'sample' if sample else 'greedy'}", \
            eng._step_fn(sample).lower(
                eng.params, eng._caches, eng._prev_ids, *eng._blank_rows())
    embed, blocks_prefill = eng._prefill_fns
    ids = jnp.zeros(eng.prefill_len, jnp.int32)
    yield "engine.prefill.embed", embed.lower(eng.params["embeddings"], ids)
    ops, names = zip(*eng._blocks)
    yield "engine.prefill.blocks", blocks_prefill.lower(
        ops, [eng.params[nm] for nm in names],
        embed(eng.params["embeddings"], ids),
        [eng.kv_format.layer(eng._caches, l) for l in range(len(ops))],
        jnp.int32(0))


def main() -> int:
    out_dir = sys.argv[1] if len(sys.argv) > 1 else None
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    programs = [*(program for cfg in ring_configurations()
                  for program in ring_programs(*cfg)),
                *engine_programs()]
    for name, lowered in programs:
        text = lowered.as_text()
        if out_dir:
            with open(os.path.join(out_dir, name + ".txt"), "w") as f:
                f.write(text)
        print(name, hashlib.sha256(text.encode()).hexdigest(), len(text))
    return 0


if __name__ == "__main__":
    sys.exit(main())
