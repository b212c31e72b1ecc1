"""Driver ``batch_decode_moe``: ``batch_decode``'s offline batch through
``PipelinedDecoder``, for the routed-expert family (``models.olmoe``).

The window, the readings, ``tokens_per_s`` and the check are
``chipbench/drivers/batch_decode.py``'s own functions, called from here.
This file has the set-up and what the expert layer adds to ``counters``.

**Weights live on the chip once.**  8 layers at published widths are
7.13 GB in bf16, and ``PipelinedDecoder`` places its own copy (the flat
rows and, beside them, the expert leaves as stage-sharded arguments): a
parameter tree kept on the chip next to it would be 14.3 of 15.75 GB.
So the tree is made from the seed by the program's own initialiser on
the host (jax's CPU backend), as a user who has loaded a checkpoint
holds it, and the decoder's copy is the first thing the chip is given;
the host tree also feeds the plain reference in ``check``.  Made on the
chip and fetched, the tree left the allocator in a state that differed
from run to run, and the grouped product's time follows where the
expert leaves sit: the same seed read 183.5 and 185.9 ms a chunk
(PERF.md section 6).

**What the random weights stand for** is the configuration's choice,
not the program's: its file scales leaves of the initialiser's tree
(``init_gain``: a path's ending -> a factor) and says why under
``assumed``.  This cell makes embedding rows of unit variance and the
router twice as sharp, so that the experts a step touches (the one
data-dependent quantity of the step's time) are spread as uniform
routing spreads them; what a trained router touches is not measured
(PERF.md section 7).

``check`` holds the program to the plain reference twice: the
generated tokens by ``batch_decode``'s measure at this file's limit,
and the router by the share of the reference's expert choices that the
program's own blocks make on the same tokens (``router_agreement``).

Counters added: the program's ``decode.moe.*`` sums over the window
(``assignments``: rows x experts a token x layers x steps;
``experts_hit``: distinct experts a layer a step; ``load_max``: the
largest group a layer a step), ``experts_hit_share`` (experts hit a
layer a step over the number of experts: the share of expert weights a
step needs) and ``prefill_tokens`` (tokens of one prefill).

Traffic file keys: as ``batch_decode``.  Configuration file keys:
``model_args`` (for ``defer_tpu.models.olmoe``), ``reference``, and
optionally ``init_gain``.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

from chipbench.drivers import batch_decode as base

#: this configuration's limit on the worst logit gap share (the measure is
#: ``batch_decode``'s: how far the reference's logit of the program's token
#: sits under the reference's best, over the position's spread).  Set
#: mid-gap between two readings on the v5e (PR 26, PERF.md section 6; a
#: reading is the worst of 512 tokens, 2 sequences, as a run judges
#: them).  The largest the program gave over 56 such readings of 28
#: seeds: 0.0331.  The reference itself, its products' inputs rounded to
#: bfloat16 and all else float32, gives 0.0288: routing is discrete, and
#: under 1% of a layer's expert choices flip at bfloat16 near-ties, so
#: the dense GPT-2 cell's 0.03 is this configuration's noise floor, not
#: a margin.  The same reference with inputs rounded to float8_e4m3, the
#: nearest precision below the stated one: 0.0631 at the least over 24
#: pairs (0.104 at the most): not correct.  0.045 is 36% over the one
#: and 29% under the other.
GAP_TOL = 0.045
#: the least share of the reference's expert choices (experts a token x
#: rows) that the program's own blocks must make on the same tokens, in
#: the layer where they agree least.  Set between two readings on the
#: v5e (PR 26, PERF.md section 6; pairs of 1279-token sequences): the
#: program's least 0.9898 (the bfloat16-input reference's 0.9941), the
#: float8_e4m3-input reference's most 0.9298: not correct.  A router
#: that takes the wrong experts shares about experts_per_tok /
#: num_experts of them.
ROUTER_TOL = 0.975
MOE_COUNTERS = ("decode.moe.assignments", "decode.moe.experts_hit",
                "decode.moe.load_max")


def make_weights(graph, seed: int, dtype, gains: dict):
    """The program's initialiser from the seed as one jitted call (as
    ``chipbench/weights.py`` makes it); a leaf whose path ends with a key
    of ``gains`` (``block_3/router/w`` ends with ``router/w``) is scaled
    by that factor before floating leaves are cast to ``dtype``."""
    import jax
    import jax.numpy as jnp

    def leaf(path, a):
        name = "/".join(str(k.key) for k in path)
        for ending, gain in gains.items():
            if name.endswith(ending):
                a = a * gain
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
            else a

    def make(key):
        return jax.tree_util.tree_map_with_path(leaf, graph.init(key))

    # seeds run to a little over 2**31: fold into the key's 32-bit range
    return jax.block_until_ready(
        jax.jit(make)(jax.random.key(int(seed) % (2 ** 31 - 1))))


def setup(ctx):
    import jax
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    graph = models.olmoe(**cfg["model_args"])
    dtype = jnp.dtype(tr["compute_dtype"])
    with ctx.span("weights"), jax.default_device(jax.devices("cpu")[0]):
        # made on the host from the seed; the chip has held nothing yet
        params = jax.device_get(make_weights(
            graph, ctx.seed, dtype, cfg.get("init_gain", {})))
    with ctx.span("build"):
        dec = PipelinedDecoder(
            graph, params, num_stages=ctx.cell.chips,
            microbatch=tr["batch"] // ctx.cell.chips, max_len=tr["max_len"],
            compute_dtype=dtype, kv_cache=tr["kv_cache"])
    rng = np.random.default_rng(ctx.seed)
    prompts = rng.integers(0, cfg["model_args"]["vocab"],
                           (tr["batch"], tr["prompt_len"])).astype(np.int32)
    state = {"params": params, "dec": dec, "prompts": prompts,
             "graph": graph, "traffic": tr, "config": cfg}
    with ctx.span("warmup"):
        # the prefill is keyed by the prompt length and the decode
        # program by token_chunk: two chunks compile all a window runs
        dec.generate(prompts, 2 * tr["token_chunk"] + 1, prefill=True,
                     token_chunk=tr["token_chunk"],
                     on_tokens=lambda *a, **k: None)
    return state


def _moe_counts() -> dict:
    from defer_tpu.obs import REGISTRY
    return {name: REGISTRY.counter(name).n for name in MOE_COUNTERS}


def measure(state, seconds, ctx):
    tr, args = state["traffic"], state["config"]["model_args"]
    before = _moe_counts()
    out = base.measure(state, seconds, ctx)
    moe = {name: n - before[name] for name, n in _moe_counts().items()}
    counters = out["counters"]
    counters.update(moe, prefill_tokens=tr["batch"] * tr["prompt_len"])
    # one (layer, step) routes rows x experts_per_tok choices
    layer_steps = moe["decode.moe.assignments"] / (
        tr["batch"] * args["experts_per_tok"])
    if layer_steps:
        hit = moe["decode.moe.experts_hit"] / layer_steps
        load_max = moe["decode.moe.load_max"] / layer_steps
        mean_load = (tr["batch"] * args["experts_per_tok"]
                     / args["num_experts"])
        counters["experts_hit_share"] = hit / args["num_experts"]
        out["notes"].append(
            f"experts hit a layer a step {hit:.2f} of "
            f"{args['num_experts']}; largest group {load_max:.2f} rows, "
            f"{load_max / mean_load:.2f}x the mean load "
            f"({layer_steps:.0f} layer-steps)")
    return out


def router_agreement(graph, params, seqs, ref_cfg: dict) -> list:
    """For each layer, the share of the plain reference's expert choices
    on ``seqs`` [n, t] that the program's blocks make too: the program's
    own full-sequence forward (``apply_with_kv``, what its prefill runs)
    in the type of ``params``, a layer's weights on the device at a time,
    against the reference's float32 forward of the same tokens."""
    import jax

    ref = importlib.import_module(ref_cfg["module"])
    _, want = ref.logits(params, seqs, lo=seqs.shape[1] - 1, experts=True,
                         **ref_cfg["args"])
    want = np.asarray(want)                            # [L, n, t, k]
    nodes = graph.nodes
    n_experts = nodes["block_0"].op.num_experts

    @jax.jit
    def layer(p, x):
        sown: dict = {}
        y, _k, _v = nodes["block_0"].op.apply_with_kv(p, x, sow=sown)
        return y, sown["moe.chosen"].reshape(x.shape[:2] + (-1,))

    def chose(ids):                                   # -> [n, t, E] bool
        hot = np.zeros(ids.shape[:2] + (n_experts,), bool)
        np.put_along_axis(hot, ids, True, -1)
        return hot

    x = jax.jit(nodes["embeddings"].op.apply)(params["embeddings"], seqs)
    shares = []
    for i in range(want.shape[0]):
        x, got = layer(params[f"block_{i}"], x)
        both = chose(np.asarray(got)) & chose(want[i])
        shares.append(float(both.sum() / want[i].size))
    return shares


def check(state, ctx):
    # the reference upcasts a layer at a time beside whatever the chip
    # still holds: let the decoder's weights and caches go first
    state.pop("dec", None)
    gc.collect()
    ok, detail = base.check(state, ctx)
    if "worst_logit_gap_share" not in detail:
        return ok, detail
    detail["tolerance"] = GAP_TOL               # judged at this file's limits
    n = state["traffic"]["check_sequences"]
    shares = router_agreement(state["graph"], state["params"],
                              state["sample"][:n, :-1],
                              state["config"]["reference"])
    detail.update(router_agreement_share=min(shares),
                  router_agreement_by_layer=[round(s, 5) for s in shares],
                  router_tolerance=ROUTER_TOL)
    return (detail["worst_logit_gap_share"] <= GAP_TOL
            and min(shares) >= ROUTER_TOL), detail


close = base.close
