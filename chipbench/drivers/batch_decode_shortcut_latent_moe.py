"""Driver ``batch_decode_shortcut_latent_moe``: ``batch_decode``'s offline
batch through ``PipelinedDecoder``, for the family whose every block is
a *double layer* — two latent-attention sublayers, a latent cache each,
around one shortcut-connected mixture of experts whose router chooses by
a biased softmax among routed experts (a share of them held) and
zero-compute ones (``models.longcat_flash``).

The window, the readings, ``tokens_per_s`` and the token check are
``chipbench/drivers/batch_decode.py``'s own functions, and what the
routed layers add to ``counters`` is ``batch_decode_window_moe``'s
``measure``, both called from here; the weights are made where and how
``batch_decode_retention`` makes them (drawn on the chip a node at a
time, kept on the *host*); the head is the model's own (untied).  This
file has the set-up, the family's counters and gauges and the rest of
``check``.

``check`` holds the program to the plain reference five times:

* **the logits** of the generated tokens, by ``batch_decode``'s measure
  at this file's limit, on ``check_sequences`` sequences over the first
  ``check_tokens`` generated tokens;
* **the router's choices**: the share of the reference's 12 choices a
  token a layer (over all 768 columns, routed — held or not — and zero)
  that the program's own blocks make on the same tokens, on their own
  stream, in the layer where they agree least;
* **the router's weights**: the program's ``route`` on the reference's
  own normed stream ``n1`` into each layer's shortcut, against the
  reference's weights of the same twelve, over the tokens whose twelve
  agree, as ``rms_err`` — what tells a bias that only chooses from one
  that also weighs, ``x 6`` from another scale, and weights used as the
  softmax gave them from renormalised ones;
* **the shortcut's output**: the program's ``shortcut`` (held routed
  pairs and zero pairs, nothing else) on the reference's own ``n1``
  against the reference's ``s``, as ``rms_err``, in the layer where they
  differ most — the check only this family needs: a program (or a
  reference) that leaves the zero-compute experts out, counts them
  twice or weighs them otherwise reads a large share of ``s`` here,
  whatever the logits say;
* **the latent probe**: one more generation outside the window, the
  prefill and ``PROBE_STEPS`` decode steps; ``check_sequences``
  sequences' cached rows ``[c, k_r]`` of **both sublayers** of the first
  block (upstream of every routed expert: the first sublayer's rows
  depend on the embedding alone, the second's on ``h1 + FFN_0(n1)``, and
  the shortcut joins only behind them, so only rounding parts program
  and reference) and of the last block are fetched from the ring's
  buffers (``latent`` and ``latent_1``) and compared with the
  reference's over the prompt and the tokens the program fed back, by
  ``rel_err``, the prompt's rows (the prefill's bulk write) and the
  generated ones' (a step's write) each.  A sublayer's rows in the
  other's buffer, a LoRA scale left out of the row, a key left
  unrotated or a row in the wrong slot do not hide here.

Counters added: ``batch_decode_window_moe``'s (the program's four
``decode.moe.*`` sums over the window, ``experts_hit_share``,
``held_share``, ``prefill_tokens``, ``prefill_piece_rows``, ``max_len``),
the window's ``decode.moe.zero_assignments`` and
``decode.moe.real_assignments`` with ``zero_share`` and ``real_share``
(of all assignments), and the gauges ``decode.cache.latent_bytes`` /
``.latent_positions`` / ``.latent_sublayers`` as ``cache_latent_*``.
``model_args`` names the attention's widths as Kimi's does
(``heads``, ``latent_dim``, ``rope_dim``, ``nope_dim``, ``v_dim``), so
the latent kernels' readers take them as they are.

Traffic file keys: as ``batch_decode``, and ``check_tokens``.
Configuration file keys: ``model_args`` (for
``defer_tpu.models.longcat_flash``), ``reference``, and optionally
``init_gain``.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

from chipbench.agreement import rel_err
from chipbench.drivers import batch_decode as base
from chipbench.drivers import batch_decode_window_moe as window_moe
from chipbench.drivers.batch_decode_hybrid_moe import rms_err
from chipbench.drivers.batch_decode_retention import make_weights

#: this configuration's limit on the worst logit gap share (the measure
#: is ``batch_decode``'s: how far the reference's logit of the program's
#: token sits under the reference's best, over the position's spread; a
#: token no better than a random one reads ~1).  Every limit here lies
#: between two readings on the v5e (PR 57; all of them, by seed, in
#: ``chipbench/README.longcat-flash.md``): the largest the program gave
#: over the builder's seeds, and the least a control gave
#: (``scripts/shortcut_latent_moe_controls.py``, seeds 4000000711 / 713
#: / 717).  The program's largest 0.0185 (0.0038 at the least, 0.0073 the
#: next largest; 11 readings of 11 seeds, each the worst of 2 x 512
#: tokens; 98% of its tokens are the reference's own argmax, and a
#: token that is not sits within bfloat16's noise of it).  The
#: reference with every product's operands rounded to float8_e4m3, the
#: nearest precision below the stated bfloat16: 1.096 at the least
#: (1.221 at the most): not correct.  0.085 is 4.6x over the one and
#: 13x under the other.
GAP_TOL = 0.085
#: the least share of the reference's choices (12 a token, over all 768
#: columns) that the program's own blocks must make on the same tokens,
#: on their own stream, in the layer where they agree least.  The
#: program's least 0.9878 (pairs of 1024-token sequences; 0.9904 at
#: the cell's length; by layer 0.997 falling to 0.988: the streams part
#: as bfloat16 turns a twelfth choice at a near-tie).  The program held
#: to a reference **without zero-compute experts**: 0.7461 at the most
#: in the last layer: not correct (the first layer's choices are made
#: upstream of every shortcut and agree).  The float8_e4m3-input
#: reference 0.427 in its best layer; a reference that renormalises
#: 0.490; one without the LoRA scales 0.512.  0.94 leaves a
#: disagreement of 0.06: 4.9x the program's 0.0122, 4.2x under that
#: control's 0.254.
ROUTER_TOL = 0.94
#: the most the program's weights of the chosen twelve may differ from
#: the reference's on the reference's own normed stream, as ``rms_err``
#: over the tokens whose twelve agree, in the layer where they differ
#: most: what tells a bias that chooses and never weighs from one that
#: also weighs, and weights used as the softmax gave them (x 6) from
#: renormalised ones.  The program's largest 1.65e-3 (1.63e-3 at the
#: least: the rounding of the stream to bfloat16 ahead of the router's
#: float32 product, and a softmax is steeper than Kimi's sigmoid); the
#: program held to a reference whose bias enters the weights 0.0901 at
#: the least (0.105 at the most); to one that renormalises 0.878: not
#: correct.  0.012 is 7.3x over the one and 7.5x under the other.
WEIGHTS_TOL = 0.012
#: the most the program's shortcut output may differ from the
#: reference's ``s`` on the reference's own normed stream, as
#: ``rms_err``, in the layer where they differ most.  The program's
#: largest 0.0198 (0.0131 at the least: the held experts' products in
#: bfloat16).  The program held to a reference **without zero-compute
#: experts** 12.4 at the least (what is left of ``s`` is a quarter of a
#: held pair a token); to one that renormalises 0.879; the
#: float8_e4m3-input reference against the float32 one 0.848: not
#: correct, each by this limit.  0.1 is 5x over the one and 8.5x under
#: the nearest of the others.
SHORTCUT_TOL = 0.1
#: decode steps behind the prefill before the rows are read back
PROBE_STEPS = 64
#: the most the cached rows of the last block's two sublayers may
#: differ from the reference's, as ``rel_err`` (largest difference over
#: largest entry), the prompt's rows and the generated ones' each.
#: The program's largest 0.0340 from the ring's buffers at the cell's
#: length (0.0488 from its forward pass over pairs of 1024-token
#: sequences); the program held to a reference without zero-compute
#: experts 0.350 at the least; to one that renormalises 0.595; the
#: float8_e4m3-input reference 1.037: not correct.  0.13 is 2.7x over
#: the one and 2.7x under the nearest; what it is for besides is a row
#: in the wrong slot or a sublayer's rows in the other's buffer (~1).
LATENT_TOL = 0.13
#: the same in the first block's two sublayers, whose rows no shortcut
#: has touched (the first sublayer's depend on the embedding alone, the
#: second's on ``h1 + FFN_0(n1)``), where only bfloat16's rounding parts
#: the program from the reference: the program's largest 0.0074 (0.0063
#: at the least); the
#: reference with its rows alone kept in float8_e4m3 — two caches one
#: precision below the stated bfloat16 — 0.0431 at the least (0.0510
#: at the most): not correct, by this limit and no other (its logits'
#: gap reads 0.044-0.046, its router's agreement 0.959-0.985, its
#: shortcut 0.080-0.086); the float8_e4m3-input reference 0.734; a
#: reference without the LoRA scales 2.44.  0.017 is 2.3x over the one
#: and 2.5x under the other.
LATENT_TOL_FIRST = 0.017
CACHE_GAUGES = ("decode.cache.latent_bytes", "decode.cache.latent_positions",
                "decode.cache.latent_sublayers")
#: the two counters this family adds to the four every routed family
#: sows (absent from a program older than the family: read as 0)
FATE_COUNTERS = ("decode.moe.zero_assignments", "decode.moe.real_assignments")


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    if ctx.cell.chips != 1:
        raise ValueError("batch_decode_shortcut_latent_moe's latent probe "
                         "reads one chip's buffers; give the cell one chip")
    graph = models.longcat_flash(**cfg["model_args"])
    dtype = jnp.dtype(tr["compute_dtype"])
    with ctx.span("weights"):
        params = make_weights(graph, ctx.seed, dtype,
                              cfg.get("init_gain", {}))
    with ctx.span("build"):
        dec = PipelinedDecoder(
            graph, params, num_stages=ctx.cell.chips,
            microbatch=tr["batch"] // ctx.cell.chips, max_len=tr["max_len"],
            compute_dtype=dtype, kv_cache=tr["kv_cache"])
    rng = np.random.default_rng(ctx.seed)
    # ids over the held rows of the vocabulary
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


def _fates() -> dict:
    from defer_tpu.obs import REGISTRY
    return {name: REGISTRY.counter(name).n for name in FATE_COUNTERS}


def measure(state, seconds, ctx):
    from defer_tpu.obs import REGISTRY

    before = _fates()
    out = window_moe.measure(state, seconds, ctx)
    counters = out["counters"]
    # that family's gauges are not this one's
    for name in window_moe.CACHE_GAUGES:
        counters.pop("cache_" + name.rsplit(".", 1)[1], None)
    counters.update({"cache_" + name.rsplit(".", 1)[1]:
                     float(REGISTRY.gauge(name).value)
                     for name in CACHE_GAUGES})
    fates = {name: n - before[name] for name, n in _fates().items()}
    counters.update(fates)
    pairs = counters.get("decode.moe.assignments", 0)
    if pairs:
        zero, real = (fates[name] / pairs for name in FATE_COUNTERS)
        counters.update(zero_share=zero, real_share=real)
        a = state["config"]["model_args"]
        out["notes"].append(
            f"{zero:.4f} of the assignments fell to zero-compute experts "
            f"({a['zero_experts'] / (a['num_experts'] + a['zero_experts']):.4f}"
            f" expected of a uniform router), {real:.4f} to routed ones: "
            f"{real * a['experts_per_tok']:.2f} real experts a token")
    return out


def cached_rows(dec, prompts, n: int, tr: dict, layers) -> tuple:
    """One generation outside the window, the prefill and
    ``PROBE_STEPS`` decode steps (fewer where the traffic's generations
    are shorter): ``(ids, rows)``, the first ``n`` sequences' prompt
    and the tokens fed back and, for each of ``layers``, what the ring
    was left with for them, a tuple with ``[n, positions, latent +
    rope]`` a sublayer, on the host."""
    out = dec.generate(prompts, min(PROBE_STEPS + 1, tr["new_tokens"]),
                       prefill=True, token_chunk=tr["token_chunk"])
    ids = np.asarray(out)[:n, :-1]
    rows = {}
    for l in layers:
        fmt = dec.state_formats[l]
        # [stage, group, sequence, position, column]: one chip's one
        # group holds every sequence
        rows[l] = tuple(np.asarray(
            dec.state[key][l][0, 0, :n, :ids.shape[1], :fmt.width]
            .astype(np.float32)) for key in fmt.keys)
    dec.state = None
    return ids, rows


def reference_extras(params, seqs, ref_cfg: dict, **control) -> list:
    """What the plain reference's forward of ``seqs`` [n, t] hands back
    a double layer (``chipbench/reference/longcat_flash.py::forward``):
    the rows a sequence would keep in each sublayer, the chosen
    columns, their weights, the normed stream they were chosen on and
    the shortcut's output.  ``control`` is the controls' (the reference
    under another rule)."""
    ref = importlib.import_module(ref_cfg["module"])
    return ref.forward(params, seqs, **ref_cfg["args"], **control,
                       keep=("chosen", "weights", "ffn_in", "shortcut",
                             "rows", "rows_1"))[1]


def program_agreement(graph, params, seqs, want: list) -> dict:
    """The program's own blocks on ``seqs`` [n, t] against ``want``
    (:func:`reference_extras` of the same tokens), a double layer an
    entry: ``shares`` (the share of the reference's choices that the
    program's full-sequence forward — ``apply_with_rows``, what its
    prefill runs, in the type of ``params``, on its own stream — makes
    too), ``weights`` (``rms_err`` of the program's ``route`` on the
    reference's normed stream against the reference's weights, over the
    tokens whose choices agree), ``shortcut`` (``rms_err`` of the
    program's ``shortcut`` on that stream against the reference's
    ``s``) and ``rows`` (``rel_err`` of the rows the program's forward
    hands its caches against the reference's, the larger of the two
    sublayers')."""
    import jax
    import jax.numpy as jnp

    nodes = graph.nodes
    op = nodes["block_0"].op        # every block is one op

    @jax.jit
    def layer(p, x):
        sown: dict = {}
        y, rows = op.apply_with_rows(p, x, sow=sown)
        return y, rows, sown["moe.chosen"].reshape(x.shape[:2] + (-1,))

    route, shortcut = jax.jit(op.route), jax.jit(op.shortcut)

    def by_column(ids, values):
        order = np.argsort(ids, -1)
        return (np.take_along_axis(ids, order, -1),
                np.take_along_axis(values, order, -1))

    x = jax.jit(nodes["embeddings"].op.apply)(params["embeddings"], seqs)
    out = {"shares": [], "weights": [], "shortcut": [], "rows": []}
    for i, ex in enumerate(want):
        p = params[f"block_{i}"]
        x, rows, got = layer(p, x)
        out["rows"].append(max(
            rel_err(np.asarray(r.astype(jnp.float32)), ex[key])
            for r, key in zip(rows, ("rows", "rows_1"))))
        same = (np.asarray(got)[..., :, None]
                == ex["chosen"][..., None, :]).any(-2)
        out["shares"].append(float(same.mean()))
        h = jnp.asarray(ex["ffn_in"]).reshape(-1, ex["ffn_in"].shape[-1])
        eid, w = route(p, h)
        eid, w = by_column(np.asarray(eid), np.asarray(w, np.float32))
        ref_id, ref_w = by_column(
            ex["chosen"].reshape(eid.shape), ex["weights"].reshape(w.shape))
        agree = (eid == ref_id).all(-1)
        out["weights"].append(rms_err(w[agree], ref_w[agree]))
        out["shortcut"].append(rms_err(
            np.asarray(shortcut(p, h)),
            ex["shortcut"].reshape(h.shape)))
    return out


def check(state, ctx):
    tr, cfg = state["traffic"], state["config"]
    plen, n = tr["prompt_len"], tr["check_sequences"]
    dec = state.pop("dec", None)
    # the block upstream of every routed expert, and the last
    probed = tuple(dict.fromkeys((0, len(dec.memory) - 1)))
    ids, got = cached_rows(dec, state["prompts"], n, tr, probed)
    # the reference upcasts a layer at a time beside whatever the chip
    # still holds: let the decoder's weights and rows go first
    del dec
    gc.collect()
    # the first ``check_tokens`` generated tokens are judged: the
    # reference runs every position of every judged sequence in float32
    state["sample"] = state["sample"][:, :plen + tr["check_tokens"]]
    ok, detail = base.check(state, ctx)
    if "worst_logit_gap_share" not in detail:
        return ok, detail
    detail["tolerance"] = GAP_TOL               # judged at this file's limits
    seqs = state["sample"][:n, :-1]
    # (a short generation may judge fewer tokens than the probe fed back)
    ids = ids[:, :seqs.shape[1]]
    if not np.array_equal(ids, seqs[:, :ids.shape[1]]):
        return False, dict(detail, error="the probe's generation is not "
                           "the window's: greedy tokens differ")
    want = reference_extras(state["params"], seqs, cfg["reference"])
    agreement = program_agreement(state["graph"], state["params"], seqs,
                                  want)
    latent = {}
    for l in probed:
        for sub, key in enumerate(("rows", "rows_1")):
            # a row depends on no later token: the longer forward's serve
            g, w = (a[:, :ids.shape[1]] for a in (got[l][sub], want[l][key]))
            latent[f"{l}.{sub}"] = {
                "prompt": rel_err(g[:, :plen], w[:, :plen]),
                "generated": rel_err(g[:, plen:], w[:, plen:])}
    first = max(max(parts.values()) for name, parts in latent.items()
                if name.startswith("0."))
    last = max(max(parts.values()) for name, parts in latent.items()
               if name.startswith(f"{probed[-1]}."))
    shares, weights = agreement["shares"], agreement["weights"]
    shortcut = agreement["shortcut"]
    detail.update(router_agreement_share=min(shares),
                  router_agreement_by_layer=[round(s, 5) for s in shares],
                  router_tolerance=ROUTER_TOL,
                  router_weights_rms_err=max(weights),
                  router_weights_rms_err_by_layer=[
                      float(f"{e:.3g}") for e in weights],
                  router_weights_tolerance=WEIGHTS_TOL,
                  shortcut_rms_err=max(shortcut),
                  shortcut_rms_err_by_layer=[
                      float(f"{e:.3g}") for e in shortcut],
                  shortcut_tolerance=SHORTCUT_TOL,
                  zero_choice_share=float(np.mean([
                      (ex["chosen"] >= cfg["model_args"]["num_experts"])
                      .mean() for ex in want])),
                  forward_rows_rel_err_by_layer=[
                      round(e, 5) for e in agreement["rows"]],
                  latent_probe_rel_err=last,
                  latent_probe_rel_err_upstream=first,
                  latent_probe_rel_err_by_part={
                      name: {k: round(v, 5) for k, v in parts.items()}
                      for name, parts in latent.items()},
                  latent_probe_tolerance=LATENT_TOL,
                  latent_probe_upstream_tolerance=LATENT_TOL_FIRST)
    return (detail["worst_logit_gap_share"] <= GAP_TOL
            and min(shares) >= ROUTER_TOL
            and max(weights) <= WEIGHTS_TOL
            and max(shortcut) <= SHORTCUT_TOL
            and last <= LATENT_TOL
            and first <= LATENT_TOL_FIRST), detail


close = base.close
