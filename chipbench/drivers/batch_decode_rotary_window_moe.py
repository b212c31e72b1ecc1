"""Driver ``batch_decode_rotary_window_moe``: ``batch_decode``'s offline
batch through ``PipelinedDecoder``, for the family whose layers keep
two lengths of memory *and rotate by two tables* — window layers a ring
buffer under plain RoPE, full layers every position under YaRN — and
hold all their routed experts (``models.mellum``).

The window, the readings, ``tokens_per_s`` and the token check are
``chipbench/drivers/batch_decode.py``'s own functions, called from here;
the weights are made where and how ``batch_decode_retention`` makes them
(drawn on the chip a node at a time, kept on the *host*: 7.6 GB of bf16
weights and 4.2 GB of cache buffers leave no room for a second tree on
the chip); the router's agreement and the window probe are
``batch_decode_window_moe``'s, at this cell's geometry.  This file has
the set-up, what the layers add to ``counters``, the rotation probe and
``check``'s limits.

``check`` holds the program to the plain reference four times:

* (a) the generated tokens, by ``batch_decode``'s measure at this
  file's limit, on ``check_sequences`` sequences over the first
  ``check_tokens`` generated tokens;
* (b) **the router**: the share of the reference's 8 choices a token a
  layer (over all 64 experts) that the program's own blocks make on the
  same tokens, in the layer where they agree least;
* (c) **the window** (``batch_decode_window_moe.window_probe``): the
  banded flash kernel over a prompt of nearly three windows,
  ``write_prefix`` into a ring buffer, decode writes and the attention
  kernel across the next wrap, on planted keys, one leaving the window
  inside the decode steps — at 4 KV heads of 8 queries and a window of
  1024 — and the same through a format without a window;
* (d) **the rotation** (:func:`rotation_probe`): seeded random keys give
  near-flat attention, and a full layer turned by the plain table, a
  YaRN ramp a pair off, a missing attention factor or the wrong pairing
  would hide inside the token limit.  So the check hands the program's
  *own* rotation (``MellumBlock.rotate`` of a window layer and of a
  full one) unrotated queries and keys at positions up to ``max_len -
  1``, writes the turned keys through the layer's own format
  (``write_prefix``, then decode writes) and attends with its kernel —
  on seeded inputs where planted keys at many distances carry a
  query's weight, so that each one's share of the softmax is set by
  ``cos((p - s) f_j)`` over every pair ``j`` — against the reference's
  own rotation and masked softmax.

Counters added: the program's ``decode.moe.*`` sums over the window,
``experts_hit_share`` (experts hit a layer a step over the number of
experts), the gauges ``decode.cache.window_bytes`` / ``.full_bytes`` /
``.window_positions`` as ``cache_*``, ``decode.cache.full_rows_read`` /
``.window_rows_read`` as ``cache_*`` too (the newest step's),
``prefill.flash.grid_steps`` / ``.live_steps`` as ``prefill_flash_*``,
``prefill_tokens`` and ``prefill_piece_rows``.

Traffic file keys: as ``batch_decode``, and ``check_tokens``.
Configuration file keys: ``model_args`` (for
``defer_tpu.models.mellum``), ``reference``, and optionally
``init_gain``.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

from chipbench.drivers import batch_decode as base
from chipbench.drivers.batch_decode_retention import make_weights
from chipbench.drivers.batch_decode_window_moe import (router_agreement,
                                                       window_probe)

#: this configuration's limit on the worst logit gap share (the measure
#: is ``batch_decode``'s: how far the reference's logit of the program's
#: token sits under the reference's best, over the position's spread; a
#: token no better than a random one reads ~1).  Set between two
#: readings on the v5e (PR 55, PERF.md section 6; a reading is the worst
#: of 2 x 256 tokens behind 24576 of prompt, as a run judges them).  The
#: configuration's ``init_gain`` makes attention sharp (scores spread by
#: ~4 over a window, ~6.5 in a full layer), so a bfloat16 rounding turns
#: near-ties between cached positions, over half the tokens are not the
#: reference's own argmax, and the streams part layer by layer: over 14
#: readings of 14 seeds the program gave 0.240-0.354 (the reference with
#: its products' operands rounded to *bfloat16* reads 0.351: the
#: program's gap is its precision's).  The reference with every
#: product's operands rounded to float8_e4m3, the nearest precision
#: below the stated one, read *in the program's place at the cell's
#: lengths* (``scripts/rotary_window_moe_controls.py --model``: 2
#: seeded sequences of 24831 positions, the float8 run's tokens judged
#: behind 24576 of context by ``logit_gaps``'s measure): 1.055 at the
#: least over 3 seeds (1.272 at the most; at 2048 positions of one
#: sequence, all judged, 1.405-1.414): not correct.  0.6 is their
#: geometric mean: 1.7x over the one and 1.8x under the other.  It is
#: also under what wrong rows far back cost: with every position more
#: than 8192 before the judged ones drawn anew (past the reach of six
#: stacked windows: only a full layer carries it) 98% of the judged
#: tokens change and the worst reads 0.949 — at q and k gains of 1
#: (bfloat16 0.039) that is 0.095, which no limit over the precision's
#: own reading would see (PERF.md section 6).
GAP_TOL = 0.6
#: the least share of the reference's expert choices (8 a token, over
#: all 64 experts) that the program's own blocks must make on the same
#: tokens, in the layer where they agree least (as
#: ``batch_decode_moe.ROUTER_TOL``).  Set the same way: the program's
#: least over 14 seeds 0.8113 (pairs of 24831-token sequences; by layer
#: 0.996, 0.990, 0.980, 0.955, 0.934, 0.909, 0.879, 0.813: the streams
#: part as the sharp attention of each layer turns its near-ties, most
#: behind the two full layers; the bfloat16-input reference 0.820), the
#: float8_e4m3-input reference's most 0.3034 over 3 seeds at the cell's
#: lengths (by layer 0.79 ... 0.30; at 2048 positions 0.2997): not
#: correct.  0.6 leaves a disagreement of 0.4: 2.1x the one's 0.189,
#: 1.74x under the other's 0.697.  A router that takes the wrong
#: experts shares about 8 / 64.
ROUTER_TOL = 0.6
#: the most any part of the window probe may differ from the reference's
#: masked softmax, as ``rel_err`` (largest difference over largest
#: entry; ``batch_decode_window_moe.PROBE_TOL``'s measure, at this
#: cell's geometry: 4 KV heads of 128, 8 queries a KV head, a window of
#: 1024 — a prompt of 3047, 50 decode steps, the wrap at step 25).  Set
#: between two readings on the v5e (PR 55; a reading is the worst of the
#: probe's four parts).  The largest the program gave over 13 readings
#: of 13 seeds (bfloat16 rows and queries, f32 accumulation): 0.0087.
#: The same kernels handed inputs rounded to float8_e4m3: 0.0678 at the
#: least over 5 seeds (0.162 at the most): not correct.  0.025 is 2.9x
#: over the one and 2.7x under the other.  It also fails, by every part
#: they touch, a window off by one (held to a reference of 1023 or
#: 1025: 0.24-0.39 in the flash kernel, 0.42-0.64 in the decode steps)
#: and a decode row written one row off (0.54-0.71 over the ring
#: buffer, 0.24-0.35 over the full layer's rows).
WINDOW_TOL = 0.025
#: the most either kind's rotation probe may differ from the reference's
#: own rotation and masked softmax, as :func:`rms_err`.  Set between two
#: readings on the v5e (PR 55; a reading is the larger of the two kinds',
#: 32 decode steps of 32 heads at positions 28640-28671).  The largest
#: the program gave over 11 readings of 11 seeds: 0.0247 (the full
#: layer's 0.0117-0.0247, the window layer's 0.0028-0.0058).  The same
#: rotation, format and kernels handed inputs rounded to float8_e4m3:
#: 0.0829 at the least over 5 seeds (0.0946 at the most; the full
#: layer's): not correct.  0.045 is their geometric mean: 1.8x over the
#: one and 1.8x under the other.  It also fails every wrong rotation
#: (five seeds each; the reference made wrong, the program's fault seen
#: from the other side): a full layer turned by the plain table 0.89-
#: 1.15, YaRN's ramp a pair up 0.85-1.06 or down 0.80-1.02, no attention
#: factor 0.233-0.288, interleaved pairs 1.19-1.29 (a window layer's
#: 0.34-0.68), a window layer under YaRN's table 0.223-0.350.
ROTATION_TOL = 0.045
MOE_COUNTERS = ("decode.moe.assignments", "decode.moe.experts_hit",
                "decode.moe.load_max")
GAUGES = {"decode.cache.window_bytes": "cache_window_bytes",
          "decode.cache.full_bytes": "cache_full_bytes",
          "decode.cache.window_positions": "cache_window_positions",
          "decode.cache.full_rows_read": "cache_full_rows_read",
          "decode.cache.window_rows_read": "cache_window_rows_read",
          "prefill.flash.grid_steps": "prefill_flash_grid_steps",
          "prefill.flash.live_steps": "prefill_flash_live_steps"}
#: decode steps of the rotation probe (the positions ``max_len - steps
#: .. max_len - 1``), and how many planted keys a query sees
PROBE_STEPS, PROBE_PLANTED = 32, 16
#: how far a probe's queries lean towards the planted direction, and
#: the size of the other keys: a planted key scores ``~ lean * sqrt(hd)
#: * (the share of pairs still in phase)`` where the others score ``~
#: N(0, (noise * lean) ** 2)``, so that the planted keys hold a query's
#: weight over tens of thousands of others, and their shares among
#: themselves are the rotation's
PROBE_LEAN, PROBE_NOISE = 3.0, 0.25


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    graph = models.mellum(**cfg["model_args"])
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
    from defer_tpu.obs import REGISTRY

    tr, args = state["traffic"], state["config"]["model_args"]
    before = _moe_counts()
    out = base.measure(state, seconds, ctx)
    moe = {name: n - before[name] for name, n in _moe_counts().items()}
    counters = out["counters"]
    counters.update(moe, prefill_tokens=tr["batch"] * tr["prompt_len"],
                    max_len=tr["max_len"])
    if "dec" in state:
        # sequences a piece of the prefill holds (the flash kernels'
        # calls are a piece's)
        counters["prefill_piece_rows"] = state["dec"]._prefill_rows(
            tr["prompt_len"])
    counters.update({key: float(REGISTRY.gauge(name).value)
                     for name, key in GAUGES.items()})
    # one (layer, step) routes rows x experts_per_tok choices
    layer_steps = moe["decode.moe.assignments"] / (
        tr["batch"] * args["experts_per_tok"])
    if layer_steps:
        hit = moe["decode.moe.experts_hit"] / layer_steps
        counters["experts_hit_share"] = hit / args["num_experts"]
        out["notes"].append(
            f"experts hit a layer a step {hit:.2f} of "
            f"{args['num_experts']}; largest group "
            f"{moe['decode.moe.load_max'] / layer_steps:.2f} rows "
            f"({layer_steps:.0f} layer-steps); the newest step read "
            f"{counters['cache_full_rows_read']:.0f} full-layer rows and "
            f"{counters['cache_window_rows_read']:.0f} window rows")
    return out


def rms_err(got, want) -> float:
    """Root mean square of ``got - want`` over that of ``want``: the
    rotation probe's measure.  Its outputs are softmax averages over a
    few planted keys; where two of them weigh nearly alike, one
    bfloat16 rounding of a score moves a whole output row, so the
    *largest* entry's error (``rel_err``) has a long tail from seed to
    seed (0.034-0.134 over five seeds on the v5e, where float8 inputs
    read 0.21 at the least), while a wrong rotation moves every row: the
    mean over all rows tells them apart."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.sqrt(np.square(got - want).mean()
                         / max(float(np.square(want).mean()), 1e-12)))


def rotation_inputs(seed: int, heads: int, kv: int, hd: int, length: int,
                    steps: int, sequences: int, planted):
    """Seeded float32 unrotated ``q`` [b, steps, heads, hd] (the queries
    of the last ``steps`` positions), ``k`` / ``v`` [b, length, kv, hd].
    Every query of a KV head's group leans :data:`PROBE_LEAN` times one
    way (a direction a KV head, entries ~1, plus noise); the keys at
    ``planted`` are that direction (plus a tenth of noise), all others
    noise of size :data:`PROBE_NOISE`."""
    rng = np.random.default_rng(seed)
    b, g = sequences, heads // kv

    def normal(*shape):
        return rng.standard_normal(shape, dtype=np.float32)

    u = normal(b, 1, kv, hd)
    u /= np.sqrt((u * u).mean(-1, keepdims=True))       # entries ~1
    q = normal(b, steps, kv, g, hd) + PROBE_LEAN * u[:, :, :, None]
    k = PROBE_NOISE * normal(b, length, kv, hd)
    k[:, planted] = u + 0.1 * normal(b, len(planted), kv, hd)
    return q.reshape(b, steps, heads, hd), k, normal(b, length, kv, hd)


def rotation_probe(seed: int, op, *, d_model: int, positions: int, dtype,
                   ref, freqs, c: float, pairing: str = "half",
                   steps: int = PROBE_STEPS, sequences: int = 1,
                   inputs=None) -> float:
    """One layer's rotation, the program's against the reference's:
    ``op`` (a ``MellumBlock``) turns unrotated queries and keys
    (:func:`rotation_inputs`) to their positions — the keys of positions
    ``0 .. positions - steps - 1`` in one call, as a prompt's, then a
    position a step —, the turned keys go through ``op``'s own memory
    format (``write_prefix``, then ``write_position``; a window layer's
    is a ring buffer) and each step's query attends with the format's
    kernel; the reference turns the same inputs by ``freqs`` / ``c`` /
    ``pairing`` (``ref.rotate``) and attends by its masked softmax.
    :func:`rms_err` of the steps' outputs, the last ``steps`` positions
    up to ``positions - 1``.

    ``freqs`` / ``c`` / ``pairing`` are the reference's side: the check
    passes the layer's own (``ref.layer_rotation``), a control a wrong
    table, factor or pairing — the program's fault seen from the other
    side.  ``inputs`` (a control's too) rounds what the program is
    given to a narrower float."""
    import jax
    import jax.numpy as jnp

    heads, kv, hd = op.geometry(d_model)
    plen, b = positions - steps, sequences
    reach = positions if op.window is None else op.window
    every = max(2, reach // PROBE_PLANTED)
    planted = np.arange(every // 2, positions, every)
    q, k, v = (jnp.asarray(a) for a in rotation_inputs(
        seed, heads, kv, hd, positions, steps, b, planted))

    pos_all = jnp.arange(positions)
    with jax.default_matmul_precision("highest"):
        def reference(q, k, v):
            qr = ref.rotate(q.transpose(0, 2, 1, 3), pos_all[plen:], freqs,
                            c, pairing)
            kr = ref.rotate(k.transpose(0, 2, 1, 3), pos_all, freqs, c,
                            pairing)
            # the reference attends a query a position: the prompt's
            # are zeros, and their rows are not read
            qr = jnp.concatenate(
                [jnp.zeros((b, heads, plen, hd), qr.dtype), qr], axis=2)
            return ref.attention(qr, kr, v.transpose(0, 2, 1, 3),
                                 op.window)[:, :, plen:]

        want = np.asarray(jax.jit(reference)(q, k, v))  # [b, heads, steps, hd]

    given = (q, k, v)
    if inputs is not None:
        kind = jnp.finfo(inputs)
        given = tuple(jax.lax.reduce_precision(a, kind.nexp, kind.nmant)
                      for a in given)
    fmt = op.memory_format(d_model, positions, dtype, groups=1)

    def run(q, k, v):
        q, k, v = (a.astype(dtype) for a in (q, k, v))
        layer = fmt.layer(fmt.zeros(b, 1), 0)
        layer = fmt.write_prefix(
            layer, op.rotate(k[:, :plen], pos_all[:plen]).reshape(
                b, plen, -1),
            v[:, :plen].reshape(b, plen, -1), fmt.prefill_slot(True, 0))

        def step(layer, xs):
            pos, qt, kt, vt = xs
            at = jnp.reshape(pos, (1,))
            qt, kt = (op.rotate(a[:, None], at).reshape(b, -1)
                      for a in (qt, kt))
            slot = fmt.decode_slot(True, pos)
            layer = fmt.write_position(
                layer, fmt.rows(kt, vt.reshape(b, -1)), slot, group=0)
            return layer, fmt.attend(qt, layer, slot, group=0)

        _, ys = jax.lax.scan(step, layer, (
            pos_all[plen:], q.swapaxes(0, 1), k[:, plen:].swapaxes(0, 1),
            v[:, plen:].swapaxes(0, 1)))
        return ys                                      # [steps, b, heads*hd]

    ys = np.asarray(jax.jit(run)(*given)).reshape(steps, b, heads, hd)
    return rms_err(ys.transpose(1, 2, 0, 3), want)


def rotation_probes(seed: int, graph, *, positions: int, dtype, ref_cfg: dict,
                    **kw) -> dict:
    """:func:`rotation_probe` of the graph's first window layer and its
    first full one, each against the reference's rotation of its kind:
    ``{"window": rms_err, "full": rms_err}``."""
    ref = importlib.import_module(ref_cfg["module"])
    args = ref_cfg["args"]
    nodes, out = graph.nodes, {}
    d_model = nodes["block_0"].out_spec.shape[-1]
    for name in (nm for nm in graph.topo_order if nm.startswith("block_")):
        op = nodes[name].op
        if op.kind in out:
            continue
        freqs, c = ref.layer_rotation(
            "sliding_attention" if op.window is not None
            else "full_attention", head_dim=args["head_dim"],
            theta=args["theta"], yarn=args["yarn"])
        out[op.kind] = rotation_probe(
            seed, op, d_model=d_model, positions=positions, dtype=dtype,
            ref=ref, freqs=freqs, c=c, **kw)
    return out


def check(state, ctx):
    import jax.numpy as jnp

    tr, cfg = state["traffic"], state["config"]
    # the reference upcasts a layer at a time beside whatever the chip
    # still holds: let the decoder's weights and caches go first
    state.pop("dec", None)
    gc.collect()
    plen, n = tr["prompt_len"], tr["check_sequences"]
    # the first ``check_tokens`` generated tokens are judged: the
    # reference runs every position of every judged sequence in float32
    state["sample"] = state["sample"][:, :plen + tr["check_tokens"]]
    ok, detail = base.check(state, ctx)
    if "worst_logit_gap_share" not in detail:
        return ok, detail
    detail["tolerance"] = GAP_TOL               # judged at this file's limits
    shares = router_agreement(state["graph"], state["params"],
                              state["sample"][:n, :-1], cfg["reference"])
    args = cfg["model_args"]
    dtype = jnp.dtype(tr["compute_dtype"])
    window = window_probe(
        ctx.seed, heads=args["heads"], kv=args["kv_heads"],
        hd=args["head_dim"], window=args["window"], dtype=dtype,
        ref=importlib.import_module(cfg["reference"]["module"]))
    rotation = rotation_probes(ctx.seed, state["graph"],
                               positions=tr["max_len"], dtype=dtype,
                               ref_cfg=cfg["reference"])
    detail.update(router_agreement_share=min(shares),
                  router_agreement_by_layer=[round(s, 5) for s in shares],
                  router_tolerance=ROUTER_TOL,
                  window_probe_rel_err=max(window.values()),
                  window_probe_rel_err_by_part=window,
                  window_probe_tolerance=WINDOW_TOL,
                  rotation_probe_rms_err=max(rotation.values()),
                  rotation_probe_rms_err_by_kind=rotation,
                  rotation_probe_tolerance=ROTATION_TOL)
    return (detail["worst_logit_gap_share"] <= GAP_TOL
            and min(shares) >= ROUTER_TOL
            and max(window.values()) <= WINDOW_TOL
            and max(rotation.values()) <= ROTATION_TOL), detail


close = base.close
