"""Driver ``batch_decode_hybrid_ssm``: ``batch_decode``'s offline batch
through ``PipelinedDecoder``, for the family whose layers keep two
*kinds* of memory — state-space layers a convolution window and a state
of fixed size, attention layers a KV cache (``models.jamba``).

The window, the readings, ``tokens_per_s`` and the token check are
``chipbench/drivers/batch_decode.py``'s own functions, called from here,
as the three newer drivers call them; the weights are made where and
how ``batch_decode_retention`` makes them (drawn on the chip a node at a
time, kept on the *host*; one program a kind of node here), and the head
is the embedding's table.  This
file has the set-up, what the layers add to ``counters`` and the second
half of ``check``.

``check`` holds the program to the plain reference three times:

* the generated tokens, by ``batch_decode``'s measure at this file's
  limit, on ``check_sequences`` sequences over the first
  ``check_tokens`` generated tokens;
* **the state the decode steps left**: one more generation outside the
  window, the prefill and ``STATE_STEPS`` decode steps.
  ``check_sequences`` sequences' ``H`` and window of every Mamba layer
  are fetched, brought to the layout-free ``[E, N]`` / ``[d_conv - 1,
  E]`` form (``ops/ssm.py::dense``) and compared with the reference's
  own recurrence over the prompt and the tokens the program fed back
  (``chipbench/reference/jamba.py::states``) by ``rel_err``: the first
  layer, whose inputs are one norm and one product away from the
  reference's, at a limit of its own.  A window read one position off
  fails it (``scripts/hybrid_ssm_controls.py``);
* **the long memory** (:func:`long_memory_error`): the model's seeded
  steps and decays give most channels a memory of tens of positions,
  under which a state kept below float32 costs little a comparison
  could see.  What the configuration's float32 is for is a sum over
  hundreds of positions under a decay near 1, so the check drives the
  program's own format (its buffers, ``ssm_scan``, ``ssm_step``) at the
  cell's geometry through a prefill of ``PROBE_STEPS`` positions and as
  many decode steps of float32 inputs with ``dt A`` in ``[-PROBE_DECAY,
  0)`` and holds its outputs to the reference's recurrence and its last
  ``H`` to the explicit sum.

Counters added: ``decode.ssm.updates`` over the window (sequences x
Mamba layers of every valid decode step), the program's gauges
``decode.ssm.state_bytes`` / ``decode.ssm.conv_bytes`` /
``decode.cache.full_bytes`` / ``decode.weights.own_bytes`` (as
``ssm_state_bytes`` ...), ``mamba_layers``, ``prefill_tokens``,
``prefill_piece_rows`` and ``max_len``.

Traffic file keys: as ``batch_decode``, and ``check_tokens``.
Configuration file keys: ``model_args`` (for ``defer_tpu.models.jamba``)
and ``reference`` (no leaf is scaled: the configuration has no
``init_gain``).
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

from chipbench.agreement import rel_err
from chipbench.drivers import batch_decode as base

#: this configuration's limit on the worst logit gap share (the measure is
#: ``batch_decode``'s: how far the reference's logit of the program's token
#: sits under the reference's best, over the position's spread; a token
#: no better than a random one reads ~1).  Set between two readings on
#: the v5e (PR 38, PERF.md section 6; a reading is the worst of 2 x 512
#: tokens, as a run judges them).  The largest the program gave over
#: the builder's 16 readings of 16 seeds: 0.0517 (0.0249 at the
#: least; 90-93% of its tokens are the reference's own argmax).  The
#: reference itself with every product's operands rounded to
#: float8_e4m3, the nearest precision below the stated one: 1.634 at the
#: least over 6 seeds (1.741 at the most; none of its tokens the
#: float32 run's argmax): not correct.  0.27 is 5.2x over the one and
#: 6.1x under the other, near their geometric mean.
GAP_TOL = 0.27
#: decode steps behind the prefill before the state is read back
STATE_STEPS = 64
#: the most a Mamba layer's state after those steps (``H`` or the
#: window, in the layout-free form) may differ from the reference's, as
#: ``rel_err`` (largest difference over largest entry), in the layer
#: where it differs most.  Set the same way (a reading is the worst of
#: 26 layers, 2 sequences of 256 + 64 tokens): the program's largest
#: over the same readings 0.0924 (0.0495 at the least; it grows with
#: depth: bfloat16 activations a layer further from the float32
#: stream), the float8_e4m3-input reference's least 0.503 (2.23 at the
#: most): not correct.  0.21 is 2.3x over the one and 2.4x under the
#: other.  A window read one position off reads 1.17-1.24 in every
#: layer: not correct.  What this limit cannot see is a state kept in
#: bfloat16: the reference with its own ``H`` rounded to bfloat16 after
#: every position reads 0.005-0.042, *under* what the program's
#: bfloat16 activations cost; that is the probe's to fail, below.
STATE_TOL = 0.21
#: the same in the first layer alone, whose inputs are one norm and one
#: bfloat16 product away from the reference's: the program's largest
#: 0.0076 (0.0032 at the least), the float8_e4m3-input reference's least
#: 0.503.  0.03 is 4.0x over the one and 17x under the other.
STATE_TOL_FIRST = 0.03
#: the long-memory probe: positions of its prefill and as many decode
#: steps, and the range of ``dt A`` (a memory of ~2 / PROBE_DECAY = 500
#: positions)
PROBE_STEPS = 2048
PROBE_DECAY = 0.004
#: the most the probe's outputs and its last ``H`` may differ from the
#: reference's, as ``rel_err``, each.  Set from two readings on the v5e
#: (PR 38, PERF.md section 6; 21 seeds, the control 6): the program's
#: largest 1.91e-3 (``H`` against the explicit sum: the chip's ``exp``
#: of a step near 0 reads a little low, and the recurrence multiplies
#: 4096 of them where the sum takes one ``exp`` of their total; its
#: outputs equal the reference's own recurrence to the last bit: 0.0);
#: the same kernels with ``H`` rounded to bfloat16 after the prefill and
#: after each step, the nearest below the float32 the configuration
#: states, at the least 0.0983 (``y``) and 0.1255 (``H``): not correct,
#: by both parts.  0.012 is 6.3x over the program's largest and 8.2x
#: under the control's least.
MEMORY_TOL = 0.012
UPDATES = "decode.ssm.updates"
GAUGES = ("decode.ssm.state_bytes", "decode.ssm.conv_bytes",
          "decode.cache.full_bytes", "decode.weights.own_bytes")


def make_weights(graph, seed: int, dtype) -> dict:
    """The program's initialiser from the seed, a node at a time on the
    chip, each fetched to the host as it is made and cast to ``dtype``
    in the same program (as ``batch_decode_retention.make_weights``
    keeps them), then the head tied to the embedding, as a tied
    checkpoint loads.  Here a node's draw is *that node's own* ``init``
    under the key ``graph.init`` would hand it (its split of the seed's
    key by the node's place), jitted once a kind of node: 26 Mamba
    layers share one program, where a trace of the whole ``graph.init``
    a node (31 x 31 inits) took most of a minute of set-up.  The tree is
    ``graph.init``'s own, leaf for leaf (``chipbench/tests`` hold it to
    that)."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.models.cohere_moe import tie_head

    # seeds run to a little over 2**31: fold into the key's 32-bit range
    key = jax.random.key(int(seed) % (2 ** 31 - 1))
    keys = jax.random.split(key, max(len(graph.nodes), 1))
    programs: dict = {}

    def cast(a):
        return a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) \
            else a

    params = {}
    for k, node in zip(keys, graph.nodes.values()):
        if node.param_spec is None:
            continue
        in_specs = tuple(graph.out_spec(i) for i in node.inputs)
        # like layers are equal ops on equal inputs
        kind = (node.op, tuple((s.shape, s.dtype) for s in in_specs))
        if kind not in programs:
            programs[kind] = jax.jit(
                lambda k, op=node.op, in_specs=in_specs: jax.tree.map(
                    cast, op.init(k, in_specs)))
        params[node.name] = jax.device_get(programs[kind](k))
    return tie_head(params)


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    if ctx.cell.chips != 1:
        raise ValueError("batch_decode_hybrid_ssm's state check reads one "
                         "chip's buffers; give the cell one chip")
    graph = models.jamba(**cfg["model_args"])
    dtype = jnp.dtype(tr["compute_dtype"])
    with ctx.span("weights"):
        params = make_weights(graph, ctx.seed, dtype)
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


def measure(state, seconds, ctx):
    from chipbench.roofline_hybrid_ssm import layer_kinds
    from defer_tpu.obs import REGISTRY

    tr, args = state["traffic"], state["config"]["model_args"]
    updates = REGISTRY.counter(UPDATES)
    before = updates.n
    out = base.measure(state, seconds, ctx)
    done = updates.n - before
    mamba, _ = layer_kinds(args)
    counters = out["counters"]
    counters.update({UPDATES: done, "mamba_layers": mamba,
                     "prefill_tokens": tr["batch"] * tr["prompt_len"],
                     "max_len": tr["max_len"]})
    counters.update({name.split(".", 1)[1].replace(".", "_"):
                     float(REGISTRY.gauge(name).value) for name in GAUGES})
    if "dec" in state:
        # sequences a piece of the prefill holds (a scan's call is a
        # piece's)
        counters["prefill_piece_rows"] = state["dec"]._prefill_rows(
            tr["prompt_len"])
    layer_steps = tr["batch"] * mamba
    out["notes"].append(
        f"{UPDATES} {done} = {layer_steps} (sequences x Mamba layers) x "
        f"{done / layer_steps:.2f} valid decode steps")
    return out


def decoded_states(dec, prompts, n: int, tr: dict) -> tuple:
    """One generation outside the window, the prefill and
    ``STATE_STEPS`` decode steps (fewer where the traffic's generations
    are shorter): ``(ids, states)``, the first ``n`` sequences' prompt
    and the tokens fed back (all the state has absorbed: the last token
    handed out was never an input), and what the ring was left with for
    them, a layer an entry: ``(H [n, E, N], window [n, d_conv - 1,
    E])`` on the host in the layout-free form, None for a layer that
    keeps no state-space state."""
    from defer_tpu.ops.ssm import dense

    out = dec.generate(prompts, min(STATE_STEPS + 1, tr["new_tokens"]),
                       prefill=True, token_chunk=tr["token_chunk"])
    states = []
    for l, kind in enumerate(dec.memory):
        # [stage, group, ...]: one chip's one group holds every
        # sequence, and its blocks are the model's
        states.append(dense(np.asarray(dec.state["h"][l][0, 0, :n]),
                            np.asarray(dec.state["conv"][l][0, 0, :, :n]))
                      if kind == "ssm" else None)
    dec.state = None
    return np.asarray(out)[:n, :-1], states


def state_errors(got: list, params, ids, ref_cfg: dict, *,
                 state_dtype=None) -> dict:
    """For each Mamba layer (by its index), how far the program's state
    is from the plain reference's over the same tokens: the larger of
    ``H``'s and the window's ``rel_err``.  ``state_dtype`` is the
    control's: the reference's own state rounded to that type after
    every position."""
    ref = importlib.import_module(ref_cfg["module"])
    extra = {} if state_dtype is None else {"state_dtype": state_dtype}
    want = ref.states(params, ids, **ref_cfg["args"], **extra)
    return {l: max(rel_err(g[0], np.asarray(w[0])),
                   rel_err(g[1], np.asarray(w[1])))
            for l, (g, w) in enumerate(zip(got, want)) if g is not None}


def long_memory_error(fmt, seed: int, ref, *, held=None,
                      steps: int = PROBE_STEPS, sequences: int = 2) -> dict:
    """The program's format ``fmt`` (its buffers, its two kernels)
    through a prefill of ``steps`` positions and ``steps`` decode steps
    of ``sequences`` seeded float32 sequences whose ``dt A`` lies in
    ``[-PROBE_DECAY, 0)``, from an empty memory, against the
    reference's recurrence (the outputs: ``y_prefill``, ``y_decode``)
    and explicit sum (the last state: ``H``), as ``rel_err``.  ``B``
    and ``C`` have unit mean square, as the blocks' norms leave them.
    ``held`` is the control: a type the state is rounded to after the
    prefill and after every step (by ``reduce_precision``)."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.ops.ssm import dense

    e, n, b, t = fmt.channels, fmt.states, sequences, 2 * steps
    rng = np.random.default_rng(seed)

    def normed(*shape):
        a = rng.standard_normal(shape, dtype=np.float32)
        return a / np.sqrt((a * a).mean(-1, keepdims=True))

    a_mat = -rng.uniform(0.25, 1.0, (n, e)).astype(np.float32)
    dt = rng.uniform(0.0, PROBE_DECAY, (b, t, e)).astype(np.float32)
    x = rng.standard_normal((b, t, e), dtype=np.float32)
    dt, x, bm, cm, a_mat = (jnp.asarray(v) for v in (
        dt, x, normed(b, t, n), normed(b, t, n), a_mat))

    def rounded(layer):
        if held is None:
            return layer
        kind = jnp.finfo(held)
        return dict(layer, h=jax.lax.reduce_precision(
            layer["h"], kind.nexp, kind.nmant))

    def run(dt, x, bm, cm, a_mat):
        layer = fmt.layer(fmt.zeros(b, 1), 0)
        y0, layer = fmt.prefill(dt[:, :steps], x[:, :steps], bm[:, :steps],
                                cm[:, :steps], a_mat, layer,
                                fmt.prefill_slot(True, 0))

        def step(layer, xs):
            y, layer = fmt.step(*xs, a_mat, layer, group=0)
            return rounded(layer), y

        layer, ys = jax.lax.scan(step, rounded(layer), tuple(
            v[:, steps:].swapaxes(0, 1) for v in (dt, x, bm, cm)))
        return y0, ys.swapaxes(0, 1), layer["h"]

    y0, y1, h = jax.jit(run)(dt, x, bm, cm, a_mat)
    with jax.default_matmul_precision("highest"):
        want_y, _ = jax.jit(ref.selective_scan)(dt, x, bm, cm, a_mat.T)
        want_h = jax.jit(ref.explicit_state)(dt, x, bm, a_mat.T)
    # behind the ring's group axis, where the format has one
    h = np.asarray(h if fmt.groups is None else h[0])
    got_h, _ = dense(h, np.zeros((fmt.d_conv - 1, b, e), np.float32))
    want_y = np.asarray(want_y)
    return {"y_prefill": rel_err(y0, want_y[:, :steps]),
            "y_decode": rel_err(y1, want_y[:, steps:]),
            "H": rel_err(got_h, np.asarray(want_h))}


def check(state, ctx):
    tr, cfg = state["traffic"], state["config"]
    plen, n = tr["prompt_len"], tr["check_sequences"]
    dec = state.pop("dec", None)
    ids, got = decoded_states(dec, state["prompts"], n, tr)
    fmt = next(f for f, kind in zip(dec.state_formats, dec.memory)
               if kind == "ssm")
    # the reference upcasts a layer at a time beside whatever the chip
    # still holds: let the decoder's weights and state go first
    del dec
    gc.collect()
    # the first ``check_tokens`` generated tokens are judged: the
    # reference runs every position of every judged sequence in float32
    state["sample"] = state["sample"][:, :plen + tr["check_tokens"]]
    ok, detail = base.check(state, ctx)
    if "worst_logit_gap_share" not in detail:
        return ok, detail
    detail["tolerance"] = GAP_TOL               # judged at this file's limits
    errors = state_errors(got, state["params"], ids, cfg["reference"])
    memory = long_memory_error(
        fmt, ctx.seed, importlib.import_module(cfg["reference"]["module"]))
    first = errors[min(errors)]
    detail.update(state_rel_err=max(errors.values()),
                  state_rel_err_by_layer={
                      l: round(e, 5) for l, e in errors.items()},
                  state_tolerance=STATE_TOL,
                  first_layer_state_tolerance=STATE_TOL_FIRST,
                  long_memory_rel_err=max(memory.values()),
                  long_memory_rel_err_by_part=memory,
                  long_memory_tolerance=MEMORY_TOL)
    return (detail["worst_logit_gap_share"] <= GAP_TOL
            and max(errors.values()) <= STATE_TOL
            and first <= STATE_TOL_FIRST
            and max(memory.values()) <= MEMORY_TOL), detail


close = base.close
