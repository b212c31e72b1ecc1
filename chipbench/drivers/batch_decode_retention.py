"""Driver ``batch_decode_retention``: ``batch_decode``'s offline batch
through ``PipelinedDecoder``, for a family whose blocks keep a retention
state and not a KV cache (``models.brumby``).

The window, the readings, ``tokens_per_s`` and the token check are
``chipbench/drivers/batch_decode.py``'s own functions, called from here;
the weights are kept where and why ``batch_decode_moe`` keeps them (one
tree on the *host*, scaled by the configuration's ``init_gain``: 8.4 GB
of bf16 weights and 4.6 GB of state leave no room for a second tree on
the chip), but they are **drawn on the chip, a node at a time, and
fetched**: the initialiser draws in float32, and 4.2 B parameters at
once with the generator's own temporaries pass the one-chip machine's
40 GiB of host memory (the first chip call of PR 30 was ended for it),
while a node at a time on the host's cores takes two minutes of
set-up.  The chip holds one node's draw at a time and nothing of it
when the decoder is built.  This file has the set-up,
what the state adds to ``counters`` and the second half of ``check``.

``check`` holds the program to the plain reference three times, the
last two the new mechanism's own agreement, as router agreement is
OLMoE's:

* the generated tokens, by ``batch_decode``'s measure at this file's
  limit;
* **the state the decode steps left**: one more generation outside the
  window, the prefill and ``STATE_STEPS`` decode steps (at a memory of a
  few tokens nothing of the prefill's own state is left by then: what
  is read is what the ``retention_step`` kernel wrote).
  ``check_sequences`` sequences' ``S`` and ``z`` of every layer are
  fetched, unpacked to the layout-free ``[d, d, d]`` form
  (``ops/retention.py::dense``) and compared with the reference's
  explicit sum ``sum_u decay k k^T (x) v`` over the prompt and the
  tokens the program fed back (``chipbench/reference/brumby.py::
  states``) by ``rel_err``: the first layer, whose inputs are one norm
  and one product away from the reference's, at a limit of its own;
* **the long memory** (:func:`long_memory_error`): seeded random gates
  sit near ``sigmoid`` 1/2, so the model's own state remembers a few
  tokens, and a state kept in bfloat16 passes both state limits there
  and the token limit in 4 readings of 6 (PERF.md section 6 has the
  control's readings).  What the configuration's float32 is for is a
  sum over
  hundreds of positions under a decay near 1, so the check drives the
  program's own format (``dec.state_format``: its buffers, its
  ``retention_step``) at the cell's head geometry through
  ``PROBE_STEPS`` positions of float32 inputs with log-decays in
  ``[-PROBE_LOG_DECAY, 0)`` and holds its outputs and its last state to
  the reference's attention form and explicit sum.

Counters added: ``decode.retention.updates`` over the window (sequences
x layers of every valid decode step), ``retention_state_bytes`` (the
program's gauge ``decode.retention.state_bytes``: what the ring holds),
``retention_layers`` and ``prefill_tokens``.

Traffic file keys: as ``batch_decode``.  Configuration file keys:
``model_args`` (for ``defer_tpu.models.brumby``), ``reference``, and
optionally ``init_gain``.
"""

from __future__ import annotations

import gc
import importlib

import numpy as np

from chipbench.agreement import rel_err
from chipbench.drivers import batch_decode as base

#: this configuration's limit on the worst logit gap share (the measure is
#: ``batch_decode``'s: how far the reference's logit of the program's token
#: sits under the reference's best, over the position's spread).  Set
#: between two readings on the v5e (PR 30, PERF.md section 6; a reading
#: is the worst of 1024 tokens, 2 sequences, as a run judges them).  The
#: largest the program gave over 26 such readings of 20 seeds: 0.0275
#: (the reference itself with its products' inputs rounded to bfloat16:
#: 0.0233 at the most over 12).  The same reference with inputs rounded
#: to float8_e4m3, the nearest precision below the stated one: 0.331 at
#: the least over 12 pairs (0.426 at the most): not correct.  0.09 is
#: 3.3x over the one and 3.7x under the other, near their geometric mean.
GAP_TOL = 0.09
#: decode steps behind the prefill before the state is read back
STATE_STEPS = 64
#: the most a layer's state after those steps (``S`` or ``z``, unpacked)
#: may differ from the reference's explicit sum, as ``rel_err`` (largest
#: difference over largest entry), in the layer where it differs most.
#: Set the same way (PR 30's review round, PERF.md section 6; a reading
#: is the worst of 8 layers, 2 sequences of 1024 + 64 tokens): the
#: program's largest over 30 readings of 18 seeds 0.0370 (0.0189 at the
#: least; it grows with depth: bfloat16 activations a layer further
#: from the float32 stream), the float8_e4m3-input reference's least
#: over 12 0.373 (0.711 at the most): not correct.  0.12 is 3.2x over
#: the one and 3.1x under the other.
STATE_TOL = 0.12
#: the same in the first layer alone, whose ``k``, ``v`` and decay are
#: one norm and one bfloat16 product away from the reference's: the
#: program's largest over the same readings 0.0062 (0.0026 at the
#: least), the float8_e4m3-input reference's least 0.0695 (0.115 at the
#: most).  0.02 is 3.2x over the one and 3.5x under the other.
STATE_TOL_FIRST = 0.02
#: the long-memory probe: positions, and the log-decays' range (a memory
#: of ~2 / PROBE_LOG_DECAY = 500 positions, where the model's seeded
#: gates give ~2)
PROBE_STEPS = 2048
PROBE_LOG_DECAY = 0.004
#: the most the probe's outputs (from position 8 on: PERF.md section 6,
#: the recurrent form's first positions), its last ``S`` and its last
#: ``z`` may differ from the reference's, as ``rel_err``, each.  Set
#: from two readings on the v5e (PR 30's review round, PERF.md section
#: 6; 24 seeds, the control 12): the program's largest 6.7e-5 (``y``),
#: 5.5e-4 (``S``), 5.8e-4 (``z``: the chip's ``exp`` of a log-decay near
#: 0 reads 1.2e-6 low, and a recurrence multiplies ~450 of them; the
#: reference's own recurrent form reads the same there); the same steps
#: with the state rounded to bfloat16 after each, the nearest below the
#: float32 the configuration states, at the least 0.0103 (``y``), 0.080
#: (``S``), 0.284 (``z``): not correct, by every part.  5e-3 is 8.6x
#: over the program's largest and 2x, 16x and 57x under the control's.
MEMORY_TOL = 5e-3
UPDATES = "decode.retention.updates"
STATE_GAUGE = "decode.retention.state_bytes"


def make_weights(graph, seed: int, dtype, gains: dict) -> dict:
    """The program's initialiser from the seed, a node at a time, each
    fetched to the host as it is made: ``graph.init(key)[node]`` under
    ``jit`` draws that node's leaves alone (the others are dead code),
    so the tree is ``graph.init``'s own and the float32 draw of one
    node (3.1 GB for the embedding) is the most the device holds.  A
    leaf whose path ends with a key of ``gains`` (``embeddings/wte``) is
    scaled by that factor before floating leaves are cast to ``dtype``,
    as ``batch_decode_moe.make_weights`` does it."""
    import jax
    import jax.numpy as jnp

    # seeds run to a little over 2**31: fold into the key's 32-bit range
    key = jax.random.key(int(seed) % (2 ** 31 - 1))

    def node(name):
        def leaf(path, a):
            full = "/".join([name] + [str(k.key) for k in path])
            for ending, gain in gains.items():
                if full.endswith(ending):
                    a = a * gain
            return a.astype(dtype) \
                if jnp.issubdtype(a.dtype, jnp.floating) else a

        return jax.device_get(jax.jit(lambda k: jax.tree_util.
                                      tree_map_with_path(
                                          leaf, graph.init(k)[name]))(key))

    return {name: node(name) for name in jax.eval_shape(graph.init, key)}


def setup(ctx):
    import jax.numpy as jnp

    from defer_tpu import PipelinedDecoder, models

    tr, cfg = ctx.cell.traffic, ctx.cell.config
    if ctx.cell.chips != 1:
        raise ValueError("batch_decode_retention's state check reads one "
                         "chip's buffers; give the cell one chip")
    graph = models.brumby(**cfg["model_args"])
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


def measure(state, seconds, ctx):
    from defer_tpu.obs import REGISTRY

    tr = state["traffic"]
    updates = REGISTRY.counter(UPDATES)
    before = updates.n
    out = base.measure(state, seconds, ctx)
    done = updates.n - before
    out["counters"].update({
        UPDATES: done,
        "retention_state_bytes": float(REGISTRY.gauge(STATE_GAUGE).value),
        "retention_layers": state["config"]["model_args"]["num_layers"],
        "prefill_tokens": tr["batch"] * tr["prompt_len"]})
    layer_steps = tr["batch"] * state["config"]["model_args"]["num_layers"]
    out["notes"].append(
        f"{UPDATES} {done} = {layer_steps} (sequences x layers) x "
        f"{done / layer_steps:.2f} valid decode steps")
    return out


def decoded_states(dec, prompts, n: int, tr: dict) -> tuple:
    """One generation outside the window, the prefill and
    ``STATE_STEPS`` decode steps (fewer where the traffic's generations
    are shorter): ``(ids, states)``, the first ``n``
    sequences' prompt and the tokens fed back (all the state has
    absorbed: the last token handed out was never an input), and the
    state the ring was left with for them, every layer: ``[(S [n, kv,
    D, d], z [n, kv, D]), ...]`` on the host."""
    out = dec.generate(prompts, min(STATE_STEPS + 1, tr["new_tokens"]),
                       prefill=True, token_chunk=tr["token_chunk"])
    states = []
    for l in range(dec.l_max):
        # [stage, group, sequence, ...]: one chip's one group holds every
        # sequence, and its blocks are the model's
        states.append(tuple(np.asarray(dec.state[key][l][0, 0, :n])
                            for key in ("S", "z")))
    dec.state = None
    return np.asarray(out)[:n, :-1], states


def state_errors(got: list, params, ids, ref_cfg: dict) -> list:
    """For each layer, how far the program's state is from the plain
    reference's explicit sum over the same tokens: the larger of ``S``'s
    and ``z``'s ``rel_err``, both unpacked to the form that knows no
    layout."""
    from defer_tpu.ops.retention import dense

    ref = importlib.import_module(ref_cfg["module"])
    want = ref.states(params, ids, **ref_cfg["args"])
    return [max(rel_err(dense(s, -2), np.asarray(ws)),
                rel_err(dense(z, -1), np.asarray(wz)))
            for (s, z), (ws, wz) in zip(got, want)]


def long_memory_error(fmt, heads: int, seed: int, ref, *, held=None,
                      steps: int = PROBE_STEPS, sequences: int = 2) -> dict:
    """The program's format ``fmt`` (its buffers, its step) through
    ``steps`` positions of ``sequences`` seeded float32 sequences whose
    log-decays lie in ``[-PROBE_LOG_DECAY, 0)``, from an empty memory,
    against the reference's attention form and explicit sum:
    ``rel_err`` of the outputs ``y`` (from position 8 on), of ``S`` and
    of ``z``.  Queries and keys have unit mean square a head, as the
    blocks' QK-norm leaves them.  ``held`` is the control: a type the
    state is rounded to after every step (by ``reduce_precision``: a
    cast there and back is the compiler's to drop, and the v5e's
    drops it)."""
    import jax
    import jax.numpy as jnp

    from defer_tpu.ops.retention import dense

    kv, d, b = fmt.kv_heads, fmt.head_dim, sequences
    rng = np.random.default_rng(seed)

    def normed(n):
        a = rng.normal(size=(steps, b, n, d))
        return a / np.sqrt((a * a).mean(-1, keepdims=True))

    q, k = normed(heads), normed(kv)
    v = rng.normal(size=(steps, b, kv, d))
    lg = -rng.uniform(0.0, PROBE_LOG_DECAY, size=(steps, b, kv))
    q, k, v, lg = (jnp.asarray(a, jnp.float32) for a in (q, k, v, lg))

    def body(layer, xs):
        qt, kt, vt, lt = xs
        y, layer = fmt.step(qt.reshape(b, -1), kt.reshape(b, -1),
                            vt.reshape(b, -1), lt, layer, group=0)
        if held is not None:
            kind = jnp.finfo(held)
            layer = jax.tree.map(lambda a: jax.lax.reduce_precision(
                a, kind.nexp, kind.nmant), layer)
        return layer, y

    empty = fmt.layer(fmt.zeros(b, 1), 0)
    layer, ys = jax.jit(lambda layer, *xs: jax.lax.scan(body, layer, xs))(
        empty, q, k, v, lg)
    # [t, b, H, d] -> the reference's [b, H, t, d]
    heads_first = [jnp.transpose(a, (1, 2, 0, 3)) for a in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want_y = jax.jit(ref.retention)(*heads_first,
                                        jnp.transpose(lg, (1, 2, 0)))
        want_s, want_z = jax.jit(ref.explicit_state)(
            *heads_first[1:], jnp.transpose(lg, (1, 2, 0)))
    got_y = np.asarray(ys).reshape(steps, b, heads, d).transpose(1, 2, 0, 3)
    # behind the ring's group axis, where the format has one
    got_s, got_z = (np.asarray(layer[key] if fmt.groups is None
                               else layer[key][0]) for key in ("S", "z"))
    return {"y": rel_err(got_y[:, :, 8:], np.asarray(want_y)[:, :, 8:]),
            "S": rel_err(dense(got_s, -2), np.asarray(want_s)),
            "z": rel_err(dense(got_z, -1), np.asarray(want_z))}


def check(state, ctx):
    tr, cfg = state["traffic"], state["config"]
    n = tr["check_sequences"]
    dec = state.pop("dec", None)
    ids, got = decoded_states(dec, state["prompts"], n, tr)
    fmt = dec.state_format
    # the reference upcasts a layer at a time beside whatever the chip
    # still holds: let the decoder's weights and state go first
    del dec
    gc.collect()
    ok, detail = base.check(state, ctx)
    if "worst_logit_gap_share" not in detail:
        return ok, detail
    detail["tolerance"] = GAP_TOL               # judged at this file's limits
    errors = state_errors(got, state["params"], ids, cfg["reference"])
    memory = long_memory_error(
        fmt, cfg["model_args"]["heads"], ctx.seed,
        importlib.import_module(cfg["reference"]["module"]))
    detail.update(state_rel_err=max(errors),
                  state_rel_err_by_layer=[round(e, 5) for e in errors],
                  state_tolerance=STATE_TOL,
                  first_layer_state_tolerance=STATE_TOL_FIRST,
                  long_memory_rel_err=max(memory.values()),
                  long_memory_rel_err_by_part=memory,
                  long_memory_tolerance=MEMORY_TOL)
    return (detail["worst_logit_gap_share"] <= GAP_TOL
            and max(errors) <= STATE_TOL and errors[0] <= STATE_TOL_FIRST
            and max(memory.values()) <= MEMORY_TOL), detail


close = base.close
