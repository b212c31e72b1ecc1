"""The controls behind the limits of ``mellum2_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_rotary_window_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``WINDOW_TOL``, ``ROTATION_TOL``), on the
chip, outside any cell's window — not part of the tests or the
benchmark.

For each seed it prints one JSON line:

* ``window``: the window probe as ``check`` runs it (the program's
  reading) at 4 KV heads of 8 queries and a window of 1024, and its
  controls: the program handed inputs rounded to ``float8_e4m3fn`` (the
  nearest precision below the configuration's bfloat16), held to a
  reference whose window is one shorter and one longer, and with each
  decode step's row written one row off;
* ``rotation``: the rotation probe of a window layer and of a full one
  as ``check`` runs it, at positions up to ``max_len - 1``, and its
  controls: float8 inputs, and the reference turning a full layer by
  the plain table, by a YaRN ramp a pair off either way, without the
  attention factor, and either kind over interleaved pairs;
* with ``--model``: the plain reference at the cell's widths against
  itself with every product's operands rounded to float8
  (``scripts/window_moe_controls.py::rounded_reference_control``, the
  low-precision run in the program's place of ``check`` (a) and (b)) —
  **at the cell's lengths**: ``check_sequences`` seeded sequences of
  ``prompt_len + check_tokens - 1`` positions, the float8 run's tokens
  judged where a run's generated ones are, behind ``prompt_len``
  positions of context, by ``logit_gaps``'s measure; and the share of
  the float32 run's expert choices it makes in the layer where they
  agree least.  ``--tokens N`` reads N positions instead, all judged
  (the PR's first readings were at 2048).  The program's own readings
  of those two are every run's ``check`` line.  ``--probes 0`` leaves
  the two probes' controls out;
* with ``--model --far-context``: whether the far context decides the
  token (:func:`far_context_control`), and with ``--gain LEAF=X`` (as
  ``--gain q/w=1 --gain k/w=1``) all of ``--model`` under another
  ``init_gain`` than the configuration's, ``--inputs bfloat16`` the
  reference rounded to the cell's own precision beside (or in place
  of) float8: what the configuration's q / k gain buys and costs.

    python3 scripts/rotary_window_moe_controls.py [--model] [--tokens N]
        [--probes 0] [--far-context] [--gain LEAF=X]...
        [--inputs bfloat16]... SEED...
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from scripts.window_moe_controls import rounded_reference_control


#: positions a stack of the cell's six window layers cannot reach back
#: over (6 x 1023 = 6138): what moves a token from farther off came
#: through a full layer
BEYOND_WINDOWS = 8192


def far_context_control(ref, ref_args: dict, params, ids, seed: int,
                        vocab: int, *, judged_from: int) -> dict:
    """Does the far context decide the token?  The float32 reference on
    ``ids`` and on ``ids`` with every position more than
    :data:`BEYOND_WINDOWS` before the first judged one drawn anew: the
    share of judged positions whose best token changes, and the worst
    gap (``logit_gaps``'s measure) of the redrawn run's tokens under the
    first run's logits.  Where attention is flat over tens of thousands
    of seeded keys (``init_gain`` without its q / k entries) both read
    ~0: the token check would then pass whatever the full layers' rows
    and rotation held."""
    far = max(judged_from - BEYOND_WINDOWS, 0)
    other = np.array(ids)
    other[:, :far] = np.random.default_rng(seed + 1).integers(
        0, vocab, (ids.shape[0], far))
    kw = dict(ref_args, lo=judged_from - 1)
    hi = np.asarray(ref.logits(params, ids, **kw))
    moved = np.asarray(ref.logits(params, other, **kw))
    picked = np.take_along_axis(hi, moved.argmax(-1)[..., None], -1)[..., 0]
    best = hi.max(-1)
    gaps = (best - picked) / np.maximum(best - hi.mean(-1), 1e-6)
    return dict(far_context_positions_redrawn=far,
                far_context_moved_token_share=float((gaps > 0).mean()),
                far_context_worst_logit_gap_share=float(gaps.max()))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--tokens", type=int, default=0)
    ap.add_argument("--probes", type=int, default=1)
    ap.add_argument("--far-context", action="store_true")
    ap.add_argument("--inputs", action="append", default=[])
    ap.add_argument("--gain", action="append", default=[],
                    metavar="LEAF=X")
    ap.add_argument("seeds", type=int, nargs="+")
    opts = ap.parse_args()
    opts.inputs = opts.inputs or ["float8_e4m3fn"]

    import jax
    import jax.numpy as jnp

    from chipbench.manifest import Manifest
    from defer_tpu import models

    manifest = Manifest()
    cell = manifest.cell("mellum2_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    args, ref_args = cfg["model_args"], cfg["reference"]["args"]
    ref = importlib.import_module(cfg["reference"]["module"])
    dtype = jnp.dtype(tr["compute_dtype"])
    graph = models.mellum(**args)
    geometry = dict(heads=args["heads"], kv=args["kv_heads"],
                    hd=args["head_dim"], window=args["window"], dtype=dtype,
                    ref=ref)
    ops = {op.kind: op for op in (graph.nodes[f"block_{i}"].op
                                  for i in reversed(range(4)))}
    hd, theta, yarn = args["head_dim"], ref_args["theta"], ref_args["yarn"]
    own = {kind: ref.layer_rotation(name, head_dim=hd, theta=theta, yarn=yarn)
           for kind, name in (("window", "sliding_attention"),
                              ("full", "full_attention"))}

    def ramp(shift):
        return ref.yarn_frequencies(
            hd, theta, yarn["factor"], yarn["original"], yarn["beta_fast"],
            yarn["beta_slow"], shift=shift)

    for seed in opts.seeds:
        w = args["window"]

        def rotation(kind, **control):
            freqs, c = own[kind]
            kw = dict(freqs=freqs, c=c)
            kw.update(control)
            return drv.rotation_probe(
                seed, ops[kind], d_model=args["hidden"],
                positions=tr["max_len"], dtype=dtype, ref=ref, **kw)

        row = {"seed": seed, "device": jax.devices()[0].device_kind}
        if opts.probes:
            row.update({
                "window": drv.window_probe(seed, **geometry),
                "window_float8_inputs": drv.window_probe(
                    seed, inputs=jnp.float8_e4m3fn, **geometry),
                "window_minus_1": drv.window_probe(
                    seed, ref_window=w - 1, **geometry),
                "window_plus_1": drv.window_probe(
                    seed, ref_window=w + 1, **geometry),
                "window_row_off_by_1": drv.window_probe(
                    seed, slot_shift=1, **geometry),
                "rotation": {k: rotation(k) for k in ops},
                "rotation_float8_inputs": {
                    k: rotation(k, inputs=jnp.float8_e4m3fn) for k in ops},
                "rotation_interleaved": {
                    k: rotation(k, pairing="interleaved") for k in ops},
                "rotation_full_plain_table": rotation(
                    "full", freqs=ref.plain_frequencies(hd, theta)),
                "rotation_full_ramp_plus_1": rotation("full", freqs=ramp(1)),
                "rotation_full_ramp_minus_1": rotation("full", freqs=ramp(-1)),
                "rotation_full_no_factor": rotation("full", c=1.0),
                "rotation_window_yarn_table": rotation(
                    "window", freqs=own["full"][0])})
        if opts.model:
            gains = dict(cfg.get("init_gain", {}),
                         **{k: float(x) for k, x in
                            (g.split("=") for g in opts.gain)})
            params = drv.make_weights(graph, seed, dtype, gains)
            # the cell's lengths unless told: as many sequences as a run
            # judges, each the prompt and the context of every judged
            # token, so that the float8 run's tokens stand where a
            # run's generated ones do, behind ``prompt_len`` positions
            plen, n = tr["prompt_len"], tr["check_sequences"]
            length = opts.tokens or plen + tr["check_tokens"] - 1
            judged_from = plen if length > plen else 1
            ids = np.random.default_rng(seed).integers(
                0, args["vocab"], (n, length)).astype(np.int32)
            for inputs in opts.inputs:
                row.update(rounded_reference_control(
                    ref, ref_args, params, ids, judged_from=judged_from,
                    num_experts=args["num_experts"], inputs=inputs),
                    init_gain=gains)
            if opts.far_context:
                row.update(far_context_control(
                    ref, ref_args, params, ids, seed, args["vocab"],
                    judged_from=judged_from))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
