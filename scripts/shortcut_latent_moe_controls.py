"""The controls behind the limits of ``longcatflash_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_shortcut_latent_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``WEIGHTS_TOL``, ``SHORTCUT_TOL``,
``LATENT_TOL``, ``LATENT_TOL_FIRST``), on the chip, outside any cell's
window — not part of the tests or the benchmark.

For each seed it draws the cell's weights and prints one JSON line, from
``--tokens`` positions of ``--sequences`` seeded sequences:

* ``program_*``: the program's own blocks against the plain reference,
  as ``check`` compares them (the share of the reference's choices a
  layer, the weights' and the shortcut's ``rms_err``, the rows'
  ``rel_err`` a layer, the larger of the two sublayers');
* ``float8_*``: the reference against itself with every product's
  operands rounded to float8_e4m3, the nearest precision below the
  configuration's bfloat16: the worst logit gap share of the
  low-precision run's own greedy tokens, the share of the float32 run's
  choices it makes, its rows' ``rel_err``, its shortcut's ``rms_err``;
* ``float8_rows_*``: the same with only the rows ``[c, k_r]`` rounded, as
  two caches kept in float8 would hold them;
* ``no_zero_experts_*`` / ``renormalise_*`` / ``bias_weighs_*`` /
  ``plain_lora_*``: the program held to a reference that leaves the
  zero-compute experts out, renormalises the chosen weights, lets the
  bias into the weights, or leaves both LoRA scales out.

Each control must miss at least one of the limits, which the line says
under ``fails``.

    python3 scripts/shortcut_latent_moe_controls.py [--tokens N] SEED...
"""

import argparse
import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("seeds", type=int, nargs="+")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench.agreement import rel_err
    from chipbench.drivers.batch_decode_hybrid_moe import rms_err
    from chipbench.manifest import Manifest
    from defer_tpu import models

    manifest = Manifest()
    cell = manifest.cell("longcatflash_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    args, kw = cfg["model_args"], cfg["reference"]["args"]
    ref = importlib.import_module(cfg["reference"]["module"])
    graph = models.longcat_flash(**args)
    keep = ("chosen", "weights", "ffn_in", "shortcut", "rows", "rows_1")
    last = args["num_layers"] - 1

    def agree(got, want):
        """By layer, the share of ``want``'s choices in ``got``."""
        return [round(float((g["chosen"][..., :, None]
                             == w["chosen"][..., None, :]).any(-2).mean()), 4)
                for g, w in zip(got, want)]

    def rows_err(got, want):
        """The rows' ``rel_err`` as ``check`` judges them: the first
        block's two sublayers (upstream of every routed expert), and
        the last block's."""
        return {name: max(rel_err(got[l][k], want[l][k])
                          for k in ("rows", "rows_1"))
                for name, l in (("first", 0), ("last", last))}

    for seed in opts.seeds:
        params = drv.make_weights(graph, seed, jnp.dtype(tr["compute_dtype"]),
                                  cfg.get("init_gain", {}))
        ids = np.random.default_rng(seed).integers(
            0, args["vocab"], (opts.sequences, opts.tokens)).astype(np.int32)
        hi, sound = ref.forward(params, ids, keep=keep, **kw)
        hi = np.asarray(hi)
        best = hi.max(-1)

        def gap(lo):
            picked = np.take_along_axis(
                hi, np.asarray(lo).argmax(-1)[..., None], -1)[..., 0]
            return float(((best - picked)
                          / np.maximum(best - hi.mean(-1), 1e-6)).max())

        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "tokens": opts.tokens,
               "logit_spread_mean": float((best - hi.mean(-1)).mean()),
               "zero_choice_share": float(np.mean([
                   (ex["chosen"] >= args["num_experts"]).mean()
                   for ex in sound]))}
        program = drv.program_agreement(graph, params, ids, sound)
        row.update(
            program_router_agreement_by_layer=[
                round(s, 4) for s in program["shares"]],
            program_router_weights_rms_err_by_layer=[
                float(f"{e:.3g}") for e in program["weights"]],
            program_shortcut_rms_err_by_layer=[
                float(f"{e:.3g}") for e in program["shortcut"]],
            program_rows_rel_err_by_layer=[
                round(e, 5) for e in program["rows"]])
        fails = {}
        for name, control in (
                ("float8", {"inputs": jnp.float8_e4m3fn}),
                ("float8_rows", {"row_dtype": jnp.float8_e4m3fn})):
            lo, coarse = ref.forward(params, ids, keep=keep, **kw, **control)
            errs = rows_err(coarse, sound)
            shares = agree(coarse, sound)
            short = max(rms_err(c["shortcut"], s["shortcut"])
                        for c, s in zip(coarse, sound))
            row.update({
                f"{name}_worst_logit_gap_share": gap(lo),
                f"{name}_router_agreement_by_layer": shares,
                f"{name}_shortcut_rms_err": short,
                f"{name}_rows_rel_err": errs})
            fails[name] = [limit for limit, missed in (
                ("GAP_TOL", gap(lo) > drv.GAP_TOL),
                ("ROUTER_TOL", min(shares) < drv.ROUTER_TOL),
                ("SHORTCUT_TOL", short > drv.SHORTCUT_TOL),
                ("LATENT_TOL_FIRST", errs["first"] > drv.LATENT_TOL_FIRST),
                ("LATENT_TOL", errs["last"] > drv.LATENT_TOL)) if missed]
        for name in ("no_zero_experts", "renormalise", "bias_weighs",
                     "plain_lora"):
            other = drv.reference_extras(params, ids, cfg["reference"],
                                         **{name: True})
            held = drv.program_agreement(graph, params, ids, other)
            row.update({
                f"{name}_router_agreement_by_layer": [
                    round(s, 4) for s in held["shares"]],
                f"{name}_router_weights_rms_err_by_layer": [
                    float(f"{e:.3g}") for e in held["weights"]],
                f"{name}_shortcut_rms_err_by_layer": [
                    float(f"{e:.3g}") for e in held["shortcut"]],
                f"{name}_rows_rel_err_by_layer": [
                    round(e, 5) for e in held["rows"]]})
            fails[name] = [limit for limit, missed in (
                ("ROUTER_TOL", min(held["shares"]) < drv.ROUTER_TOL),
                ("WEIGHTS_TOL", max(held["weights"]) > drv.WEIGHTS_TOL),
                ("SHORTCUT_TOL", max(held["shortcut"]) > drv.SHORTCUT_TOL),
                ("LATENT_TOL_FIRST", held["rows"][0] > drv.LATENT_TOL_FIRST),
                ("LATENT_TOL", held["rows"][last] > drv.LATENT_TOL))
                if missed]
        row["fails"] = fails
        row["every_control_fails"] = all(fails.values())
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
