"""The controls behind the limits of ``solaropen2_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_delta_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``STATE_TOL``, ``STATE_TOL_FIRST``,
``WINDOW_TOL``, ``LOGITS_TOL``, ``STATE_SUM_TOL``, ``ROUTER_SUM_TOL``),
on the chip, outside any cell's window — not part of the tests or the
benchmark.

For each seed it draws the cell's weights, runs the driver's own
``decode_probe`` (the program's blocks through their formats: a prefill
of ``--prompt`` positions and ``--steps`` decode steps of
``--sequences`` seeded sequences) and prints one JSON line:

* ``program``: the probe against the sound float32 reference — the
  logits' ``rms_err``, the router's agreement in the worst layer, the
  states' ``rms_err`` and the windows' ``rel_err`` a layer and in the
  worst, the worst logit gap share of the probe's own greedy tokens,
  and the two float32 sums' probe (``sum_probe``);
* a control an entry, **the program held to the reference under it**
  (what the limits must refuse): ``decay_a_head`` (the decay a head in
  place of a channel), ``beta_sigma`` (``beta = sigma`` in place of ``2
  sigma``), ``window_shift`` (a window one position off),
  ``write_without_read`` (``S' + beta k v^T``), ``gqa_rotation`` (a
  rotation let into the GQA layer), ``gqa_gate`` (its gate dropped),
  ``bias_weighs`` (the selection bias in the weights);
* ``float8_inputs``: the reference with every product's operands
  rounded to float8_e4m3, the nearest precision below the stated
  bfloat16, **against the sound reference** (the same measures, the
  low-precision run in the program's place);
* ``sums_bfloat16``: ``sum_probe`` with the reference's own state, and
  its router's logits, kept in bfloat16; ``sums_bias_weighs``: its
  router's weights with the bias let in; ``sums_write_without_read``:
  its state under the write that does not read it.

    python3 scripts/delta_moe_controls.py [--prompt N] [--steps N] SEED...
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from conv_moe_controls import gap_share

CONTROLS = (("decay_a_head", {"decay_a_head": True}),
            ("beta_sigma", {"beta_scale": 1.0}),
            ("window_shift", {"window_shift": 1}),
            ("write_without_read", {"delta_reads": False}),
            ("gqa_rotation", {"gqa_theta": 10000.0}),
            ("gqa_gate", {"gqa_gate": False}),
            ("bias_weighs", {"bias_weighs": True}))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--prompt", type=int, default=512)
    ap.add_argument("--steps", type=int, default=64)
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
    cell = manifest.cell("solaropen2_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    ref_cfg = cfg["reference"]
    dtype = jnp.dtype(tr["compute_dtype"])
    graph = models.solar_open2(**cfg["model_args"])
    plen, layers = opts.prompt, cfg["model_args"]["num_layers"]
    for seed in opts.seeds:
        params = drv.make_weights(graph, seed, dtype,
                                  cfg.get("init_gain", {}))
        seqs = np.random.default_rng(seed).integers(
            0, cfg["model_args"]["vocab"],
            (opts.sequences, plen + opts.steps)).astype(np.int32)
        probe = drv.decode_probe(graph, params, seqs, plen, dtype)
        memory = [probe[2].get(l) for l in range(layers)]

        def held_to(**control):
            """The program's probe against the reference under
            ``control``."""
            want, extras = drv.reference_forward(params, seqs, plen, ref_cfg,
                                                 **control)
            shares, logits = drv.probe_agreement(probe, want, extras, plen)
            states, windows = drv.memory_errors(memory, extras)
            return {"logits_rms_err": logits,
                    "router_agreement_least": min(shares.values()),
                    "state_rms_err_by_layer": {
                        l: float(f"{e:.4g}") for l, e in states.items()},
                    "state_rms_err_most": max(states.values()),
                    "state_rms_err_first": states[min(states)],
                    "window_rel_err_by_layer": {
                        l: float(f"{e:.4g}") for l, e in windows.items()},
                    "window_rel_err_most": max(windows.values()),
                    "window_rel_err_least": min(windows.values())}

        want, extras = drv.reference_forward(params, seqs, plen, ref_cfg)
        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "program": dict(
                   held_to(), worst_logit_gap_share=gap_share(probe[0], want),
                   logit_spread_mean=float(
                       (want.max(-1) - want.mean(-1)).mean()),
                   sums=drv.sum_probe(graph, params, seed, dtype, ref_cfg))}
        for name, control in CONTROLS:
            row[name] = held_to(**control)
        # the reference one precision below the stated one, in the
        # program's place
        low, low_extras = drv.reference_forward(
            params, seqs, plen, ref_cfg, inputs=jnp.float8_e4m3fn)
        shares, errs = [], []
        for a, b in zip(low_extras, extras):
            mine, theirs = (np.asarray(x["chosen"])[:, plen:]
                            for x in (a, b))
            shares.append(float((mine[..., :, None]
                                 == theirs[..., None, :]).any(-2).mean()))
            if b["state"] is not None:
                errs.append(rms_err(np.asarray(a["state"]),
                                    np.asarray(b["state"])))
        row["float8_inputs"] = {
            "logits_rms_err": rms_err(low, want),
            "router_agreement_least": min(shares),
            "router_agreement_most": max(shares),
            "state_rms_err_first": errs[0], "state_rms_err_least": min(errs),
            "worst_logit_gap_share": gap_share(low, want),
            "exact_argmax_share": float(
                (low.argmax(-1) == want.argmax(-1)).mean())}
        row["sums_bfloat16"] = {
            "state": drv.sum_probe(graph, params, seed, dtype, ref_cfg,
                                   state_dtype=jnp.bfloat16)["state"],
            "router": drv.sum_probe(graph, params, seed, dtype, ref_cfg,
                                    router_dtype=jnp.bfloat16)["router"]}
        row["sums_bias_weighs"] = drv.sum_probe(
            graph, params, seed, dtype, ref_cfg, bias_weighs=True)["router"]
        row["sums_write_without_read"] = drv.sum_probe(
            graph, params, seed, dtype, ref_cfg, delta_reads=False)["state"]
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
