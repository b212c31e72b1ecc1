"""The controls behind the limits of ``nemotron3super_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_ssd_latent_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``BRANCH_TOL``, ``LATENT_TOL``,
``MIXER_TOL``, ``WEIGHTS_TOL``,
``STATE_TOL``, ``ROWS_TOL``, ``MEMORY_TOL``), on the chip, outside any
cell's window — not part of the tests or the benchmark.

Each control is a model this family is *not*, made by one keyword of
the plain reference (``chipbench/reference/nemotron_h.py``), and each
must fail at least one limit.  For each seed one JSON line:

* ``probe``: the long-memory probe as ``check`` runs it (the program's
  reading), and ``probe_bfloat16_state``: the same kernels with ``H``
  rounded to bfloat16 (the nearest precision below the configuration's
  float32) after the prefill and after every step;
* ``weights``: the router's weights probe as ``check`` runs it, and
  ``weights_bias_in``: against a reference that lets the bias into the
  weights;
* with ``--model``: the plain reference at the cell's widths on
  ``--tokens`` positions of ``--sequences`` seeded sequences —
  against itself with every product's operands rounded to float8_e4m3
  (the nearest below the configuration's bfloat16): the worst logit gap
  share of the low-precision run's own greedy tokens, the share of the
  float32 run's expert choices it makes, its states' and rows'
  ``rel_err``; against itself under ``one_bc_group`` (one B/C group for
  all heads), ``norm_one_group`` (the gated norm over 8192 channels as
  one group), ``state_dtype`` bfloat16, ``window_shift`` 1 and
  ``rotation_theta`` 10000: the states' and rows' ``rel_err`` and the
  logits' gap; and the program's own ``E`` blocks held to a reference
  under ``activation`` ``silu`` / ``relu``, ``routed_scale`` 1,
  ``drop_last`` and ``bias_in_weights`` (``branch_*``: the choices
  agree, the branch does not).  The program's own readings of these are
  every run's ``check`` line.

    python3 scripts/ssd_latent_moe_controls.py [--model] [--tokens N] SEED...
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
    ap.add_argument("--model", action="store_true")
    ap.add_argument("--tokens", type=int, default=320)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("seeds", type=int, nargs="+")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench.agreement import rel_err
    from chipbench.manifest import Manifest
    from defer_tpu.models import nemotron_h
    from defer_tpu.ops.ssm import SsdFormat

    manifest = Manifest()
    cell = manifest.cell("nemotron3super_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    args = cfg["model_args"]
    ref = importlib.import_module(cfg["reference"]["module"])
    dtype = jnp.dtype(tr["compute_dtype"])
    fmt = SsdFormat(args["mamba_heads"], args["mamba_head_dim"],
                    args["mamba_d_state"], args["mamba_d_conv"],
                    args["mamba_chunk"], dtype, groups=1,
                    bc_groups=args["mamba_groups"])
    graph = nemotron_h(**args)
    kw = cfg["reference"]["args"]
    for seed in opts.seeds:
        params = drv.make_weights(graph, seed, dtype,
                                  cfg.get("init_gain", {}))
        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "probe": drv.long_memory_error(fmt, seed, ref),
               "probe_bfloat16_state": drv.long_memory_error(
                   fmt, seed, ref, held=jnp.bfloat16),
               "weights": drv.router_weights_error(
                   graph, params, seed, cfg["reference"]),
               "weights_bias_in": drv.router_weights_error(
                   graph, params, seed, cfg["reference"],
                   bias_in_weights=True)}
        if opts.model:
            ids = np.random.default_rng(seed).integers(
                0, args["vocab"], (opts.sequences, opts.tokens)
            ).astype(np.int32)
            hi, chosen = ref.logits(params, ids, experts=True, **kw)
            hi = np.asarray(hi)
            best = hi.max(-1)
            sound = ref.states(params, ids, **kw)

            def gap(**control):
                """The worst logit gap share of the control's own greedy
                tokens under the sound reference's logits."""
                lo = np.asarray(ref.logits(params, ids, **kw, **control))
                picked = np.take_along_axis(
                    hi, lo.argmax(-1)[..., None], -1)[..., 0]
                return float(((best - picked) / np.maximum(
                    best - hi.mean(-1), 1e-6)).max())

            def errs(**control):
                """``(states by Mamba layer, rows of the attention
                layer)`` of the control against the sound reference."""
                got = ref.states(params, ids, **kw, **control)
                states, rows = {}, {}
                for l, (g, w) in enumerate(zip(got, sound)):
                    if w is None:
                        continue
                    err = max(rel_err(np.asarray(g[i]), np.asarray(w[i]))
                              for i in (0, 1))
                    (states if kw["layer_pattern"][l] == "M" else rows)[
                        l] = round(err, 5)
                return states, rows

            def agree(got, want):
                got, want = np.asarray(got), np.asarray(want)
                return float((got[..., :, None] == want[..., None, :]
                              ).any(-2).mean())

            f8 = jnp.float8_e4m3fn
            _, coarse = ref.logits(params, ids, experts=True, inputs=f8,
                                   **kw)
            row["float8"] = {
                "worst_logit_gap_share": gap(inputs=f8),
                "router_agreement_by_layer": {
                    l: round(agree(coarse[l], chosen[l]), 4)
                    for l in chosen},
                "memory": errs(inputs=f8)}
            for name, control in (
                    ("one_bc_group", {"one_bc_group": True}),
                    ("norm_one_group", {"norm_one_group": True}),
                    ("bfloat16_state", {"state_dtype": jnp.bfloat16}),
                    ("window_off_by_one", {"window_shift": 1}),
                    ("rotation", {"rotation_theta": 10000.0})):
                row[name] = {"memory": errs(**control),
                             "worst_logit_gap_share": gap(**control)}
            shares, branches, latents, mixers = drv.router_agreement(
                graph, params, ids, cfg["reference"])
            row["program"] = {"router_agreement_by_layer": shares,
                              "branch_rms_err_by_layer": branches,
                              "latent_rms_err_by_layer": latents,
                              "mixer_rms_err_by_layer": mixers}
            for name, control in (
                    ("mixer_norm_one_group", {"norm_one_group": True}),
                    ("mixer_one_bc_group", {"one_bc_group": True})):
                row[name] = {l: round(e, 5) for l, e in drv.router_agreement(
                    graph, params, ids, cfg["reference"],
                    **control)[3].items()}
            for name, control in (
                    ("branch_silu", {"activation": "silu"}),
                    ("branch_relu", {"activation": "relu"}),
                    ("branch_routed_scale_1", {"routed_scale": 1.0}),
                    ("branch_drop_last", {"drop_last": True}),
                    ("branch_bias_in_weights", {"bias_in_weights": True})):
                _, moved, routed, _ = drv.router_agreement(
                    graph, params, ids, cfg["reference"], **control)
                row[name] = {"branch": {l: round(e, 5)
                                        for l, e in moved.items()},
                             "latent": {l: round(e, 5)
                                        for l, e in routed.items()}}
        print(json.dumps(row, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
