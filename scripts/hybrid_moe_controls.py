"""The controls behind the limits of ``granite4h_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_hybrid_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``WEIGHTS_TOL``, ``STATE_TOL``,
``STATE_TOL_FIRST``, ``MEMORY_TOL``), on the chip, outside any cell's
window — not part of the tests or the benchmark.

For each seed it prints one JSON line:

* ``probe``: the long-memory probe as ``check`` runs it (the program's
  reading), and ``probe_bfloat16_state``: the same kernels with ``H``
  rounded to bfloat16 (the nearest precision below the configuration's
  float32) after the prefill and after every step;
* with ``--model``: the plain reference at the cell's widths against
  itself on ``--tokens`` positions of ``--sequences`` seeded sequences —
  with every product's operands rounded to float8_e4m3 (the nearest
  below the configuration's bfloat16): the worst logit gap share of the
  low-precision run's own greedy tokens, the share of the float32 run's
  expert choices it makes, and its states' ``rel_err`` against the
  float32 run's; with its own ``H`` kept in bfloat16: the same
  ``rel_err`` (a bfloat16 ``H`` is the probe's to fail); against its own
  window one position earlier (a window read one position off); and the
  program's own blocks held to a reference whose router renormalises
  over all 72 experts (``renormalised_*``: the choices agree, the
  expert half does not).  The program's own readings of these are every
  run's ``check`` line.

    python3 scripts/hybrid_moe_controls.py [--model] [--tokens N] SEED...
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
    from defer_tpu.ops.ssm import SsdFormat

    manifest = Manifest()
    cell = manifest.cell("granite4h_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    args = cfg["model_args"]
    ref = importlib.import_module(cfg["reference"]["module"])
    fmt = SsdFormat(args["mamba_heads"], args["mamba_head_dim"],
                    args["mamba_d_state"], args["mamba_d_conv"],
                    args["mamba_chunk"], jnp.dtype(tr["compute_dtype"]),
                    groups=1)
    for seed in opts.seeds:
        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "probe": drv.long_memory_error(fmt, seed, ref),
               "probe_bfloat16_state": drv.long_memory_error(
                   fmt, seed, ref, held=jnp.bfloat16)}
        if opts.model:
            from defer_tpu import models
            graph = models.granite_hybrid(**args)
            params = drv.make_weights(
                graph, seed, jnp.dtype(tr["compute_dtype"]),
                cfg.get("init_gain", {}))
            ids = np.random.default_rng(seed).integers(
                0, args["vocab"], (opts.sequences, opts.tokens)
            ).astype(np.int32)
            kw = cfg["reference"]["args"]
            hi, chosen = ref.logits(params, ids, experts=True, **kw)
            lo, coarse_chosen = ref.logits(
                params, ids, experts=True, inputs=jnp.float8_e4m3fn, **kw)
            hi, lo = np.asarray(hi), np.asarray(lo)
            picked = np.take_along_axis(hi, lo.argmax(-1)[..., None],
                                        -1)[..., 0]
            best = hi.max(-1)
            gaps = (best - picked) / np.maximum(best - hi.mean(-1), 1e-6)

            def agree(got, want):
                """By layer, the share of ``want``'s choices in ``got``."""
                got, want = np.asarray(got), np.asarray(want)
                same = (got[..., :, None] == want[..., None, :]).any(-2)
                return same.reshape(same.shape[0], -1).mean(-1)

            sound = ref.states(params, ids, **kw)
            narrow = ref.states(params, ids, state_dtype=jnp.bfloat16, **kw)
            coarse = ref.states(params, ids, inputs=jnp.float8_e4m3fn, **kw)
            before = ref.states(params, ids, window_shift=1, **kw)

            def errs(got, part=None):
                out = {}
                for l, (g, w) in enumerate(zip(got, sound)):
                    if w is None:
                        continue
                    parts = (0, 1) if part is None else (part,)
                    out[l] = max(rel_err(np.asarray(g[i]), np.asarray(w[i]))
                                 for i in parts)
                return out

            narrow_errs, off_errs = errs(narrow), errs(before, part=1)
            coarse_errs = errs(coarse)
            shares, halves = drv.router_agreement(
                graph, params, ids, cfg["reference"])
            other_shares, other_halves = drv.router_agreement(
                graph, params, ids, cfg["reference"],
                renormalise_over_all=True)
            row.update(
                float8_worst_logit_gap_share=float(gaps.max()),
                float8_exact_argmax_share=float((gaps <= 0).mean()),
                logit_spread_mean=float((best - hi.mean(-1)).mean()),
                float8_router_agreement_by_layer=[
                    round(float(s), 4)
                    for s in agree(coarse_chosen, chosen)],
                float8_state_rel_err_first=coarse_errs[min(coarse_errs)],
                float8_state_rel_err_least=min(coarse_errs.values()),
                float8_state_rel_err_most=max(coarse_errs.values()),
                bfloat16_state_rel_err_first=narrow_errs[min(narrow_errs)],
                bfloat16_state_rel_err_least=min(narrow_errs.values()),
                bfloat16_state_rel_err_most=max(narrow_errs.values()),
                window_off_by_one_rel_err_least=min(off_errs.values()),
                program_router_agreement_by_layer=[
                    round(s, 4) for s in shares],
                program_expert_half_rel_err_by_layer=[
                    round(e, 5) for e in halves],
                renormalised_router_agreement_by_layer=[
                    round(s, 4) for s in other_shares],
                renormalised_expert_half_rel_err_by_layer=[
                    round(e, 5) for e in other_halves])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
