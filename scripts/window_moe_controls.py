"""The controls behind the limits of ``commandaplus_batch_decode``'s
``correct`` (``chipbench/drivers/batch_decode_window_moe.py``:
``GAP_TOL``, ``ROUTER_TOL``, ``PROBE_TOL``), on the chip, outside any
cell's window — not part of the tests or the benchmark.

For each seed it prints one JSON line:

* ``probe``: the window probe as ``check`` runs it (the program's
  reading), and its controls: the program handed inputs rounded to
  ``float8_e4m3fn`` (the nearest precision below the configuration's
  bfloat16), held to a reference whose window is one shorter and one
  longer, and with each decode step's row written one row off;
* with ``--model``: the plain reference at the cell's widths against
  itself with every product's operands rounded to float8 — the worst
  logit gap share of the low-precision run's own greedy tokens over
  ``--tokens`` positions of one seeded sequence (teacher-forced on
  them), and the share of the float32 run's expert choices it makes in
  the layer where they agree least.  The program's own readings of
  those two are every run's ``check`` line.

    python3 scripts/window_moe_controls.py [--model] [--tokens N] SEED...
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
    ap.add_argument("--tokens", type=int, default=1024)
    ap.add_argument("seeds", type=int, nargs="+")
    opts = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from chipbench.manifest import Manifest

    manifest = Manifest()
    cell = manifest.cell("commandaplus_batch_decode")
    drv = manifest.driver(cell)
    cfg, tr = cell.config, cell.traffic
    args = cfg["model_args"]
    ref = importlib.import_module(cfg["reference"]["module"])
    geometry = dict(heads=args["heads"], kv=args["kv_heads"],
                    hd=args["head_dim"], window=args["window"],
                    dtype=jnp.dtype(tr["compute_dtype"]), ref=ref)
    for seed in opts.seeds:
        w = args["window"]
        row = {"seed": seed, "device": jax.devices()[0].device_kind,
               "probe": drv.window_probe(seed, **geometry),
               "probe_float8_inputs": drv.window_probe(
                   seed, inputs=jnp.float8_e4m3fn, **geometry),
               "probe_window_minus_1": drv.window_probe(
                   seed, ref_window=w - 1, **geometry),
               "probe_window_plus_1": drv.window_probe(
                   seed, ref_window=w + 1, **geometry),
               "probe_row_off_by_1": drv.window_probe(
                   seed, slot_shift=1, **geometry)}
        if opts.model:
            from defer_tpu import models
            graph = models.cohere_moe(**args)
            params = drv.make_weights(graph, seed, jnp.dtype(
                tr["compute_dtype"]), cfg.get("init_gain", {}))
            ids = np.random.default_rng(seed).integers(
                0, args["vocab"], (1, opts.tokens)).astype(np.int32)
            kw = dict(cfg["reference"]["args"], experts=True)
            hi, hi_chosen = ref.logits(params, ids, **kw)
            lo, lo_chosen = ref.logits(params, ids, inputs=jnp.float8_e4m3fn,
                                       **kw)
            hi, lo = np.asarray(hi), np.asarray(lo)
            picked = np.take_along_axis(hi, lo.argmax(-1)[..., None],
                                        -1)[..., 0]
            best = hi.max(-1)
            gaps = (best - picked) / np.maximum(best - hi.mean(-1), 1e-6)

            def chose(c):
                hot = np.zeros(c.shape[:-1] + (args["num_experts"],), bool)
                np.put_along_axis(hot, np.asarray(c), True, -1)
                return hot

            agree = [float((chose(a) & chose(b)).sum() / np.asarray(a).size)
                     for a, b in zip(hi_chosen, lo_chosen)]
            row.update(float8_worst_logit_gap_share=float(gaps.max()),
                       float8_router_agreement_share=min(agree),
                       float8_router_agreement_by_layer=agree,
                       logit_spread_mean=float((best - hi.mean(-1)).mean()))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
