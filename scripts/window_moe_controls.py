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


def rounded_reference_control(ref, ref_args: dict, params, ids, *,
                              num_experts: int, judged_from: int = 1,
                              inputs: str = "float8_e4m3fn") -> dict:
    """The plain reference against itself with every product's operands
    rounded to ``inputs`` (``float8_e4m3fn``: the nearest precision
    below the cells' bfloat16), on ``ids`` [b, t]: the low-precision
    run *in the program's place* of ``check`` (a).  Both runs are
    teacher-forced on ``ids``, so every position is judged by itself,
    as ``chipbench/agreement.py::logit_gaps`` judges a program's: the
    token the rounded run would emit after ``ids[:, :p]`` is its argmax
    there, and its gap is how far the float32 run's logit of that token
    sits under the float32 run's best, over that position's spread (max
    - mean) — ``logit_gaps``'s measure, for the tokens at positions
    ``judged_from .. t`` (a cell's generated ones, behind its prompt).
    And check (b)'s: the share of the float32 run's expert choices the
    rounded run makes, over all ``t`` positions, by layer."""
    import jax.numpy as jnp

    kw = dict(ref_args, experts=True, lo=judged_from - 1)
    hi, hi_chosen = ref.logits(params, ids, **kw)
    lo, lo_chosen = ref.logits(params, ids, inputs=getattr(jnp, inputs), **kw)
    hi, lo = np.asarray(hi), np.asarray(lo)
    picked = np.take_along_axis(hi, lo.argmax(-1)[..., None], -1)[..., 0]
    best = hi.max(-1)
    gaps = (best - picked) / np.maximum(best - hi.mean(-1), 1e-6)

    def chose(c):
        hot = np.zeros(c.shape[:-1] + (num_experts,), bool)
        np.put_along_axis(hot, np.asarray(c), True, -1)
        return hot

    agree = [float((chose(a) & chose(b)).sum() / np.asarray(a).size)
             for a, b in zip(hi_chosen, lo_chosen)]
    label = inputs.split("_")[0]                # float8, bfloat16
    return {"tokens": list(ids.shape), "judged_from": judged_from,
            f"{label}_worst_logit_gap_share": float(gaps.max()),
            f"{label}_exact_argmax_share": float((gaps <= 0).mean()),
            f"{label}_router_agreement_share": min(agree),
            f"{label}_router_agreement_by_layer": agree,
            "logit_spread_mean": float((best - hi.mean(-1)).mean())}


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
            row.update(rounded_reference_control(
                ref, cfg["reference"]["args"], params, ids,
                num_experts=args["num_experts"]))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
