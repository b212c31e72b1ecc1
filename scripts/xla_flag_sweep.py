"""XLA flag sweep for the MFU-ceiling hunt.

Each flag set gets its own ``bench.py`` subprocess (focused config: the
best-known batch/chunk/microbatch); this parent never initialises a jax
backend, so each child in turn owns the chip.  Flags travel via
``DEFER_XLA_COMPILER_OPTS`` -> per-executable ``compiler_options``
(``defer_tpu/utils/xla_opts.py``).  Flags probed are the documented TPU
performance levers relevant to a conv-dominated pipelined workload:

- ``scoped_vmem_limit_kib``: more VMEM headroom for fusions (less HBM
  spill between the conv and its fused elementwise epilogue);
- ``latency_hiding_scheduler``: overlaps the pipeline's ppermute
  collectives with stage compute;
- ``async collective_permute``: makes the stage->stage hop itself
  asynchronous.

Per-flag progress lines go to stderr; stdout gets ONE final JSON line
with the scoreboard ``value`` (best pipeline img/s over all flag sets)
and ``unit`` keys, like every other measurement script.  The combined
artifact is rewritten incrementally to ``DEFER_SWEEP_OUT`` (default
XLA_SWEEP.json in the repo root) — a timeout keeps completed rows,
same contract as bench_decode.
"""

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: async collective_permute is now the pipeline's TPU DEFAULT
#: (utils/xla_opts.RING_DEFAULTS, adopted off this sweep's r5 result),
#: so the control row must switch it OFF explicitly — a bare env no
#: longer isolates flags.
FLAG_SETS = {
    "no_async_cp": "xla_enable_async_collective_permute=false",
    "default": "",
    "default+vmem64m": "xla_tpu_scoped_vmem_limit_kib=65536",
    "default+lhs": "xla_tpu_enable_latency_hiding_scheduler=true",
    "default+lhs+vmem64m": ("xla_tpu_enable_latency_hiding_scheduler=true "
                            "xla_tpu_scoped_vmem_limit_kib=65536"),
}


def main():
    out_path = os.environ.get("DEFER_SWEEP_OUT",
                              os.path.join(REPO, "XLA_SWEEP.json"))
    per_run_s = float(os.environ.get("DEFER_SWEEP_RUN_TIMEOUT_S", "1200"))
    rows = {}

    from defer_tpu.utils.artifact import flush_artifact

    def flush():
        return flush_artifact(out_path,
                              {"metric": "resnet50_xla_flag_sweep",
                               "value": 0.0, "unit": "inferences/sec",
                               "rows": rows}, merge_key="rows",
                              value_key="pipeline_img_per_s")

    for name, flags in FLAG_SETS.items():
        p = None
        env = dict(os.environ)
        env["DEFER_XLA_COMPILER_OPTS"] = flags
        t0 = time.time()
        try:
            p = subprocess.run(
                [sys.executable, os.path.join(REPO, "bench.py"),
                 "--batches", "128", "--chunks", "32",
                 "--microbatches", "32"],
                capture_output=True, text=True, timeout=per_run_s, env=env,
                cwd=REPO)
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else ""
            d = json.loads(line)
            rows[name] = {
                "flags": flags,
                "pipeline_img_per_s": d.get("value"),
                "single_chip_best_img_per_s":
                    d.get("single_chip_best_img_per_s"),
                "mfu_pipeline_best": d.get("mfu_pipeline_best"),
                "mfu_best": d.get("mfu_best"),
                "wall_s": round(time.time() - t0, 1),
            }
        except subprocess.TimeoutExpired:
            rows[name] = {"flags": flags, "error": "timeout",
                          "wall_s": round(time.time() - t0, 1)}
        except Exception as e:  # noqa: BLE001 — record and continue
            rows[name] = {"flags": flags, "error": repr(e)[:300],
                          "stderr": p.stderr[-500:] if p is not None else ""}
        print(json.dumps({name: rows[name]}), file=sys.stderr, flush=True)
        final = flush()
    print(json.dumps(final))


if __name__ == "__main__":
    main()
