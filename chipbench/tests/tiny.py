"""A tiny benchmark root for the CPU tests: the real drivers and metric
readers (copied), tiny configurations and traffic, one cell per driver."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
PKG = os.path.dirname(HERE)
REPO = os.path.dirname(PKG)

GPT_ARGS = {"num_layers": 2, "hidden": 64, "heads": 4, "seq_len": 64,
            "vocab": 211, "ln_eps": 1e-05}
CONFIGS = {
    "gpt-tiny": {
        "model_args": GPT_ARGS,
        "reference": {"module": "chipbench.reference.gpt2",
                      "args": {"n_layer": 2, "n_head": 4, "eps": 1e-05}}},
}
TRAFFIC = {
    "batch_tiny": {
        "driver": "batch_decode", "batch": 2, "prompt_len": 8,
        "new_tokens": 16, "token_chunk": 2, "max_len": 32,
        "compute_dtype": "float32", "kv_cache": "buffer",
        "check_sequences": 2, "trace_seconds": 0.5},
    "chat_tiny": {
        "driver": "serve_decode", "width": 4, "max_len": 48,
        "params_dtype": "float32", "rate_hz": 25.0, "arrival_seed": 23,
        "lengths": [[4, 4], [6, 5], [8, 3], [5, 6]],
        "warm_requests": 2, "check_requests": 2, "drain_timeout_s": 60,
        "trace_seconds": 1.0},
}
CELLS = [("batch_tiny", "gpt-tiny", "batch_tiny", 1),
         ("chat_tiny", "gpt-tiny", "chat_tiny", 1)]
RENAME = {"gpt2xl_batch_decode": "batch_tiny",
          "gpt2xl_chat_serve": "chat_tiny"}


def make_root(dst: str) -> str:
    """Write the tiny root under ``dst`` and return it.  Metrics keep the
    real manifest's entries, renamed to the tiny cells."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        doc = json.load(f)
    bench = os.path.join(dst, "chipbench")
    for kind in ("drivers", "metrics"):
        shutil.copytree(os.path.join(PKG, kind), os.path.join(bench, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for sub, files in (("configs", CONFIGS), ("traffic", TRAFFIC)):
        os.makedirs(os.path.join(bench, sub))
        for name, body in files.items():
            with open(os.path.join(bench, sub, name + ".json"), "w") as f:
                json.dump(body, f)
    doc["configs"] = [{"name": n, "source": "none: a test preset",
                       "file": f"chipbench/configs/{n}.json", "reduced": [],
                       "why": "tiny preset for the CPU tests"}
                      for n in CONFIGS]
    doc["workloads"] = [{"name": n, "config": c, "traffic": t, "chips": k,
                         "why": "tiny preset for the CPU tests"}
                        for n, c, t, k in CELLS]
    for m in doc["end_to_end"] + doc["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [RENAME[w] for w in m["workloads"]
                              if w in RENAME]
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    return dst
