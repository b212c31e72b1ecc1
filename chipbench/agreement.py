"""The comparisons that decide ``correct``.

The program's outputs are held against the plain references of
``chipbench/reference/`` — never against another path of the program.
"""

from __future__ import annotations

import importlib

import numpy as np


def rel_err(got, ref) -> float:
    """max|got - ref| relative to max|ref| over the compared block."""
    ref = np.asarray(ref, np.float32)
    got = np.asarray(got, np.float32)
    return float(np.abs(got - ref).max()
                 / max(float(np.abs(ref).max()), 1e-6))


def logit_gaps(params, seqs, prompt_len: int, ref_cfg: dict):
    """For each generated token of ``seqs`` [b, t]: how far the plain
    reference's logit of the program's token sits under the reference's
    best, as a share of that position's spread (max - mean).  The
    reference is teacher-forced with the program's own tokens, so every
    position is judged by itself."""
    ref = importlib.import_module(ref_cfg["module"])
    seqs = np.asarray(seqs)
    lg = np.asarray(ref.logits(params, seqs[:, :-1], lo=prompt_len - 1,
                               **ref_cfg["args"]))       # [b, new, vocab]
    chosen = seqs[:, prompt_len:]
    picked = np.take_along_axis(lg, chosen[..., None], axis=-1)[..., 0]
    best = lg.max(-1)
    spread = best - lg.mean(-1)
    return (best - picked) / np.maximum(spread, 1e-6)
