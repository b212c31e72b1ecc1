"""Operations and bytes a step needs, from shapes alone, and the least
time the chip could take for them.

*Needed* bytes are what the algorithm must move once: every weight once
per step, every live key/value row once, inputs and outputs once.  What
the program moves beyond that (copies of a whole cache, padding, layer
activations that spill) is exactly what a roofline share is there to
show, so it is not counted.  The share is ``least_time / device_time``
and cannot pass 100% unless one of these functions over-counts.
"""

from __future__ import annotations

import json
import os


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown kind is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json; "
            f"add its published peaks (with their source) before "
            f"computing a roofline share on it")
    return table[device_kind]


def least_time_s(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """``(seconds, bound)``: the larger of flops over the matrix peak and
    bytes over the memory peak, and which of the two it was."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


# -- GPT-2 decode step ------------------------------------------------------

def gpt_matmul_params(n_layer: int, n_embd: int, vocab: int) -> int:
    """Weights a decode step multiplies by: 12 d^2 a block (qkv, proj and
    the 4x MLP) and the output head; the embedding tables are gathered,
    a row a token, and are not counted."""
    return n_layer * 12 * n_embd * n_embd + n_embd * vocab


def gpt_decode_step_needs(*, n_layer: int, n_embd: int, vocab: int,
                          rows: float, live_positions: float,
                          weight_bytes: int, kv_bytes: int
                          ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step: ``rows`` sequences, one
    token each, attending over ``live_positions`` cached positions on
    average.  Weights are read once whatever the batch; every live key
    and value row once; the logits are written once in f32."""
    w = gpt_matmul_params(n_layer, n_embd, vocab)
    flops = rows * (2 * w + n_layer * 4 * live_positions * n_embd)
    nbytes = (w * weight_bytes
              + rows * n_layer * 2 * live_positions * n_embd * kv_bytes
              + rows * vocab * 4)
    return float(flops), float(nbytes)
