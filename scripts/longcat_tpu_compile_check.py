"""The LongCat-Flash-Chat cell's decode and prefill programs under the
TPU's own compiler, at the cell's size (4 double layers at published
widths, 16 of 512 routed experts held in each beside the 256
zero-compute ones, 16 sequences, 6144 in, 10240 positions, bf16, 32
tokens a call) — no chip needed, not part of the tests.

What it answers before any chip time is spent:

* do the programs fit one v5e by the compiler's own count
  (``memory_analysis``: 10.35 GB of weights, 3.36 GB of latent rows in
  two buffers a double layer with the scratch group, and what the
  compiler adds; the prefill crosses the stage one sequence at a time —
  ``PipelinedDecoder._prefill_rows`` — because the widest activation is
  the expanded heads' 16384 columns);
* **how large the compiler makes the caches' arguments**: each
  argument's bytes are counted from the layout the compiled program
  gives it, tiles and all, and their sum over both sublayers' buffers
  is held to 1.12 of the need (1152 B a row: 576 bfloat16 values);
* does either program *produce* an array the size of a weight matrix,
  of a layer's experts or of a cache buffer inside a loop
  (``scripts/hlo_cache_ops.py``);
* does the decode program hold two ``latent_attend`` calls a double
  layer and the prefill two ``flash_latent`` calls.

    env JAX_PLATFORMS=cpu python scripts/longcat_tpu_compile_check.py

A few minutes and ~15 GB of host memory (the weights are zeros); one
JSON line; exit 0 when both programs fit under 16 GB, the caches'
arguments stay within 1.12 of the need and nothing weight-sized or
buffer-sized is produced inside a loop.  ``LONGCAT_CHECK_DUMP=DIR``
writes both compiled texts.  A process of its own, like the other
compile checks: the TPU's library is locked machine-wide while it runs.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from defer_tpu.models import longcat_flash
from kimi_tpu_compile_check import check


def main() -> int:
    # the checks are the latent family's, whatever its blocks hold
    return check(longcat_flash, "longcat-flash-chat-4l-ep32.json",
                 "batch16_6144in_4096out_chunk32.json", "longcat")


if __name__ == "__main__":
    sys.exit(main())
