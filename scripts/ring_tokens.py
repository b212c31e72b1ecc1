"""The tokens the ring generates for every configuration
``lowered_text_hashes.py`` lists (families x stages x ``kv_cache`` x
``beam_width`` x greedy and sampling, with and without the fused
prefill) and for ``weight_dtype="int8"``, to show that a refactor whose
programs differ still decodes what its parent decoded — no chip needed,
not part of the tests.  Run it in two trees and ``diff`` the listings:

    env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python scripts/ring_tokens.py

One ``name sha256 tokens...`` line a generation.  It reads only
``PipelinedDecoder``'s constructor and ``generate``.
"""

import hashlib
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from defer_tpu.runtime.decode import PipelinedDecoder
from lowered_text_hashes import ring_configurations

PLEN, NEW = 5, 6


def generations(name, graph, stages, kv_caches, beams):
    params = graph.init(jax.random.key(0))
    for n in stages:
        prompts = np.random.default_rng(n).integers(
            0, 50, (2 * n, PLEN))
        for kv_cache in kv_caches:
            for beam in beams:
                for weight_dtype in (None, "int8"):
                    dec = PipelinedDecoder(
                        graph, params, num_stages=n, microbatch=2,
                        max_len=16, kv_cache=kv_cache, beam_width=beam,
                        weight_dtype=weight_dtype)
                    tag = (f"ring.{name}.{kv_cache}.beam{beam}.stages{n}"
                           f".w{weight_dtype or 'plain'}")
                    if beam > 1:
                        yield f"{tag}.beam", dec.generate(
                            prompts[: n * 2 // beam], NEW)
                        continue
                    for prefill in (False, True):
                        how = "prefill" if prefill else "forced"
                        yield f"{tag}.{how}.greedy", dec.generate(
                            prompts, NEW, prefill=prefill, token_chunk=2)
                        yield f"{tag}.{how}.sample", dec.generate(
                            prompts, NEW, prefill=prefill, temperature=0.8,
                            top_k=3, seed=7)


def main() -> int:
    for cfg in ring_configurations():
        for name, tokens in generations(*cfg):
            tokens = np.asarray(tokens, np.int64)
            print(name, hashlib.sha256(tokens.tobytes()).hexdigest()[:16],
                  " ".join(map(str, tokens[:, PLEN:].ravel())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
