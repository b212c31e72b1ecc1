"""The step that writes while it attends (``ops/kv_cache.py::kv_step``)
against the two calls it replaces, bit for bit, on whatever backend JAX
has — the tests hold the same on the CPU's interpreter
(``tests/test_kv_cache.py``), this is the chip's turn:

    python scripts/kv_step_check.py [kv_heads head_dim sequences positions groups]

(default ``50 32 8 768 1``: the bytes a position of gpt2-xl's ring in
the batch cell at heads of 32 — since PR 68 the ring holds heads of 64
joined and ``kv_step`` is the kernel of heads that pair into no lane
row; the format is refused where it does not write in its attention).
A step at every given position
of a bfloat16 buffer of noise: ``fmt.step`` and ``fmt.write_position``
then ``fmt.attend`` from the same buffers, the outputs and both buffers
compared as bits.  One JSON line; exit 0 when nothing differs.
"""

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from defer_tpu.ops.kv_cache import KVCacheFormat, attend_blocks


def main(kv=50, hd=32, b=8, positions=768, groups=1) -> int:
    fmt = KVCacheFormat(kv, hd, positions, jnp.bfloat16, groups=groups)
    assert fmt.writes_in_attention
    _, tl = attend_blocks(kv, hd, positions + 1, 2)
    rng = np.random.default_rng(0)
    layer = {key: jnp.asarray(rng.standard_normal(s.shape), s.dtype)
             for key, s in fmt.buffers(b).items()}
    # a block's first and last row, either side of a lane row's edge,
    # the first and the last position, a bubble's scratch row
    at = sorted({0, 127, 128, tl - 1, tl, tl + 129, 2 * tl - 1, 2 * tl,
                 positions - 1, fmt.scratch_position} & set(range(positions + 1)))

    @jax.jit
    def two(q, layer, rows, pos, group):
        layer = fmt.write_position(layer, rows, pos, group=group)
        return fmt.attend(q, layer, pos, group=group), layer

    one = jax.jit(fmt.step)
    differ = 0
    for pos in at:
        q = jnp.asarray(rng.standard_normal((b, kv * hd)), jnp.bfloat16)
        rows = fmt.rows(*(jnp.asarray(rng.standard_normal((b, kv * hd)),
                                      jnp.bfloat16) for _ in range(2)))
        group = jnp.int32(pos % groups)
        want, got = (fn(q, layer, rows, jnp.int32(pos), group)
                     for fn in (two, one))
        for a, c in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            differ += int((np.asarray(a).view(np.uint16)
                           != np.asarray(c).view(np.uint16)).sum())
        layer = got[1]
    print(json.dumps({"platform": jax.devices()[0].platform,
                      "device_kind": jax.devices()[0].device_kind,
                      "block_positions": tl, "positions": at,
                      "values_that_differ": differ}))
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main(*(int(a) for a in sys.argv[1:])))
