"""``grouped_rows`` (``defer_tpu/ops/grouped.py``) on the chip where the
CPU tests cannot reach: Mosaic's handling of a partial last row tile
(4000 and 1000 rows of 128-row tiles, 520 rows with no group), a tail
of ``inf`` behind the groups, one matrix and gate-and-up, each against
``lax.ragged_dot`` on the finite rows.  Chip only, ~20 s; a line a
case, exit 1 on the first that fails.

    python scripts/grouped_rows_check.py
"""

from __future__ import annotations

import sys

import numpy as np

sys.path.insert(0, ".")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
from jax import lax                                         # noqa: E402

from defer_tpu.ops import grouped as G                      # noqa: E402

#: (rows, k, n, sizes a group): Kimi's widths both ways with a tail and
#: without, Mellum2's down shape with one group, its gate shape with none
CASES = (
    (4000, 2048, 7168, [170, 0, 300, 411, 9, 0, 0, 1200, 37, 255, 1, 600]),
    (4000, 7168, 2048, [170, 0, 300, 411, 9, 0, 0, 1200, 37, 255, 1, 1617]),
    (1000, 896, 2304, [0, 0, 999, 0, 0]),
    (520, 2304, 896, [0, 0, 0]))


def main() -> int:
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_rows_check: no TPU: "
                         f"jax found {jax.default_backend()}")
    bf, f32 = jnp.bfloat16, jnp.float32
    for rows, k, n, sizes in CASES:
        ks = jax.random.split(jax.random.key(rows + k), 3)
        s = jnp.asarray(sizes, jnp.int32)
        held = int(s.sum())
        xs = jax.random.normal(ks[0], (rows, k), bf).at[held:].set(jnp.inf)
        g, u = ((jax.random.normal(key, (len(sizes), k, n), bf)
                 * k ** -0.5).astype(bf) for key in ks[1:])
        clean = jnp.where(jnp.isfinite(xs), xs, 0)
        wants = (
            ((g,), lax.ragged_dot(clean, g, s)),
            ((g, u), jax.nn.silu(lax.ragged_dot(
                clean, g, s, preferred_element_type=f32))
             * lax.ragged_dot(clean, u, s, preferred_element_type=f32)))
        live = np.arange(rows) < held
        for mats, want in wants:
            got = np.asarray(G.grouped_rows(xs, mats, s).astype(f32))
            want = np.asarray(want.astype(f32))
            err = float(np.abs(got[live] - want[live]).max()) if held else 0.
            ok = err < 0.05 and not got[~live].any() \
                and bool(np.isfinite(got).all())
            print(f"grouped_rows_check rows {rows} k {k} n {n} mats "
                  f"{len(mats)} held {held}: max_err {err:.4f} tail_zero "
                  f"{not got[~live].any()} ok {ok}", flush=True)
            if not ok:
                return 1
    print("grouped_rows_check passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
