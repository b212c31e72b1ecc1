"""The routed experts' SwiGLU a layer, timed on the chip at the three
cells' decode shapes: ``lax.ragged_dot`` three times (the parent's
path), the ``grouped_experts`` kernel (``defer_tpu/ops/grouped.py``:
gate and up in one pass, then down) under a few row blocks and tile
budgets, and ``megablox.gmm`` under a few tilings.  Chip only.

    python scripts/grouped_product_bench.py [OUT.json] [shape ...]

Each variant is one jitted program over ``LAYERS`` layers' own matrices
(so no matrix is read twice from a cache), called ``CALLS`` times
behind two warm-up calls; a line a variant: microseconds a layer, the
touched matrices' bytes over that (GB/s), the share of 819 GB/s, and
the largest distance from the ``ragged_dot`` result.  Group sizes are
drawn as the cells' seeded routers fill them.
"""

from __future__ import annotations

import json
import sys
import time
from unittest import mock

import numpy as np

sys.path.insert(0, ".")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402
from jax import lax                                         # noqa: E402

from defer_tpu.ops import grouped as G                      # noqa: E402

LAYERS, CALLS, PEAK = 4, 10, 819e9

#: name: (tokens, choices a token, experts routed among, held, d, hidden)
SHAPES = {"granite": (64, 10, 72, 36, 4096, 768),
          "olmoe": (16, 8, 64, 64, 2048, 1024),
          "commandaplus": (16, 8, 128, 16, 4096, 4096)}


def sizes_of(rng, tokens, choices, routed, held):
    """Group sizes of the held experts when every token draws
    ``choices`` distinct experts of ``routed``."""
    sizes = np.zeros(held, np.int32)
    for _ in range(tokens):
        for e in rng.choice(routed, choices, replace=False):
            if e < held:
                sizes[e] += 1
    return sizes


def swiglu_ragged(xs, ex, sizes):
    a = jax.nn.silu(lax.ragged_dot(xs, ex["gate"], sizes)) \
        * lax.ragged_dot(xs, ex["up"], sizes)
    return lax.ragged_dot(a, ex["down"], sizes)


def swiglu_kernel(fused, block, tile_bytes):
    def fn(xs, ex, sizes):
        with mock.patch.object(G, "_ROW_BLOCK", block), \
                mock.patch.object(G, "_TILE_BYTES", tile_bytes):
            call = G.grouped_experts.__wrapped__
            if fused:
                a = call(xs, (ex["gate"], ex["up"]), sizes)
            else:
                a = jax.nn.silu(call(xs, (ex["gate"],), sizes)) \
                    * call(xs, (ex["up"],), sizes)
            return call(a, (ex["down"],), sizes)
    return fn


def swiglu_gmm(tm, tile_bytes):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def product(xs, w, sizes):
        with mock.patch.object(G, "_TILE_BYTES", tile_bytes):
            tn = G._tile_width(*w.shape[1:], 2)
        return gmm(xs, w, sizes, xs.dtype, (tm, w.shape[1], tn))

    def fn(xs, ex, sizes):
        a = jax.nn.silu(product(xs, ex["gate"], sizes)) \
            * product(xs, ex["up"], sizes)
        return product(a, ex["down"], sizes)
    return fn


def run(name, out):
    tokens, choices, routed, held, d, h = SHAPES[name]
    rng = np.random.default_rng(7)
    rows = tokens * choices
    sizes_np = sizes_of(rng, tokens, choices, routed, held)
    sizes = jnp.asarray(sizes_np)
    touched = int((sizes_np > 0).sum())
    need = touched * 3 * d * h * 2            # bytes a layer
    key = jax.random.key(3)
    bf = jnp.bfloat16

    @jax.jit
    def draw(key):
        ks = jax.random.split(key, 3)
        return {"gate": (jax.random.normal(ks[0], (held, d, h), bf)
                         * d ** -0.5).astype(bf),
                "up": (jax.random.normal(ks[1], (held, d, h), bf)
                       * d ** -0.5).astype(bf),
                "down": (jax.random.normal(ks[2], (held, h, d), bf)
                         * h ** -0.5).astype(bf)}

    layers = [draw(k) for k in jax.random.split(key, LAYERS)]
    xs = jax.random.normal(jax.random.key(5), (rows, d), bf)
    live = (jnp.arange(rows) < sizes.sum())[:, None]
    print(f"{name}: rows {rows} groups {held} touched {touched} "
          f"rows in groups {int(sizes_np.sum())} max {int(sizes_np.max())} "
          f"bytes a layer {need / 1e6:.1f} MB = {need / PEAK * 1e6:.1f} us",
          flush=True)

    variants = {"ragged_dot": swiglu_ragged}
    for fused, block, mb in ((False, 32, 8), (True, 32, 8), (True, 16, 8),
                             (True, 32, 4)):
        variants[f"kernel {'fused' if fused else 'apart'} block {block} "
                 f"tile {mb} MiB"] = swiglu_kernel(fused, block, mb << 20)
    for tm, mb in ((16, 4), (16, 2), (128, 4)):
        if rows % tm == 0:
            variants[f"gmm tm {tm} tile {mb} MiB"] = swiglu_gmm(tm, mb << 20)

    ref = None
    for label, fn in variants.items():
        def program(xs, layers, sizes, fn=fn):
            y = jnp.zeros(xs.shape, jnp.float32)
            for ex in layers:
                y = y + jnp.where(live, fn(xs, ex, sizes), 0
                                  ).astype(jnp.float32)
            return y
        try:
            t0 = time.perf_counter()
            step = jax.jit(program)
            y = step(xs, layers, sizes).block_until_ready()
            compile_s = time.perf_counter() - t0
            step(xs, layers, sizes).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(CALLS):
                y = step(xs, layers, sizes)
            y.block_until_ready()
            us = (time.perf_counter() - t0) / CALLS / LAYERS * 1e6
        except Exception as e:          # a variant the compiler refuses
            print(f"  {label}: FAILED {str(e)[:300]}", flush=True)
            out.append({"shape": name, "variant": label, "failed": True})
            continue
        y = np.asarray(y)
        if ref is None:
            ref = y
        err = float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-9))
        print(f"  {label}: {us:8.1f} us a layer  {need / us / 1e3:6.1f} GB/s "
              f"{100 * need / PEAK / us * 1e6:5.1f}% of the peak  "
              f"rel_err {err:.2e}  finite {bool(np.isfinite(y).all())}  "
              f"compile {compile_s:.1f} s", flush=True)
        out.append({"shape": name, "variant": label, "us_a_layer": us,
                    "share_of_peak": need / PEAK / us * 1e6,
                    "rel_err": err, "touched": touched,
                    "need_bytes": need})
    del layers


def main(argv):
    if jax.default_backend() != "tpu":
        raise SystemExit("grouped_product_bench: no TPU: "
                         f"jax found {jax.default_backend()}")
    path = argv[0] if argv else None
    out: list = []
    for name in argv[1:] or SHAPES:
        run(name, out)
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
