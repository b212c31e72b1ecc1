"""The routed experts' SwiGLU a layer, timed on the chip at the three
cells' decode shapes: ``lax.ragged_dot`` three times (the parent's
path), the ``grouped_experts`` kernel (``defer_tpu/ops/grouped.py``:
gate and up in one pass, then down) under a few row blocks and tile
budgets, and ``megablox.gmm`` under a few tilings.  Chip only.

    python scripts/grouped_product_bench.py [OUT.json] [shape ...|prefill]
        [--only=WORD,WORD]

Each variant is one jitted program over ``LAYERS`` layers' own matrices
(so no matrix is read twice from a cache), called ``CALLS`` times
behind two warm-up calls; a line a variant: microseconds a layer, the
touched matrices' bytes over that (GB/s), the share of 819 GB/s, and
the largest distance from the ``ragged_dot`` result.  Group sizes are
drawn as the cells' seeded routers fill them.

A name of :data:`PREFILL` times a *prompt's* products instead (PR 56):
the gate-shaped and the down-shaped product alone and the whole SwiGLU,
under ``lax.ragged_dot``, ``megablox.gmm`` at a few ``(tm, tk, tn)`` and
the tiled kernel ``grouped_rows`` at a few row tiles; a line a variant:
microseconds, TFLOP/s over the rows that are some group's, and the share
of the matrix peak (197 TFLOP/s).
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


def experts_of(held, d, h):
    """A jitted ``key -> {"gate", "up", "down"}`` of ``held`` experts'
    bfloat16 stacks ``[d, h]`` / ``[h, d]``."""
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
    return draw


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


#: a prompt's products.  name: (tokens of a piece, choices a token,
#: experts routed among, held, d, hidden).  Where all are held the
#: piece's pairs are one product (``ops/routed.py::expert_dispatch``);
#: else the sorted held pairs go a run of 4096 at a time
#: (``ops/routed.py::expert_dispatch_held``) and
#: the bench takes the middle run, which holds the few groups it spans
PREFILL = {"mellum_prefill": (24576, 8, 64, 64, 2304, 896),
           "olmoe_prefill": (16384, 8, 64, 64, 2048, 1024),
           "granite_run": (8192, 10, 72, 36, 4096, 768),
           "commandaplus_run": (8192, 8, 128, 16, 4096, 4096),
           "kimi_run": (8192, 8, 384, 12, 7168, 2048),
           # Mellum2's shape with one thing changed at a time
           "mellum_rows131072": (16384, 8, 64, 64, 2304, 896),
           "mellum_h1024": (24576, 8, 64, 64, 2304, 1024),
           "mellum_d2048": (24576, 8, 64, 64, 2048, 896)}
#: ``--only=WORD,WORD``: a prompt's variants whose label holds a word
ONLY: tuple = ()
VARIED = ("mellum_rows131072", "mellum_h1024", "mellum_d2048")
MATRIX_PEAK = 197e12
_HELD_RUN = 4096


def prefill_sizes(rng, tokens, choices, routed, held):
    """``(rows, sizes [held])`` of the product a prompt's piece hands
    the grouped product: every pair where all experts are held, the
    middle run of the sorted held pairs else."""
    chosen = np.argsort(rng.random((tokens, routed)), axis=1)[:, :choices]
    sizes = np.bincount(chosen[chosen < held], minlength=held)
    if held == routed:
        return tokens * choices, sizes.astype(np.int32)
    run = _HELD_RUN
    runs = -(-int(sizes.sum()) // run)
    start = (runs - 1) // 2 * run if runs > 1 else 0
    ends = np.cumsum(sizes)
    part = np.clip(ends - start, 0, run) - np.clip(ends - sizes - start,
                                                   0, run)
    return run, part.astype(np.int32)


def tiled(tm):
    def product(xs, mats, sizes):
        with mock.patch.object(G, "_ROW_TILE", tm):
            return G.grouped_rows.__wrapped__(xs, mats, sizes)
    return product


def gmm_product(tm, tk, tn):
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def product(xs, mats, sizes):
        (w,) = mats
        return gmm(xs, w, sizes, xs.dtype,
                   (tm, min(tk, w.shape[1]), min(tn, w.shape[2])))
    return product


def ragged_product(xs, mats, sizes):
    (w,) = mats
    return lax.ragged_dot(xs, w, sizes)


def run_prefill(name, out):
    tokens, choices, routed, held, d, h = PREFILL[name]
    rows, sizes_np = prefill_sizes(np.random.default_rng(7), tokens,
                                   choices, routed, held)
    sizes = jnp.asarray(sizes_np)
    live_rows = int(sizes_np.sum())
    bf = jnp.bfloat16
    layers = 1 if rows > _HELD_RUN else 4

    draw = experts_of(held, d, h)
    exs = [draw(k) for k in jax.random.split(jax.random.key(3), layers)]
    xs = jax.random.normal(jax.random.key(5), (rows, d), bf)
    hs = jax.random.normal(jax.random.key(6), (rows, h), bf)
    print(f"{name}: rows {rows} groups {held} touched "
          f"{int((sizes_np > 0).sum())} rows in groups {live_rows} "
          f"max {int(sizes_np.max())} [{d} x {h}] x {layers} layers",
          flush=True)

    def swiglu(product, fused):
        def fn(xs, hs, ex, sizes):
            del hs
            if fused:
                a = product(xs, (ex["gate"], ex["up"]), sizes)
            else:
                a = jax.nn.silu(product(xs, (ex["gate"],), sizes)) \
                    * product(xs, (ex["up"],), sizes)
            return product(a, (ex["down"],), sizes)
        return fn, 3

    def gate(product):
        return (lambda xs, hs, ex, sizes:
                product(xs, (ex["gate"],), sizes)), 1

    def down(product):
        return (lambda xs, hs, ex, sizes:
                product(hs, (ex["down"],), sizes)), 1

    def gate_up(product):
        return (lambda xs, hs, ex, sizes:
                product(xs, (ex["gate"], ex["up"]), sizes)), 2

    variants = {"ragged_dot gate": gate(ragged_product),
                "ragged_dot down": down(ragged_product),
                "ragged_dot swiglu": swiglu(ragged_product, False)}
    lean = name in VARIED                # ragged_dot and the shipped tile
    tms = (G._ROW_TILE,) if lean else (128, 256, 512) if rows <= _HELD_RUN \
        else (128, 256, 512, 1024)
    for tm in tms:
        variants[f"grouped_rows tm {tm} gate"] = gate(tiled(tm))
        variants[f"grouped_rows tm {tm} down"] = down(tiled(tm))
        variants[f"grouped_rows tm {tm} gate_up"] = gate_up(tiled(tm))
        variants[f"grouped_rows tm {tm} swiglu"] = swiglu(tiled(tm), True)
    for tm, tk, tn in ((512, 1 << 20, 1 << 20), (512, 1024, 1024),
                       (512, 512, 1024), (256, 1024, 1024),
                       (128, 128, 128)):
        if rows % tm == 0 and not lean:
            variants[f"gmm ({tm}, {tk}, {tn}) gate"] = gate(
                gmm_product(tm, tk, tn))
            variants[f"gmm ({tm}, {tk}, {tn}) down"] = down(
                gmm_product(tm, tk, tn))

    live = (jnp.arange(rows) < live_rows)[:, None]
    refs: dict = {}
    for label, (fn, products) in variants.items():
        if ONLY and not any(word in label for word in ONLY):
            continue
        def program(xs, hs, exs, sizes, fn=fn):
            # the last layer's rows, and a sum that keeps the others
            ys = [fn(xs, hs, ex, sizes) for ex in exs]
            return jnp.where(live, ys[-1], 0), sum(
                y[0, 0].astype(jnp.float32) for y in ys)
        try:
            t0 = time.perf_counter()
            step = jax.jit(program)
            y, _ = jax.block_until_ready(step(xs, hs, exs, sizes))
            compile_s = time.perf_counter() - t0
            jax.block_until_ready(step(xs, hs, exs, sizes))
            t0 = time.perf_counter()
            for _ in range(CALLS):
                r = step(xs, hs, exs, sizes)
            jax.block_until_ready(r)
            us = (time.perf_counter() - t0) / CALLS / layers * 1e6
        except Exception as e:          # a variant the compiler refuses
            print(f"  {label}: FAILED {str(e)[:300]}", flush=True)
            out.append({"shape": name, "variant": label, "failed": True})
            continue
        y = np.asarray(y.astype(jnp.float32))
        kind = label.rsplit(" ", 1)[-1]
        ref = refs.setdefault(kind, y)
        err = float(np.abs(y - ref).max() / max(np.abs(ref).max(), 1e-9))
        flops = 2 * live_rows * d * h * products
        print(f"  {label}: {us:9.1f} us  {flops / us / 1e6:6.1f} TFLOP/s "
              f"{100 * flops / MATRIX_PEAK / us * 1e6:5.1f}% of the matrix "
              f"peak  rel_err {err:.2e}  finite "
              f"{bool(np.isfinite(y).all())}  compile {compile_s:.1f} s",
              flush=True)
        out.append({"shape": name, "variant": label, "us": us,
                    "tflops": flops / us / 1e6,
                    "share_of_matrix_peak": flops / MATRIX_PEAK / us * 1e6,
                    "rel_err": err, "rows": rows, "groups": held,
                    "rows_in_groups": live_rows, "k_n": [d, h]})
    del exs


def run(name, out):
    if name in PREFILL:
        return run_prefill(name, out)
    tokens, choices, routed, held, d, h = SHAPES[name]
    rng = np.random.default_rng(7)
    rows = tokens * choices
    sizes_np = sizes_of(rng, tokens, choices, routed, held)
    sizes = jnp.asarray(sizes_np)
    touched = int((sizes_np > 0).sum())
    need = touched * 3 * d * h * 2            # bytes a layer
    key = jax.random.key(3)
    bf = jnp.bfloat16

    draw = experts_of(held, d, h)
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
        if rows % tm == 0 and not lean:
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
    global ONLY
    ONLY = tuple(w for a in argv if a.startswith("--only=")
                 for w in a[7:].split(","))
    argv = [a for a in argv if not a.startswith("--only=")]
    path = argv[0] if argv else None
    out: list = []
    names = argv[1:] or SHAPES
    for name in PREFILL if names == ["prefill"] else names:
        run(name, out)
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
