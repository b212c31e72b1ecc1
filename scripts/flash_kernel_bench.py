"""The prefill's causal flash kernels alone, timed on the chip:
``flash_latent``, ``flash_band``, ``flash_grouped`` and ``flash_causal``
(``defer_tpu/ops/flash_attention.py``) at the shapes of one call of the
two long-prompt cells' prefills (Kimi's 64 expanded heads and
command-a-plus's 128 query heads on 8 KV heads, window 4096 and none,
one prompt of 8192), at Jamba's and granite's (prompts of 256 and
1024, where a head's triangle is one and three pairs) and at the two
full-head families' (GPT-2's 8 prompts of 512 and
``gpt2xl_long_prompt``'s of 896 on 25 heads of 64, OLMoE's 16 of 1024
on 16 heads of 128).  The two GPT-2 shapes are timed through both
entries, on the same token-major operands ``[prompts, t, 1600]`` laid
row-major as the projection leaves them: ``head-major`` is
``flash_attention(causal=True)`` with the head split, its pads, its
slice and the merge inside the timed call (what a layer paid until
PR 70), ``token-major`` is ``flash_causal_columns`` with nothing around
it.  Chip only.

    python scripts/flash_kernel_bench.py [OUT.json] [shape[:block] ...]

``shape:256`` calls the kernel in blocks of 256 rows and keys in place
of its own choice (``flash_attention``'s shapes only).

A line a shape: milliseconds a call (``CALLS`` calls behind two
warm-ups), the call's operations by the benchmark's own functions
(``chipbench/roofline_latent_moe.py::flash_flops``,
``roofline_window_moe.py::band_flops``) over that as a share of the
matrix peak, the largest distance from the masked softmax in float32
over the first heads, the grid's steps and those that work
(``prefill.flash.grid_steps`` / ``.live_steps``), the heads a block
holds (``.heads_a_block``) and microseconds a step.  It runs on a tree from before PR 50 too (copy it there), which
sets no gauges; nor does a full-head call before PR 58 (``_attn_kernel``
then, whatever the line calls it).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from chipbench import roofline_latent_moe as rl             # noqa: E402
from chipbench import roofline_window_moe as rw             # noqa: E402
from chipbench.roofline import peaks_for                    # noqa: E402
from defer_tpu.obs.registry import REGISTRY                 # noqa: E402
from jax.experimental.layout import Format, Layout          # noqa: E402

from defer_tpu.ops.flash_attention import (                 # noqa: E402
    flash_attention, flash_latent)

try:
    from defer_tpu.ops.flash_attention import flash_causal_columns
except ImportError:         # a tree from before PR 70: one entry
    flash_causal_columns = None

CALLS = 8

#: name: (configuration, kernel, prompts a call, prompt length, window)
SHAPES = {
    "kimi": ("kimi-k2.7-code-5l-ep32", "flash_latent", 1, 8192, None),
    "commandaplus_window": ("command-a-plus-4l-ep8", "flash_band", 1, 8192,
                            4096),
    "commandaplus_full": ("command-a-plus-4l-ep8", "flash_grouped", 1, 8192,
                          None),
    "jamba": ("jamba2-3b", "flash_grouped", 64, 256, None),
    "granite": ("granite-4.0-h-small-10l-ep2", "flash_grouped", 8, 1024,
                None),
    "gpt2xl": ("gpt2-xl", "flash_causal", 8, 512, None),
    "gpt2xl_long": ("gpt2-xl", "flash_causal", 8, 896, None),
    "olmoe": ("olmoe-1b-7b-8l", "flash_causal", 16, 1024, None),
}


def model_args(config: str) -> dict:
    """The configuration's ``model_args``; a family that names neither
    has as many KV heads as query heads, of the stream's share."""
    with open(f"chipbench/configs/{config}.json") as f:
        a = json.load(f)["model_args"]
    return {"kv_heads": a["heads"], "head_dim": a["hidden"] // a["heads"],
            **a}


def operands(kernel: str, a: dict, rows: int, t: int):
    """bf16 operands of one call."""
    ks = jax.random.split(jax.random.key(7), 5)
    bf, h = jnp.bfloat16, a["heads"]
    if kernel == "flash_latent":
        return (jax.random.normal(ks[0], (rows, h, t, a["nope_dim"]), bf),
                jax.random.normal(ks[1], (rows, h, t, a["rope_dim"]), bf),
                jax.random.normal(ks[2], (rows, h, t, a["nope_dim"]), bf),
                jax.random.normal(ks[3], (rows, 1, t, a["rope_dim"]), bf),
                jax.random.normal(ks[4], (rows, h, t, a["v_dim"]), bf))
    d = a["head_dim"]
    return (jax.random.normal(ks[0], (rows, h, t, d), bf),
            jax.random.normal(ks[1], (rows, a["kv_heads"], t, d), bf),
            jax.random.normal(ks[2], (rows, a["kv_heads"], t, d), bf))


def masked_softmax(kernel, ops, window, heads, scale):
    """The first ``heads`` heads of the first prompt in float32."""
    f32 = jnp.float32
    if kernel == "flash_latent":
        qn, qr, kn, kr, v = (o[:1].astype(f32) for o in ops)
        s = (jnp.einsum("bhqd,bhkd->bhqk", qn[:, :heads], kn[:, :heads])
             + jnp.einsum("bhqd,bxkd->bhqk", qr[:, :heads], kr)) * scale
        v = v[:, :heads]
    else:
        q, k, v = (o[:1].astype(f32) for o in ops)
        s = jnp.einsum("bhqd,bxkd->bhqk", q[:, :heads], k[:, :1]) * scale
        v = jnp.broadcast_to(v[:, :1], (1, heads) + v.shape[2:])
    t = s.shape[-1]
    qp, kp = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = kp <= qp
    if window is not None:
        seen &= qp - kp < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def head_major(x, heads: int):
    """``[b, t, heads * d]`` as ``[b, heads, t, d]``."""
    b, t, cols = x.shape
    return x.reshape(b, t, heads, cols // heads).transpose(0, 2, 1, 3)


def token_major(x):
    """``[b, heads, t, d]`` as ``[b, t, heads * d]``."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def timed(call, ops) -> tuple:
    """``call(*ops)`` traced anew: its result, milliseconds a call
    behind two warm-ups and the gauges its trace set."""
    # the gauges are set while a call is traced: trace this one anew,
    # and leave no other shape's reading where a tree sets none
    jax.clear_caches()
    gauges = ("grid_steps", "live_steps", "heads_a_block")
    for gauge in gauges:
        REGISTRY.gauge(f"prefill.flash.{gauge}").set(0)
    y = call(*ops).block_until_ready()
    call(*ops).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(CALLS):
        y = call(*ops)
    y.block_until_ready()
    ms = (time.perf_counter() - t0) / CALLS * 1e3
    return y, ms, {g: REGISTRY.gauge(f"prefill.flash.{g}").value
                   for g in gauges}


def run(name: str, peak: float) -> list:
    name, _, block = name.partition(":")
    block = int(block) if block else None
    config, kernel, rows, t, window = SHAPES[name]
    a = model_args(config)
    ops = operands(kernel, a, rows, t)
    if kernel == "flash_latent":
        flops = rl.flash_flops(a, rows=rows, prompt_len=t)
        scale, heads = (a["nope_dim"] + a["rope_dim"]) ** -0.5, 2

        def call(*o):
            return flash_latent(*o, scale=scale)
    else:
        flops = rw.band_flops(a, rows=rows, prompt_len=t, window=window)
        # heads that share the first KV head, so that one K / V serves
        scale, heads = a["head_dim"] ** -0.5, min(
            2, a["heads"] // a["kv_heads"])

        def call(q, k, v):
            return flash_attention(q, k, v, causal=True, window=window,
                                   block_q=block, block_k=block)
    entries = {kernel: (call, ops, lambda y: y)}
    if kernel == "flash_causal" and a["head_dim"] < 128 \
            and flash_causal_columns:
        # full heads under a lane row: the same operands token-major,
        # row-major on the device as a projection's columns are (the
        # chip's own choice for [8, 896, 1600] has the rows innermost)
        rowmajor = Format(Layout(major_to_minor=(0, 1, 2)),
                          ops[0].sharding)
        cols = tuple(jax.device_put(token_major(o), rowmajor) for o in ops)
        h = a["heads"]
        entries = {
            "head-major": (jax.jit(lambda q, k, v: token_major(call(
                *(head_major(o, h) for o in (q, k, v)))),
                out_shardings=rowmajor), cols, lambda y: head_major(y, h)),
            "token-major": (jax.jit(lambda q, k, v: flash_causal_columns(
                q, k, v, heads=h, block_q=block, block_k=block),
                out_shardings=rowmajor), cols, lambda y: head_major(y, h))}
    ref = masked_softmax(kernel, ops, window, heads, scale)
    out = []
    for entry, (fn, args, as_heads) in entries.items():
        y, ms, gauges = timed(fn, args)
        err = float(jnp.abs(
            as_heads(y)[:1, :heads].astype(jnp.float32) - ref).max())
        steps, live = gauges["grid_steps"], gauges["live_steps"]
        row = {"shape": name, "kernel": kernel, "entry": entry,
               "rows": rows, "prompt_len": t, "window": window,
               "block": block, "ms": ms, "flops": flops,
               "peak_share": flops / peak / (ms / 1e3), "max_err": err,
               **gauges}
        print(f"{name}{f' in blocks of {block}' if block else ''}: {entry} "
              f"{ms:.3f} ms a call, "
              f"{100 * row['peak_share']:.1f}% of the matrix peak, "
              f"err {err:.4f}; "
              # a tree from before PR 50 sets no gauge
              + (f"{live:.0f} of {steps:.0f} steps work, "
                 f"{gauges['heads_a_block']:.0f} heads a block, "
                 f"{ms * 1e3 / steps:.3f} us a step" if steps else
                 "no step gauges"), flush=True)
        out.append(row)
    return out


def main() -> int:
    args = sys.argv[1:]
    out = args.pop(0) if args and args[0].endswith(".json") else None
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"flash_kernel_bench: a chip only, found {dev.platform}",
              file=sys.stderr)
        return 1
    peak = peaks_for(dev.device_kind)["bf16_flops_per_s"]
    rows = [row for name in (args or SHAPES) for row in run(name, peak)]
    if out:
        with open(out, "w") as f:
            json.dump({"device": dev.device_kind, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
