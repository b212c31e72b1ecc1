"""``kv_attend_joined`` (``defer_tpu/ops/kv_cache.py``) alone, timed on
the chip: one decode step's attention of one layer over joined bfloat16
buffers of noise, every sequence at one position as the ring has them.
Chip only.

    python scripts/joined_attend_bench.py [OUT.json] \\
        [--side-by-side=lane|row] [case ...]

A case is ``kv[xwidth]:queries:sequences:rows:pos[:cap]`` — KV heads
(of 128, or of ``width``: ``8x64`` is LFM2's eight heads of 64, two a
lane row, in a tree whose function takes them: PR 66), queries a KV
head, sequences, the buffers' rows (a multiple of 16) and the position;
``cap`` sets ``_BLOCK_POSITIONS`` for that case, in a tree that has it.
Without cases: Jamba's call (one KV head, 20
queries, 256 sequences, 4368 rows) along the cell's window — 384, the
traced window's middle; 1136, the 40 s window's mean; 2016, 4351 — and
the same at a cap of 1024; that call over buffers of 512 rows, whose
block *is* 512 rows whatever the tree (the bytes a block of live rows
fetches, a sequence a grid step); Mellum2's and command-a-plus's
full layers at their windows' ends (4 x 8 queries at 28671 of 28688
rows, 8 x 16 at 12287 of 12304; 16 sequences), which no tree may move;
and granite's call (8 KV heads, 4 queries, 64 sequences, 3088 rows) at
the traced window's position, the 40 s window's mean and its end —
1151, 1839, 2623 — with a group of 2 beside it, the low edge of what
holds joined rows (``_JOINED_GROUP``); and LFM2's call (8 KV heads of
64, 4 queries, 128 sequences, 2560 rows) at the traced window's middle,
the generation's mean and its end — 740, 1535, 2559; and GPT-2's call
(25 heads of 64 and a phantom, one query a head: ``26x64:1``; 8
sequences, 784 rows) at the batch cells' first, mean and last position
— 512, 640, 767 —, the long prompt's (944 rows, 911) and the four-chip
ring's group of 2 (since PR 68 the whole row's heads side by side, one
head of 1664 columns and 26 query rows; in a tree from before it 13
lane-row heads of two query rows).  The function is called as it is,
whatever ``KVCacheFormat.joined`` says of the group in that tree.
``--side-by-side`` sets, for the cases behind it, how many of a row's
heads the wrapper hands the kernel as one head (in a tree that has the
rule, ``_side_by_side_heads``; the kernel is the same): ``lane`` a lane
row's (one head of 128, or two of 64: 13 passes a block over GPT-2's
row), ``row`` the whole row's (one pass; the query rows ``kv`` times as
wide, zeros outside a head's own columns) — PR 68's sweep, from the
tree: ``OUT.json --side-by-side=lane 26x64:1:8:784:640 8x64:4:128:2560:740
--side-by-side=row 26x64:1:8:784:640 8x64:4:128:2560:740``.

A line a case: the block's extents as the tree's ``joined_block_rows``
gives them (``(1, positions)`` before PR 63, whose function returned
the positions alone), the grid, milliseconds a call (``CALLS`` calls
behind two warm-ups, each its own dispatch: the host's clock, and for a
call under ~0.1 ms the host's pace), ``device_ms`` (``layers`` calls over
as many buffer pairs inside one jitted program, each call's output the
next one's query, as a stage's layers follow one another: the chip sets
the pace; at most ``LAYERS`` pairs and ``CHAIN_BYTES`` of buffers),
``traced_us`` (one run of that program under ``jax.profiler``, a call's
microseconds by kind of operation as ``chipbench/trace.py`` names them:
the kernel's own beside the fusions around it), the
bytes of the live rows and of the blocks fetched over that device time
as shares of the memory peak, and the largest distance from
``attend_einsum`` in float32 over the first two sequences.
"""

from __future__ import annotations

import inspect
import json
import sys
import tempfile
import time

sys.path.insert(0, ".")

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from chipbench.roofline import peaks_for                    # noqa: E402
from chipbench.trace import reduce_trace                    # noqa: E402
from defer_tpu.ops import kv_cache                          # noqa: E402

CALLS, HD = 50, 128
LAYERS, CHAIN_BYTES, CHAINS = 24, 3e9, 10

JAMBA, GRANITE, LFM2 = "1:20:256:4368:", "8:4:64:3088:", "8x64:4:128:2560:"
GPT2 = "26x64:1:8:"
DEFAULT = [JAMBA + "384", JAMBA + "1136", JAMBA + "2016", JAMBA + "4351",
           JAMBA + "384:1024", JAMBA + "1136:1024", JAMBA + "2016:1024",
           "1:20:256:512:384", "4:8:16:28688:28671", "8:16:16:12304:12287",
           GRANITE + "1151", GRANITE + "1839", GRANITE + "2623",
           "8:2:64:3088:1839", LFM2 + "740", LFM2 + "1535", LFM2 + "2559",
           GPT2 + "784:512", GPT2 + "784:640", GPT2 + "784:767",
           GPT2 + "944:911", "26x64:1:2:784:640"]


def geometry(kv: int, hd: int, rows: int, b: int) -> tuple[int, int]:
    """``(sequences, positions)`` of the tree's block."""
    fn = kv_cache.joined_block_rows
    if "b" in inspect.signature(fn).parameters:
        return fn(kv, hd, rows, 2, b)
    return 1, fn(kv, hd, rows, 2)


def chained(call, q, like, key) -> tuple[int, float, dict]:
    """``(layers, seconds a call, traced microseconds a call by kind)``
    of ``layers`` calls inside one program, each over a buffer pair of
    its own and on the call's output before it: no dispatch between two
    calls, so a call shorter than a dispatch is timed at the chip's
    pace."""
    layers = int(max(2, min(LAYERS, CHAIN_BYTES // (2 * like.nbytes))))
    keys = jax.random.split(key, 2 * layers)
    bufs = [jax.random.normal(k, like.shape, like.dtype) for k in keys]

    @jax.jit
    def program(q, ks, vs):
        for k, v in zip(ks, vs):
            q = call(q, k, v)
        return q

    ks, vs = bufs[:layers], bufs[layers:]
    for _ in range(2):
        out = program(q, ks, vs).block_until_ready()
    start = time.perf_counter()
    for _ in range(CHAINS):
        out = program(q, ks, vs)
    out.block_until_ready()
    seconds = (time.perf_counter() - start) / CHAINS / layers
    # the same program under the profiler: a call's microseconds by kind
    # of operation (the kernel, and the fusions around it)
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            program(q, ks, vs).block_until_ready()
        by_kind = {kind: s * 1e6 / layers
                   for kind, s in reduce_trace(trace_dir, 1).top_ops()}
    return layers, seconds, by_kind


#: ``--side-by-side``: the heads that are one head to the kernel
SIDE_BY_SIDE = {"lane": lambda hd, kv, g: kv_cache._lane_heads(hd),
                "row": lambda hd, kv, g: kv}


def run(case: str, peak_bytes_s: float, side: str | None = None) -> dict:
    heads, *rest = case.split(":")
    kv, hd = map(int, heads.split("x")) if "x" in heads else (int(heads), HD)
    g, b, rows, pos, *cap = map(int, rest)
    if cap and not hasattr(kv_cache, "_BLOCK_POSITIONS"):
        return {"case": case, "skipped": "this tree has no cap to set"}
    rule = getattr(kv_cache, "_side_by_side_heads", None)
    if side and rule is None:
        return {"case": case, "skipped": "this tree has no rule to set"}
    kept = getattr(kv_cache, "_BLOCK_POSITIONS", None)
    if cap:
        kv_cache._BLOCK_POSITIONS = cap[0]
    if side:
        kv_cache._side_by_side_heads = SIDE_BY_SIDE[side]
    try:
        sb, tl = geometry(kv, hd, rows, b)
        keys = jax.random.split(jax.random.key(rows + pos), 3)
        k_buf, v_buf = (jax.random.normal(key, (1, b, rows, kv * hd),
                                          jnp.bfloat16) for key in keys[:2])
        q = jax.random.normal(keys[2], (b, kv * g * hd), jnp.bfloat16)
        at, group = jnp.full(b, pos, jnp.int32), jnp.zeros(1, jnp.int32)
        # a fresh trace a case: the block is sized when the kernel is built
        call = jax.jit(lambda q, k, v: kv_cache.kv_attend_joined.__wrapped__(
            q, k, v, at, group, kv=kv))
        for _ in range(2):
            out = call(q, k_buf, v_buf).block_until_ready()
        start = time.perf_counter()
        for _ in range(CALLS):
            out = call(q, k_buf, v_buf)
        out.block_until_ready()
        seconds = (time.perf_counter() - start) / CALLS
        layers, device_seconds, traced_us = chained(call, q, k_buf, keys[2])
    finally:
        if cap:
            kv_cache._BLOCK_POSITIONS = kept
        if side:
            kv_cache._side_by_side_heads = rule
    item = {key: buf[0, :2].reshape(2, rows, kv, hd).swapaxes(1, 2)
            .astype(jnp.float32) for key, buf in (("k", k_buf), ("v", v_buf))}
    want = kv_cache.attend_einsum(q[:2].astype(jnp.float32), item, pos)
    row = 2 * kv * hd * 2               # a position's keys and values
    live = b * (pos + 1) * row
    fetched = b * min(-(-(pos + 1) // tl) * tl, rows) * row
    return {"case": case, **({"side_by_side": side} if side else {}),
            "block": [sb, tl], "grid": [b // sb, -(-rows // tl)],
            "ms": seconds * 1e3, "layers": layers,
            "device_ms": device_seconds * 1e3, "traced_us": traced_us,
            "live_mb": live / 1e6,
            "fetched_mb": fetched / 1e6,
            "live_share_of_peak": live / device_seconds / peak_bytes_s,
            "fetched_share_of_peak": fetched / device_seconds / peak_bytes_s,
            "max_err": float(jnp.max(jnp.abs(
                out[:2].astype(jnp.float32) - want)))}


def main(argv: list[str]) -> int:
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"joined_attend_bench: a {device.platform} device times "
              "nothing a chip would", file=sys.stderr)
        return 1
    out = argv[0] if argv else None
    peak = peaks_for(device.device_kind)["hbm_bytes_per_s"]
    lines, side = [], None
    for case in argv[1:] or DEFAULT:
        if case.startswith("--side-by-side="):
            side = case.split("=")[1]
            continue
        try:
            lines.append(run(case, peak, side))
        except Exception as e:      # a form the compiler refuses: say so
            if not side:
                raise
            lines.append({"case": case, "side_by_side": side,
                          "error": repr(e)[:400]})
        print(json.dumps(lines[-1]), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump({"device_kind": device.device_kind, "calls": CALLS,
                       "cases": lines}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
