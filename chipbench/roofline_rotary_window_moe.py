"""Operations and bytes that a step and a prefill of the window-and-
full, two-rotation family (``models.mellum``) need, from shapes alone
(``least_time_s`` is in ``chipbench/roofline.py``; what this family
shares with ``roofline_window_moe`` — the kinds of layer, a band's
operations, the held-bytes check — is that module's, called from here).

*Needed* as there: every weight a step multiplies by once, every live
key/value row once, outputs once — the same work whatever implements
it.  Live rows differ by layer: a window layer's are the
``min(positions, window)`` newest, a full layer's all of them.  Every
layer holds all its experts, and of those only the ones a step
*touches* are needed — which ones is data, so it comes in as
``experts_hit_share``, from the program's own ``decode.moe.*`` counters;
in prefill every expert is touched and the count is bound by
operations: ``top_k`` experts a token.  A rotation costs no matrix
operation and no byte of its own (its table is 64 numbers).
"""

from __future__ import annotations

from chipbench.roofline_window_moe import (  # noqa: F401 — the readers' too
    band_flops, check_held, layer_kinds, live_rows, needed_cache_bytes,
    share_of)


def layer_params(args: dict) -> tuple[int, int, int]:
    """``(attention, router, one routed expert)`` matrix parameters of a
    layer: q and o (hidden x heads x head_dim each), k and v (hidden x
    kv x head_dim each); the router over all experts; an expert's gate,
    up and down."""
    d, hd = args["hidden"], args["head_dim"]
    return (2 * d * hd * (args["heads"] + args["kv_heads"]),
            d * args["num_experts"], 3 * d * args["expert_hidden"])


def row_bytes(args: dict, kv_bytes: int) -> int:
    """A cached position's key and value of one layer of one sequence."""
    return 2 * args["kv_heads"] * args["head_dim"] * kv_bytes


def attend_call_needs(args: dict, *, rows: float, live: float,
                      kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's decode attention over ``live``
    rows a sequence: the live key and value rows once, the queries read
    and the output written once; 4 operations a query head a live row a
    value of the head."""
    qd = args["heads"] * args["head_dim"]
    return (float(rows * 4 * live * qd),
            float(rows * (live * row_bytes(args, kv_bytes)
                          + 2 * qd * kv_bytes)))


def step_bytes_by_part(args: dict, *, rows: float, positions: float,
                       experts_hit_share: float, weight_bytes: int,
                       kv_bytes: int) -> dict:
    """The bytes one decode step of ``rows`` sequences at ``positions``
    cached positions needs, by part: ``weights`` (attention and router
    matrices once a layer, the *touched* experts' once —
    ``experts_hit_share`` of a layer's, on average — and the head),
    ``full_rows`` / ``window_rows`` (live rows by layer kind), ``io``
    (queries in, outputs out, the logits in f32)."""
    attn, router, expert = layer_params(args)
    n_window, n_full = layer_kinds(args)
    win, full = live_rows(args, positions)
    row = row_bytes(args, kv_bytes)
    return {
        "weights": float(
            (args["num_layers"] * (attn + router + experts_hit_share
                                   * args["num_experts"] * expert)
             + args["hidden"] * args["vocab"]) * weight_bytes),
        "full_rows": float(n_full * rows * full * row),
        "window_rows": float(n_window * rows * win * row),
        "io": float(args["num_layers"] * rows * 2 * args["heads"]
                    * args["head_dim"] * kv_bytes
                    + rows * args["vocab"] * 4)}


def decode_step_needs(args: dict, *, rows: float, positions: float,
                      experts_hit_share: float, weight_bytes: int,
                      kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step (:func:`step_bytes_by_part`
    summed; every matrix on every row, ``experts_per_tok`` experts a
    row, attention over the live rows of each kind)."""
    attn, router, expert = layer_params(args)
    n_window, n_full = layer_kinds(args)
    win, full = live_rows(args, positions)
    qd = args["heads"] * args["head_dim"]
    flops = rows * (2 * (args["num_layers"] * (
        attn + router + args["experts_per_tok"] * expert)
        + args["hidden"] * args["vocab"])
        + 4 * qd * (n_window * win + n_full * full))
    nbytes = sum(step_bytes_by_part(
        args, rows=rows, positions=positions,
        experts_hit_share=experts_hit_share, weight_bytes=weight_bytes,
        kv_bytes=kv_bytes).values())
    return float(flops), float(nbytes)


def band_call_needs(args: dict, *, rows: float, prompt_len: float,
                    window: float | None, kv_bytes: int
                    ) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's prefill attention of ``rows``
    prompts: the band's (or the causal triangle's) operations; queries,
    keys and values read and the output written once."""
    qd = args["heads"] * args["head_dim"]
    return (band_flops(args, rows=rows, prompt_len=prompt_len,
                       window=window),
            float(rows * prompt_len * (2 * qd * kv_bytes
                                       + row_bytes(args, kv_bytes))))


def prefill_needs(args: dict, *, rows: float, prompt_len: float,
                  weight_bytes: int, kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens: every matrix on every token,
    ``experts_per_tok`` experts a token, banded and causal attention at
    their own operations, the head on the last position alone.  Bytes:
    every weight once, the cached rows written once."""
    attn, router, expert = layer_params(args)
    n_window, n_full = layer_kinds(args)
    tokens = rows * prompt_len
    head = args["hidden"] * args["vocab"]
    flops = (args["num_layers"] * tokens * 2
             * (attn + router + args["experts_per_tok"] * expert)
             + n_window * band_flops(args, rows=rows, prompt_len=prompt_len,
                                     window=args["window"])
             + n_full * band_flops(args, rows=rows, prompt_len=prompt_len,
                                   window=None)
             + rows * 2 * head)
    win, full = live_rows(args, prompt_len)
    nbytes = ((args["num_layers"] * (attn + router
                                     + args["num_experts"] * expert) + head)
              * weight_bytes
              + rows * (n_window * win + n_full * full)
              * row_bytes(args, kv_bytes)
              + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)
