"""Operations and bytes that a step and a prefill of the window-and-
full, held-share family (``models.cohere_moe``) need, from shapes alone
(``least_time_s`` is in ``chipbench/roofline.py``).

*Needed* as there: every weight a step multiplies by once, every live
key/value row once, outputs once.  Live rows differ by layer: a window
layer's are the ``min(positions, window)`` newest, a full layer's all of
them.  Of the routed experts only the *held* exist on the chip, and of
those only the ones a step *touches* are needed — which ones is data,
so it comes in as ``experts_hit_share``, from the program's own
``decode.moe.*`` counters; in prefill every held expert is touched and
the count is bound by operations: the pairs that fell to held experts
(``held_share`` of ``top_k`` a token), not all ``top_k``.
"""

from __future__ import annotations

WINDOW_LAYER = "sliding_attention"


def layer_kinds(args: dict) -> tuple[int, int]:
    """``(window layers, full layers)`` of ``model_args``."""
    kinds = [args["layer_types"][i % len(args["layer_types"])]
             for i in range(args["num_layers"])]
    n_window = sum(k == WINDOW_LAYER for k in kinds)
    return n_window, len(kinds) - n_window


def layer_params(args: dict) -> tuple[int, int, int, int]:
    """``(attention, router, shared experts, one routed expert)`` matrix
    parameters of a layer: q and o (hidden x heads x head_dim each), k
    and v (hidden x kv x head_dim each); the router over all experts;
    the shared experts' gate, up and down; an expert's three."""
    d, hd = args["hidden"], args["head_dim"]
    attn = 2 * d * hd * (args["heads"] + args["kv_heads"])
    expert = 3 * d * args["expert_hidden"]
    return (attn, d * args["num_experts"], args["num_shared"] * expert,
            expert)


def held_experts(args: dict) -> int:
    lo, hi = args.get("experts_held") or (0, args["num_experts"])
    return hi - lo


def live_rows(args: dict, positions: float) -> tuple[float, float]:
    """Rows a window layer and a full layer hold live at ``positions``."""
    return min(positions, args["window"]), positions


def needed_cache_bytes(args: dict, *, rows: float, max_len: int,
                       kv_bytes: int) -> tuple[float, float]:
    """``(window layers', full layers')`` bytes of the cache a
    deployment needs for ``rows`` sequences to ``max_len`` positions:
    keys and values, a window's rows a window layer."""
    n_window, n_full = layer_kinds(args)
    row = 2 * args["kv_heads"] * args["head_dim"] * kv_bytes
    return (n_window * rows * min(max_len, args["window"]) * row,
            n_full * rows * max_len * row)


def check_held(held: float | None, needed: float, *, groups: int,
               rows_held: int) -> None:
    """Raise where the program holds (``held``: its gauges; None where
    a program has none) more than the layout's own padding over
    ``needed``: the ring's scratch group beside its ``groups`` and the
    scratch row in whole tiles of 16 positions."""
    allowed = (groups + 1) / groups * (rows_held + 16) / rows_held
    if held and held > allowed * needed:
        raise ValueError(
            f"the program holds {held:.0f} B of cache, "
            f"{held / needed:.4f} times the {needed:.0f} B the "
            f"configuration needs (allowed: {allowed:.4f})")


def share_of(least_s: float, measured_s: float, what: str) -> float:
    """``least_s`` over ``measured_s`` in percent; over 100 the needs
    were counted too high (or the time leaves work out), and that is an
    error, not a number."""
    share = 100.0 * least_s / measured_s
    if share > 100.0:
        raise ValueError(f"{what} at {share:.1f}% of its roofline: the "
                         "needs are counted too high")
    return share


def attend_call_needs(args: dict, *, rows: float, positions: float,
                      kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's decode attention, averaged over
    the layers as they occur: a call's live key and value rows once,
    its queries read and its output written once; 4 operations a query
    head a live row a value of the head."""
    n_window, n_full = layer_kinds(args)
    win, full = live_rows(args, positions)
    live = (n_window * win + n_full * full) / (n_window + n_full)
    qd = args["heads"] * args["head_dim"]
    flops = rows * 4 * live * qd
    nbytes = rows * (2 * live * args["kv_heads"] * args["head_dim"]
                     + 2 * qd) * kv_bytes
    return float(flops), float(nbytes)


def decode_step_needs(args: dict, *, rows: float, positions: float,
                      experts_hit_share: float, held_share: float,
                      weight_bytes: int, kv_bytes: int
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences at
    ``positions`` cached positions: attention, router and shared
    weights once a layer, the *touched* held experts' once
    (``experts_hit_share`` of the held, a layer, on average), live rows
    by layer kind, the head once, the logits written once in f32."""
    attn, router, shared, expert = layer_params(args)
    n_layer = args["num_layers"]
    call_flops, call_bytes = attend_call_needs(
        args, rows=rows, positions=positions, kv_bytes=kv_bytes)
    head = args["hidden"] * args["vocab"]
    flops = rows * 2 * (n_layer * (attn + router + shared + held_share
                                   * args["experts_per_tok"] * expert)
                        + head) + n_layer * call_flops
    nbytes = ((n_layer * (attn + router + shared + experts_hit_share
                          * held_experts(args) * expert) + head)
              * weight_bytes + n_layer * call_bytes
              + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)


def band_flops(args: dict, *, rows: float, prompt_len: float,
               window: float | None) -> float:
    """Operations of one layer's prefill attention: 4 a query head a
    (query, key) pair a value of the head, over the pairs of the causal
    triangle, or of the band a window leaves of it."""
    p = prompt_len
    pairs = p * (p + 1) / 2
    if window is not None and p > window:
        pairs -= (p - window) * (p - window + 1) / 2
    return rows * 4 * pairs * args["heads"] * args["head_dim"]


def prefill_needs(args: dict, *, rows: float, prompt_len: float,
                  held_share: float, weight_bytes: int, kv_bytes: int
                  ) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens: every matrix on every token, the routed
    experts on the pairs that fell to held experts only, banded and
    causal attention at their own operations, the head on the last
    position alone.  Bytes: every weight once, the cached rows written
    once."""
    attn, router, shared, expert = layer_params(args)
    n_window, n_full = layer_kinds(args)
    tokens = rows * prompt_len
    head = args["hidden"] * args["vocab"]
    flops = (args["num_layers"] * tokens * 2
             * (attn + router + shared
                + held_share * args["experts_per_tok"] * expert)
             + n_window * band_flops(args, rows=rows, prompt_len=prompt_len,
                                     window=args["window"])
             + n_full * band_flops(args, rows=rows, prompt_len=prompt_len,
                                   window=None)
             + rows * 2 * head)
    win, full = live_rows(args, prompt_len)
    nbytes = ((args["num_layers"] * (attn + router + shared
                                     + held_experts(args) * expert) + head)
              * weight_bytes
              + rows * (n_window * win + n_full * full) * 2
              * args["kv_heads"] * args["head_dim"] * kv_bytes
              + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)
