"""Operations and bytes that a step and a prefill of the latent-attention,
held-share family (``models.kimi_k2``) need, from shapes alone
(``least_time_s`` is in ``chipbench/roofline.py``).

*Needed* as there: every weight a step multiplies by once, every live
row once, outputs once — and what is needed is the *configuration's*,
whatever the program's layout or form.  A live row is ``latent + rope``
values (576: 1152 B in bfloat16), though the program pads it to whole
lane tiles; a row's products are two a head, the scores over ``latent +
rope`` and the output over ``latent`` (64 x 2 x (576 + 512) = 139,264
operations), in the latent space, the cheaper of the two forms at one
query a head (the expanded form would read 40,960 B a row).  A prompt's
attention is counted over the expanded heads, ``nope + rope`` to score
and ``v`` to sum a pair, the cheaper form there.  Of the routed experts
only the *held* exist on the chip, and of those only the ones a step
*touches* are needed — which ones is data, so it comes in as
``experts_hit_share``, from the program's own ``decode.moe.*`` counters;
in prefill every held expert is touched and the count is bound by
operations: the pairs that fell to held experts (``held_share`` of
``top_k`` a token).
"""

from __future__ import annotations

#: the most the program's bytes a cached row may pass the configuration's
ROW_OVER_NEED = 1.12


def layer_params(args: dict) -> dict:
    """Parameters by part: ``attention`` (the five matrices and the two
    small norms between them), ``norms`` (a layer's two), ``dense`` (the
    leading layers' SwiGLU), ``router`` (all experts' columns and
    biases), ``shared`` (the shared experts' three matrices), ``expert``
    (one routed expert's), ``head``."""
    d, nh, r, c = (args["hidden"], args["heads"], args["q_rank"],
                   args["latent_dim"])
    qk = args["nope_dim"] + args["rope_dim"]
    kv = args["nope_dim"] + args["v_dim"]
    expert = 3 * d * args["expert_hidden"]
    return {
        "attention": (d * r + r + r * nh * qk + d * (c + args["rope_dim"])
                      + c + c * nh * kv + nh * args["v_dim"] * d),
        "norms": 2 * d,
        "dense": 3 * d * args["dense_hidden"],
        "router": d * args["num_experts"] + args["num_experts"],
        "shared": args["num_shared"] * expert,
        "expert": expert,
        "head": d * args["vocab"],
    }


def layer_counts(args: dict) -> tuple[int, int]:
    """``(dense layers, routed layers)``."""
    dense = min(args.get("dense_layers", 1), args["num_layers"])
    return dense, args["num_layers"] - dense


def held_experts(args: dict) -> int:
    lo, hi = args.get("experts_held") or (0, args["num_experts"])
    return hi - lo


def fixed_params(args: dict) -> int:
    """What every step multiplies by whatever it routes: the layers
    outside their routed experts and the head (the embedding is
    gathered, a row a token)."""
    p = layer_params(args)
    dense, routed = layer_counts(args)
    return (dense * (p["attention"] + p["norms"] + p["dense"])
            + routed * (p["attention"] + p["norms"] + p["router"]
                        + p["shared"]) + p["head"])


def held_params(args: dict) -> int:
    """Everything the chip holds: :func:`fixed_params`, the held routed
    experts and the embedding."""
    _, routed = layer_counts(args)
    return (fixed_params(args) + args["hidden"] * args["vocab"]
            + routed * held_experts(args) * layer_params(args)["expert"])


def row_values(args: dict) -> int:
    """Values a position keeps a layer: the latent and the shared key."""
    return args["latent_dim"] + args["rope_dim"]


def row_flops(args: dict) -> int:
    """Operations a live row costs a layer a step: every head's score
    over the whole row and its output over the latent."""
    return args["heads"] * 2 * (row_values(args) + args["latent_dim"])


def check_row_bytes(held_bytes: float, held_rows: float, args: dict,
                    kv_bytes: int) -> None:
    """Raise where the program's buffers take more than
    :data:`ROW_OVER_NEED` of the configuration's bytes a row (``held``:
    its gauges ``decode.cache.latent_bytes`` over ``.latent_positions``):
    a fatter layout must not read as a higher share."""
    need = row_values(args) * kv_bytes
    if held_rows and held_bytes / held_rows > ROW_OVER_NEED * need:
        raise ValueError(
            f"the program keeps a row in {held_bytes / held_rows:.0f} B, "
            f"{held_bytes / held_rows / need:.4f} times the {need} B the "
            f"configuration needs (allowed: {ROW_OVER_NEED})")


def attend_call_needs(args: dict, *, rows: float, positions: float,
                      kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's decode attention: a call's live
    rows once, its absorbed queries read and its outputs written
    once."""
    live = rows * positions
    nbytes = (live * row_values(args)
              + rows * args["heads"] * (row_values(args)
                                        + args["latent_dim"])) * kv_bytes
    return float(live * row_flops(args)), float(nbytes)


def decode_step_needs(args: dict, *, rows: float, positions: float,
                      experts_hit_share: float, held_share: float,
                      weight_bytes: int, kv_bytes: int
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences at
    ``positions`` cached positions: every matrix outside the routed
    experts once, the *touched* held experts' once
    (``experts_hit_share`` of the held, a routed layer, on average),
    the live rows once a layer, the logits written once in f32."""
    p = layer_params(args)
    _, routed = layer_counts(args)
    call_flops, call_bytes = attend_call_needs(
        args, rows=rows, positions=positions, kv_bytes=kv_bytes)
    flops = (rows * 2 * (fixed_params(args) + routed * held_share
                         * args["experts_per_tok"] * p["expert"])
             + args["num_layers"] * call_flops)
    nbytes = ((fixed_params(args) + routed * experts_hit_share
               * held_experts(args) * p["expert"]) * weight_bytes
              + args["num_layers"] * call_bytes + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)


def flash_flops(args: dict, *, rows: float, prompt_len: float) -> float:
    """Operations of one layer's prefill attention over the expanded
    heads: 2 a head a (query, key) pair of the causal triangle a value
    of the key (``nope + rope``) and of the value (``v``)."""
    pairs = prompt_len * (prompt_len + 1) / 2
    return float(rows * pairs * args["heads"] * 2
                 * (args["nope_dim"] + args["rope_dim"] + args["v_dim"]))


def prefill_needs(args: dict, *, rows: float, prompt_len: float,
                  held_share: float, weight_bytes: int, kv_bytes: int
                  ) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens: every matrix outside the routed experts on
    every token, the routed experts on the pairs that fell to held
    experts only, causal attention over the expanded heads, the head on
    the last position alone.  Bytes: every weight once, the rows
    written once."""
    p = layer_params(args)
    _, routed = layer_counts(args)
    tokens = rows * prompt_len
    flops = (tokens * 2 * (fixed_params(args) - p["head"] + routed
                           * held_share * args["experts_per_tok"]
                           * p["expert"])
             + args["num_layers"] * flash_flops(args, rows=rows,
                                                prompt_len=prompt_len)
             + rows * 2 * p["head"])
    nbytes = ((held_params(args) - args["hidden"] * args["vocab"])
              * weight_bytes
              + args["num_layers"] * tokens * row_values(args) * kv_bytes
              + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)
