"""Operations and bytes that a step and a prefill of the double-layer,
shortcut-connected family (``models.longcat_flash``) need, from shapes
alone (``least_time_s`` is in ``chipbench/roofline.py``).

*Needed* as ``roofline_latent_moe.py`` counts it for Kimi — every weight
a step multiplies by once, every live row once, outputs once; what is
needed is the *configuration's*, whatever the program's layout or form —
with what a double layer changes:

* a layer has **two** latent-attention sublayers (their matrices, and a
  position's rows twice: 2 x 576 values, 2 x 1152 B in bfloat16, read by
  two attention calls a layer a step) and **two** dense SwiGLUs;
* the router has ``num_experts + zero_experts`` columns, no shared
  expert stands beside it, and **how many of a token's choices cost
  anything is data**: a pair that fell to a zero-compute expert costs
  no operation and no byte (one multiply of the stream by a weight,
  counted with the activations, that is, not at all), a pair that fell
  to a routed expert another chip holds is not this chip's, and the
  pairs that fell to *held* experts are ``held_share`` of all
  assignments (the program's ``decode.moe.held_assignments`` over
  ``.assignments``, zero pairs in the denominator) — never more than
  ``real_share`` (``decode.moe.real_assignments`` over ``.assignments``),
  which the functions here check.  So nothing multiplies by
  ``experts_per_tok`` alone.

Of the routed experts only the *held* exist on the chip, and of those
only the ones a step *touches* are needed — ``experts_hit_share``, from
the program's ``decode.moe.experts_hit``.  The attention's calls, the
rows and the prompt's kernel are Kimi's own, counted by
``roofline_latent_moe``'s functions on the same key names.
"""

from __future__ import annotations

from chipbench.roofline_latent_moe import (attend_call_needs,
                                           check_row_bytes, flash_flops,
                                           held_experts, row_values)

#: latent-attention sublayers (and dense SwiGLUs) of a double layer
SUBLAYERS = 2

__all__ = ["SUBLAYERS", "attend_call_needs", "check_row_bytes",
           "decode_step_needs", "fixed_params", "flash_flops",
           "held_experts", "held_params", "layer_params", "prefill_needs",
           "row_values", "step_bytes_by_part"]


def layer_params(args: dict) -> dict:
    """Parameters by part: ``attention`` (one sublayer's five matrices
    and the two small norms between them), ``norms`` (a double layer's
    four), ``dense`` (one SwiGLU's), ``router`` (every column's and
    bias's, routed and zero), ``expert`` (one routed expert's),
    ``head``."""
    d, nh, r, c = (args["hidden"], args["heads"], args["q_rank"],
                   args["latent_dim"])
    qk = args["nope_dim"] + args["rope_dim"]
    kv = args["nope_dim"] + args["v_dim"]
    columns = args["num_experts"] + args["zero_experts"]
    return {
        "attention": (d * r + r + r * nh * qk + d * (c + args["rope_dim"])
                      + c + c * nh * kv + nh * args["v_dim"] * d),
        "norms": 2 * SUBLAYERS * d,
        "dense": 3 * d * args["dense_hidden"],
        "router": d * columns + columns,
        "expert": 3 * d * args["expert_hidden"],
        "head": d * args["vocab"],
    }


def fixed_params(args: dict) -> int:
    """What every step multiplies by whatever it routes: the double
    layers outside their routed experts and the head (the embedding is
    gathered, a row a token)."""
    p = layer_params(args)
    return (args["num_layers"] * (SUBLAYERS * (p["attention"] + p["dense"])
                                  + p["norms"] + p["router"]) + p["head"])


def held_params(args: dict) -> int:
    """Everything the chip holds: :func:`fixed_params`, the held routed
    experts, the embedding and the last norm."""
    return (fixed_params(args) + args["hidden"] * (args["vocab"] + 1)
            + args["num_layers"] * held_experts(args)
            * layer_params(args)["expert"])


def _held_pairs(args: dict, held_share: float, real_share: float) -> float:
    """Pairs a token a layer that fell to held experts; a held pair is
    a real pair."""
    if held_share > real_share + 1e-9:
        raise ValueError(
            f"held_share {held_share:.4f} over real_share {real_share:.4f}: "
            "a pair that fell to a held expert fell to a routed one")
    return held_share * args["experts_per_tok"]


def step_bytes_by_part(args: dict, *, rows: float, positions: float,
                       experts_hit_share: float, weight_bytes: int,
                       kv_bytes: int) -> dict:
    """The bytes one decode step needs, by part: ``attention`` (both
    sublayers' matrices), ``dense`` (both SwiGLUs), ``router``,
    ``experts`` (the touched held experts), ``rows`` (both sublayers'
    live rows, queries and outputs), ``head`` (its matrix and the
    logits)."""
    p, layers = layer_params(args), args["num_layers"]
    _, call_bytes = attend_call_needs(args, rows=rows, positions=positions,
                                      kv_bytes=kv_bytes)
    return {
        "attention": layers * (SUBLAYERS * p["attention"] + p["norms"])
        * weight_bytes,
        "dense": layers * SUBLAYERS * p["dense"] * weight_bytes,
        "router": layers * p["router"] * weight_bytes,
        "experts": layers * experts_hit_share * held_experts(args)
        * p["expert"] * weight_bytes,
        "rows": layers * SUBLAYERS * call_bytes,
        "head": p["head"] * weight_bytes + rows * args["vocab"] * 4,
    }


def decode_step_needs(args: dict, *, rows: float, positions: float,
                      experts_hit_share: float, held_share: float,
                      real_share: float, weight_bytes: int, kv_bytes: int
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences at
    ``positions`` cached positions: every matrix outside the routed
    experts once, the *touched* held experts' once, both sublayers'
    live rows once a layer, the logits written once in f32; the held
    pairs' products, the zero pairs at no cost."""
    p, layers = layer_params(args), args["num_layers"]
    call_flops, _ = attend_call_needs(args, rows=rows, positions=positions,
                                      kv_bytes=kv_bytes)
    flops = (rows * 2 * (fixed_params(args) + layers
                         * _held_pairs(args, held_share, real_share)
                         * p["expert"])
             + layers * SUBLAYERS * call_flops)
    nbytes = sum(step_bytes_by_part(
        args, rows=rows, positions=positions,
        experts_hit_share=experts_hit_share, weight_bytes=weight_bytes,
        kv_bytes=kv_bytes).values())
    return float(flops), float(nbytes)


def prefill_needs(args: dict, *, rows: float, prompt_len: float,
                  held_share: float, real_share: float, weight_bytes: int,
                  kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens: every matrix outside the routed experts on
    every token, the routed experts on the pairs that fell to held
    experts only, causal attention over the expanded heads in both
    sublayers, the head on the last position alone.  Bytes: every
    weight once, both sublayers' rows written once."""
    p, layers = layer_params(args), args["num_layers"]
    tokens = rows * prompt_len
    flops = (tokens * 2 * (fixed_params(args) - p["head"] + layers
                           * _held_pairs(args, held_share, real_share)
                           * p["expert"])
             + layers * SUBLAYERS * flash_flops(args, rows=rows,
                                                prompt_len=prompt_len)
             + rows * 2 * p["head"])
    nbytes = ((held_params(args) - args["hidden"] * args["vocab"])
              * weight_bytes
              + layers * SUBLAYERS * tokens * row_values(args) * kv_bytes
              + rows * args["vocab"] * 4)
    return float(flops), float(nbytes)
