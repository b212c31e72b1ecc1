"""Operations and bytes a step of a gated-short-convolution / attention
hybrid with routed experts needs, from the configuration's shapes alone
(``least_time_s`` and the peaks are ``chipbench/roofline.py``'s).

*Needed* as there: every weight a step multiplies by once — of the
routed experts only those *touched* (the program's own count,
``decode.moe.experts_hit`` a layer a step) — outputs once, the attention
layers' live key and value rows once, and every convolution layer's
window **read once and written once**: it is moved on by a row each
step.  The window's size is the configuration's (``(d_conv - 1) x
hidden`` values of the compute type a sequence a layer), whatever layout
the program keeps it in.  What the program holds — its gauges
``decode.conv.window_bytes`` / ``decode.cache.full_bytes`` — is only
checked against the need (:func:`check_held`), and the reader raises
where it holds more.
"""

from __future__ import annotations

#: the most the program may hold over the need (the KV layers' scratch
#: group and row, the ring's own, apart)
HELD_OVER_NEEDED = 1.10


def layer_kinds(a: dict) -> tuple[int, int]:
    """``(convolution layers, attention layers)`` of the configuration."""
    types = a["layer_types"]
    attention = sum(types[l % len(types)] == "full_attention"
                    for l in range(a["num_layers"]))
    return a["num_layers"] - attention, attention


def routed_layers(a: dict) -> int:
    return a["num_layers"] - a["dense_layers"]


def conv_mixer_params(a: dict) -> int:
    """``in_proj`` (d x 3d), the taps (d_conv x d), ``out_proj`` (d x d)."""
    d = a["hidden"]
    return 3 * d * d + a["d_conv"] * d + d * d


def attention_mixer_params(a: dict) -> int:
    """q and o (d x heads*hd), k and v (d x kv*hd), the two norms a head."""
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 2 * a["hidden"] * qd + 2 * a["hidden"] * kvd + 2 * a["head_dim"]


def expert_params(a: dict) -> int:
    """One routed expert's gate, up and down."""
    return 3 * a["hidden"] * a["expert_hidden"]


def dense_params(a: dict) -> int:
    """Every parameter of the layers outside their routed experts: the
    mixers, the dense layers' SwiGLU, the routers (a column and a bias
    an expert), two norms a layer."""
    conv, attention = layer_kinds(a)
    d = a["hidden"]
    return (conv * conv_mixer_params(a)
            + attention * attention_mixer_params(a)
            + a["dense_layers"] * 3 * d * a["dense_hidden"]
            + routed_layers(a) * (d + 1) * a["num_experts"]
            + a["num_layers"] * 2 * d)


def held_params(a: dict) -> int:
    """Every parameter the chip holds, the tied table once, the final
    norm with it."""
    return (dense_params(a)
            + routed_layers(a) * a["num_experts"] * expert_params(a)
            + a["vocab"] * a["hidden"] + a["hidden"])


def needed_window_bytes(a: dict, rows: float, window_bytes: int) -> float:
    """Bytes of ``rows`` sequences' windows (``window_bytes`` a value)
    over all convolution layers."""
    conv, _ = layer_kinds(a)
    return (float(window_bytes) * conv * rows * a["hidden"]
            * (a["d_conv"] - 1))


def needed_cache_bytes(a: dict, rows: float, positions: float,
                       kv_bytes: int) -> float:
    """Key and value rows of ``rows`` sequences over ``positions``
    positions in the attention layers."""
    _, attention = layer_kinds(a)
    return (float(kv_bytes) * attention * rows * positions * 2
            * a["kv_heads"] * a["head_dim"])


def check_held(counters: dict, a: dict) -> None:
    """Raise where the program holds (its gauges, as the driver's
    ``counters`` carry them; None or 0 where a program has none) more
    than :data:`HELD_OVER_NEEDED` times what the configuration needs:
    of the windows (a group a stage: the ring's ``groups`` axis is all
    that may multiply the need), or of the attention layers' rows (over
    ``max_len`` positions, the ring's scratch group and row apart: it
    holds ``groups + 1`` groups of ``max_len + 1`` rows, rounded up to
    whole tiles of 16)."""
    rows = counters["rows"]
    windows = needed_window_bytes(a, rows, counters["weight_bytes"])
    positions = -(-(counters["max_len"] + 1) // 16) * 16
    full = 2 * needed_cache_bytes(a, rows, positions, counters["kv_bytes"])
    for name, held, need in (
            ("convolution windows",
             counters.get("conv_window_bytes") or 0.0, windows),
            ("attention rows", counters.get("cache_full_bytes") or 0.0,
             full)):
        if held > HELD_OVER_NEEDED * need:
            raise ValueError(
                f"the program holds {held:.0f} B of {name}, "
                f"{held / need:.3f} times the {need:.0f} B the "
                f"configuration needs (allowed: {HELD_OVER_NEEDED:.2f})")


def routed_step_needs(a: dict, rows: float, weight_bytes: int,
                      experts_hit: float | None = None
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one routed layer's two calls of the
    ``grouped_experts`` kernel in a decode step of ``rows`` sequences
    (gate-and-up, then down): the touched experts' three matrices once
    (``experts_hit`` of them, the program's own count; all, where it
    gives none), the ``rows x experts_per_tok`` sorted rows in, the
    hidden activations out of the first call and into the second, the
    result out; three products a pair."""
    pairs = rows * a["experts_per_tok"]
    hit = a["num_experts"] if experts_hit is None else experts_hit
    d, h = a["hidden"], a["expert_hidden"]
    nbytes = weight_bytes * (hit * expert_params(a)
                             + pairs * (2 * d + 2 * h))
    return float(2 * pairs * expert_params(a)), float(nbytes)


def decode_step_needs(a: dict, *, rows: float, live_positions: float,
                      weight_bytes: int, kv_bytes: int,
                      experts_hit_share: float = 1.0
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences:
    every matrix outside the routed experts once, of the experts those
    a step touches (``experts_hit_share`` of them, the program's own
    count), the windows read once and written once, the attention
    layers' live rows once, the logits written once in f32 (the
    embedding is gathered and is not counted).  A token's products:
    every dense matrix, the head, ``experts_per_tok`` experts a routed
    layer, and its attention over the live rows."""
    _, attention = layer_kinds(a)
    dense = dense_params(a) + a["hidden"] + a["vocab"] * a["hidden"]
    routed = routed_layers(a) * a["num_experts"] * expert_params(a)
    per_token = routed_layers(a) * a["experts_per_tok"] * expert_params(a)
    windows = needed_window_bytes(a, rows, weight_bytes)
    live = needed_cache_bytes(a, rows, live_positions, kv_bytes)
    flops = (rows * 2 * (dense + per_token)
             + attention * rows * 4 * live_positions
             * a["heads"] * a["head_dim"])
    nbytes = ((dense + experts_hit_share * routed) * weight_bytes
              + 2 * windows + live + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)


def prefill_needs(a: dict, *, rows: float, prompt_len: float,
                  weight_bytes: int, kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts: every
    matrix outside the routed experts on every token,
    ``experts_per_tok`` experts a token a routed layer, causal attention
    in the attention layers (half the square), the head on the last
    position; every held weight once, the windows and the rows written
    once, the last position's logits."""
    _, attention = layer_kinds(a)
    tokens = rows * prompt_len
    per_token = routed_layers(a) * a["experts_per_tok"] * expert_params(a)
    flops = (tokens * 2 * (dense_params(a) + per_token)
             + attention * tokens * 2 * prompt_len
             * a["heads"] * a["head_dim"]
             + rows * 2 * a["hidden"] * a["vocab"])
    nbytes = (held_params(a) * weight_bytes
              + needed_window_bytes(a, rows, weight_bytes)
              + needed_cache_bytes(a, rows, prompt_len, kv_bytes)
              + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)
