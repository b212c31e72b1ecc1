"""Operations and bytes a step of a delta-rule / attention hybrid with
routed experts needs, from the configuration's shapes alone
(``least_time_s`` and the peaks are ``chipbench/roofline.py``'s).

*Needed* as there: every weight a step multiplies by once — of the held
routed experts only those *touched* (the program's own count,
``decode.moe.experts_hit`` a layer a step) — outputs once, the attention
layers' live key and value rows once, every KDA layer's windows read
once and written once, and every KDA layer's state — ``heads x head_dim
x head_dim`` float32 values a sequence — **read once and written
once**: the write reads it, so a step rewrites it whole.  The state's
size is the configuration's, whatever layout the program keeps it in.
What the program holds — its gauges ``decode.delta.state_bytes`` /
``decode.delta.window_bytes`` / ``decode.cache.full_bytes`` — is only
checked against the need (:func:`check_held`), and the reader raises
where it holds more.

Parameter counts leave the norms' weights out (two of ``hidden`` a
layer, one of ``head_dim`` a KDA layer, the final one): the hand count
the configuration's ``size`` gives, 3,308,316,096 for the cell's cut.
"""

from __future__ import annotations

#: the most the program may hold over the need of a state (Brumby's
#: rule: a fatter layout must not read as a higher share)
STATE_HELD_OVER_NEEDED = 1.06
#: the same of the windows and the attention layers' rows (the KV
#: layers' scratch group and row, the ring's own, apart)
HELD_OVER_NEEDED = 1.10
#: bytes of a state's value
STATE_BYTES = 4


def layer_kinds(a: dict) -> tuple[int, int]:
    """``(KDA layers, attention layers)`` of the configuration."""
    attention = len(set(a["gqa_layers"]))
    return a["num_layers"] - attention, attention


def kda_shape(a: dict) -> tuple[int, int]:
    """``(heads, a head's channels)`` of a KDA layer."""
    return (a.get("kda_heads") or a["heads"],
            a.get("kda_head_dim") or a["head_dim"])


def held_experts(a: dict) -> int:
    lo, hi = a.get("experts_held") or (0, a["num_experts"])
    return hi - lo


def kda_mixer_params(a: dict) -> int:
    """q, k, v and o (d x H D each), the taps over q, k and v, the two
    low-rank paths (decay and gate), ``dt_bias`` a channel, ``A_log`` a
    head, ``beta``."""
    d, r = a["hidden"], a.get("gate_rank", 128)
    heads, hd = kda_shape(a)
    e = heads * hd
    return (4 * d * e + a.get("d_conv", 4) * 3 * e + 2 * (d * r + r * e)
            + e + heads + d * heads)


def attention_mixer_params(a: dict) -> int:
    """q, the gate and o (d x heads*hd), k and v (d x kv*hd)."""
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 3 * a["hidden"] * qd + 2 * a["hidden"] * kvd


def expert_params(a: dict) -> int:
    """One expert's gate, up and down (a routed one's or the shared)."""
    return 3 * a["hidden"] * a["expert_hidden"]


def dense_params(a: dict) -> int:
    """Every matrix of the layers outside their routed experts: the
    mixers, the shared expert and the router (a column and a bias an
    expert) of every layer."""
    kda, attention = layer_kinds(a)
    return (kda * kda_mixer_params(a)
            + attention * attention_mixer_params(a)
            + a["num_layers"] * (expert_params(a)
                                 + (a["hidden"] + 1) * a["num_experts"]))


def held_params(a: dict) -> int:
    """Every parameter the chip holds but the norms' weights: the
    layers, their held routed experts, the embedding and the untied
    head."""
    return (dense_params(a)
            + a["num_layers"] * held_experts(a) * expert_params(a)
            + 2 * a["vocab"] * a["hidden"])


def whole_model_params(a: dict, layers: int, gqa_layers: int,
                       vocab: int) -> int:
    """The published model by the same shapes: ``layers`` layers of
    which ``gqa_layers`` attend, every routed expert, ``vocab`` ids."""
    per_layer = (a["num_experts"] + 1) * expert_params(a) \
        + (a["hidden"] + 1) * a["num_experts"]
    return (layers * per_layer
            + (layers - gqa_layers) * kda_mixer_params(a)
            + gqa_layers * attention_mixer_params(a)
            + 2 * vocab * a["hidden"])


def needed_state_bytes(a: dict, rows: float) -> float:
    """Bytes of ``rows`` sequences' states over all KDA layers."""
    kda, _ = layer_kinds(a)
    heads, hd = kda_shape(a)
    return float(STATE_BYTES) * kda * rows * heads * hd * hd


def needed_window_bytes(a: dict, rows: float, window_bytes: int) -> float:
    """Bytes of ``rows`` sequences' windows (``window_bytes`` a value)
    over all KDA layers: ``d_conv - 1`` rows of q, k and v."""
    kda, _ = layer_kinds(a)
    heads, hd = kda_shape(a)
    return (float(window_bytes) * kda * rows * 3 * heads * hd
            * (a.get("d_conv", 4) - 1))


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
    than the need allows: of the states :data:`STATE_HELD_OVER_NEEDED`
    times what the configuration needs, of the windows and of the
    attention layers' rows :data:`HELD_OVER_NEEDED` (the rows over
    ``max_len`` positions, the ring's scratch group and row apart: it
    holds ``groups + 1`` groups of ``max_len + 1`` rows, rounded up to
    whole tiles of 16)."""
    rows = counters["rows"]
    positions = -(-(counters["max_len"] + 1) // 16) * 16
    for name, key, need, over in (
            ("delta-rule states", "delta_state_bytes",
             needed_state_bytes(a, rows), STATE_HELD_OVER_NEEDED),
            ("convolution windows", "delta_window_bytes",
             needed_window_bytes(a, rows, counters["weight_bytes"]),
             HELD_OVER_NEEDED),
            ("attention rows", "cache_full_bytes",
             2 * needed_cache_bytes(a, rows, positions,
                                    counters["kv_bytes"]),
             HELD_OVER_NEEDED)):
        held = counters.get(key) or 0.0
        if held > over * need:
            raise ValueError(
                f"the program holds {held:.0f} B of {name}, "
                f"{held / need:.3f} times the {need:.0f} B the "
                f"configuration needs (allowed: {over:.2f})")


def delta_step_needs(a: dict, rows: float) -> tuple[float, float]:
    """``(flops, bytes)`` of one call of the ``delta_step`` kernel: one
    KDA layer's state of ``rows`` sequences read once and written once
    (what it is handed a sequence — a row a key channel — is a
    thousandth of that and not counted); seven operations a value (the
    decay, the two contractions, the write)."""
    heads, hd = kda_shape(a)
    values = rows * heads * hd * hd
    return 7.0 * values, 2.0 * STATE_BYTES * values


def delta_chunk_needs(a: dict, rows: float, prompt_len: float, chunk: int,
                      weight_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one KDA layer's chunked (WY) prefill of
    ``rows`` prompts of ``prompt_len`` tokens in chunks of ``chunk``: a
    token a head, the three products with the carried state (``2 D D``
    each), the two causal halves of the chunk's pairwise sums and the
    solve's and the read-out's (``chunk D`` each); ``q``, ``k`` and
    ``v`` in once in the compute type, the log-decay in and the output
    out once in float32, the state out once."""
    heads, hd = kda_shape(a)
    tokens = rows * prompt_len
    flops = tokens * heads * (6 * hd * hd + 4 * chunk * hd)
    nbytes = (tokens * heads * hd * (3 * weight_bytes + 2 * 4)
              + STATE_BYTES * rows * heads * hd * hd)
    return float(flops), float(nbytes)


def step_bytes_by_part(a: dict, *, rows: float, live_positions: float,
                       weight_bytes: int, kv_bytes: int,
                       experts_hit_share: float = 1.0) -> dict:
    """What one decode step of ``rows`` sequences reads and writes, by
    part: the KDA ``states`` (read and written), the touched held
    ``experts``, every other matrix a step multiplies by (``dense``:
    mixers, shared experts, routers, the head; the embedding is gathered
    and is not counted), the attention layers' live ``rows``, the
    ``windows`` (read and written), the ``logits`` (float32)."""
    dense = dense_params(a) + a["vocab"] * a["hidden"]
    routed = a["num_layers"] * held_experts(a) * expert_params(a)
    return {
        "states": 2 * needed_state_bytes(a, rows),
        "experts": experts_hit_share * routed * weight_bytes,
        "dense": float(dense * weight_bytes),
        "rows": needed_cache_bytes(a, rows, live_positions, kv_bytes),
        "windows": 2 * needed_window_bytes(a, rows, weight_bytes),
        "logits": rows * a["vocab"] * 4.0}


def decode_step_needs(a: dict, *, rows: float, live_positions: float,
                      weight_bytes: int, kv_bytes: int,
                      experts_hit_share: float = 1.0,
                      held_pairs_share: float | None = None
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences:
    :func:`step_bytes_by_part` added up; a token's products: every
    dense matrix, the head, the pairs that fall to the held experts
    (``held_pairs_share`` of a token's ``experts_per_tok``, the
    program's own count; the held over all experts where it gives
    none), its attention over the live rows and the state's seven
    operations a value."""
    kda, attention = layer_kinds(a)
    if held_pairs_share is None:
        held_pairs_share = held_experts(a) / a["num_experts"]
    dense = dense_params(a) + a["vocab"] * a["hidden"]
    per_token = (a["num_layers"] * held_pairs_share
                 * a["experts_per_tok"] * expert_params(a))
    flops = (rows * 2 * (dense + per_token)
             + attention * rows * 4 * live_positions
             * a["heads"] * a["head_dim"]
             + kda * delta_step_needs(a, rows)[0])
    nbytes = sum(step_bytes_by_part(
        a, rows=rows, live_positions=live_positions,
        weight_bytes=weight_bytes, kv_bytes=kv_bytes,
        experts_hit_share=experts_hit_share).values())
    return float(flops), float(nbytes)


def prefill_needs(a: dict, *, rows: float, prompt_len: float,
                  weight_bytes: int, kv_bytes: int, chunk: int = 64,
                  held_pairs_share: float | None = None
                  ) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts: every
    matrix outside the routed experts on every token, the held
    experts' pairs, causal attention in the attention layers (half the
    square), the KDA layers' chunked form, the head on the last
    position; every held weight once, the states, the windows and the
    rows written once, the last position's logits."""
    kda, attention = layer_kinds(a)
    if held_pairs_share is None:
        held_pairs_share = held_experts(a) / a["num_experts"]
    tokens = rows * prompt_len
    per_token = (a["num_layers"] * held_pairs_share
                 * a["experts_per_tok"] * expert_params(a))
    flops = (tokens * 2 * (dense_params(a) + per_token)
             + attention * tokens * 2 * prompt_len
             * a["heads"] * a["head_dim"]
             + kda * delta_chunk_needs(a, rows, prompt_len, chunk,
                                       weight_bytes)[0]
             + rows * 2 * a["hidden"] * a["vocab"])
    nbytes = (held_params(a) * weight_bytes
              + needed_state_bytes(a, rows)
              + needed_window_bytes(a, rows, weight_bytes)
              + needed_cache_bytes(a, rows, prompt_len, kv_bytes)
              + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)
