"""Operations and bytes a step of a Mamba-2 (with B/C groups) /
attention / LatentMoE hybrid needs, from the configuration's shapes
alone, whatever implements them (``least_time_s`` and the peaks are
``chipbench/roofline.py``'s).  A layer here is a mixer **or** a
feed-forward part: ``layer_pattern`` names each ``M``, ``*`` or ``E``.

*Needed* as there: every weight a step multiplies by once — of the
routed experts only those *held* and *touched* (the program's own
``experts_hit_share``) — outputs once, the attention layer's live key
and value rows once, and every Mamba-2 layer's state ``H`` **read once
and written once**, and its convolution's window likewise: they are
rewritten whole each step.  Their size is the configuration's (``heads
x head_dim x N`` float32 values and ``(d_conv - 1) x (E + 2 G N)``
values of the compute type a sequence a layer), whatever layout the
program keeps them in.  An ``E`` layer keeps nothing a sequence and
needs no byte of memory.  What the program holds — its gauges
``decode.ssm.state_bytes`` / ``decode.ssm.conv_bytes`` /
``decode.cache.full_bytes`` — is only checked against the need
(:func:`check_held`), and the reader raises where it holds more.
"""

from __future__ import annotations

#: the most the program may hold over the need (the KV layer's scratch
#: group and row, the ring's own, apart)
HELD_OVER_NEEDED = 1.10


def channels_of(a: dict) -> int:
    """``E``: the mixer's heads side by side."""
    return a["mamba_heads"] * a["mamba_head_dim"]


def bc_width(a: dict) -> int:
    """Columns of ``B`` (or of ``C``): a group's ``N`` after the other."""
    return a["mamba_groups"] * a["mamba_d_state"]


def conv_width(a: dict) -> int:
    """Columns the convolution runs over: the channels, every group's
    ``B`` and every group's ``C``."""
    return channels_of(a) + 2 * bc_width(a)


def layer_kinds(a: dict) -> tuple[int, int, int]:
    """``(Mamba-2 layers, attention layers, LatentMoE layers)``."""
    pattern = a["layer_pattern"]
    return pattern.count("M"), pattern.count("*"), pattern.count("E")


def held_experts(a: dict) -> int:
    lo, hi = a.get("experts_held") or (0, a["num_experts"])
    return hi - lo


def mamba_params(a: dict) -> int:
    """One ``M`` layer: ``in_proj`` (d x (2E + 2GN + heads)), ``conv1d``
    ((E + 2GN) x d_conv and its bias), ``dt_bias``, ``A_log`` and ``D``
    (heads each), the gated norm (E), ``out_proj`` (E x d), the norm."""
    d, e, w = a["hidden"], channels_of(a), conv_width(a)
    return (d * (e + w + a["mamba_heads"]) + w * a["mamba_d_conv"] + w
            + 3 * a["mamba_heads"] + e + e * d + d)


def attention_params(a: dict) -> int:
    """One ``*`` layer: q and o (d x heads*hd), k and v (d x kv*hd), the
    norm."""
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 2 * a["hidden"] * qd + 2 * a["hidden"] * kvd + a["hidden"]


def expert_params(a: dict) -> int:
    """One routed expert's two matrices in the latent space."""
    return 2 * a["latent"] * a["expert_hidden"]


def routed_rest_params(a: dict) -> int:
    """An ``E`` layer outside its routed experts: the router and its
    bias, the two latent projections, the shared expert, the norm."""
    d = a["hidden"]
    return (d * a["num_experts"] + a["num_experts"] + 2 * d * a["latent"]
            + 2 * d * a["shared_hidden"] + d)


def dense_params(a: dict) -> int:
    """The layers outside their routed experts."""
    mamba, attention, routed = layer_kinds(a)
    return (mamba * mamba_params(a) + attention * attention_params(a)
            + routed * routed_rest_params(a))


def held_params(a: dict) -> int:
    """Every parameter this chip's share holds: the layers, the held
    experts, the embedding's and the head's rows, the final norm."""
    _, _, routed = layer_kinds(a)
    return (dense_params(a) + routed * held_experts(a) * expert_params(a)
            + 2 * a["vocab"] * a["hidden"] + a["hidden"])


def needed_state_bytes(a: dict, rows: float, window_bytes: int
                       ) -> tuple[float, float]:
    """``(h, conv)``: bytes of ``rows`` sequences' states (float32) and
    windows (``window_bytes`` a value) over all Mamba-2 layers."""
    mamba, _, _ = layer_kinds(a)
    return (4.0 * mamba * rows * channels_of(a) * a["mamba_d_state"],
            float(window_bytes) * mamba * rows * conv_width(a)
            * (a["mamba_d_conv"] - 1))


def needed_cache_bytes(a: dict, rows: float, positions: float,
                       kv_bytes: int) -> float:
    """Key and value rows of ``rows`` sequences over ``positions``
    positions in the attention layers."""
    _, attention, _ = layer_kinds(a)
    return (float(kv_bytes) * attention * rows * positions * 2
            * a["kv_heads"] * a["head_dim"])


def check_held(counters: dict, a: dict) -> None:
    """Raise where the program holds (its gauges, as the driver's
    ``counters`` carry them; None or 0 where a program has none) more
    than :data:`HELD_OVER_NEEDED` times what the configuration needs:
    of ``H``, of the windows, or of the attention layer's rows (over
    ``max_len`` positions, the ring's scratch group and row apart: it
    holds ``groups + 1`` groups of ``max_len + 1`` rows, rounded up to
    whole tiles of 16)."""
    rows = counters["rows"]
    h, conv = needed_state_bytes(a, rows, counters["weight_bytes"])
    held_conv = counters.get("ssm_conv_bytes") or 0.0
    held_h = (counters.get("ssm_state_bytes") or 0.0) - held_conv
    positions = -(-(counters["max_len"] + 1) // 16) * 16
    full = 2 * needed_cache_bytes(a, rows, positions, counters["kv_bytes"])
    for name, held, need in (("state-space state", held_h, h),
                             ("convolution windows", held_conv, conv),
                             ("attention rows", counters.get(
                                 "cache_full_bytes") or 0.0, full)):
        if held > HELD_OVER_NEEDED * need:
            raise ValueError(
                f"the program holds {held:.0f} B of {name}, "
                f"{held / need:.3f} times the {need:.0f} B the "
                f"configuration needs (allowed: {HELD_OVER_NEEDED:.2f})")


def ssd_step_needs(a: dict, rows: float) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's step of the recurrence with
    groups: a layer's ``H`` read once and written once, the decay and
    the input (float32, E a sequence each) and every group's ``B`` and
    ``C`` (G N each) in, ``y`` (E) out; an update and a read of ``E x
    N`` values a sequence, 3 operations each."""
    e, n = channels_of(a), a["mamba_d_state"]
    nbytes = 4.0 * (2 * rows * e * n + 3 * rows * e + 2 * rows * bc_width(a))
    return float(6 * rows * e * n), nbytes


def ssd_scan_needs(a: dict, rows: float, prompt_len: float
                   ) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's chunked recurrence over
    ``rows`` prompts of ``prompt_len`` positions in chunks of
    ``mamba_chunk`` (``L``).  A sequence's chunk: ``C B^T`` once a group
    (2 L^2 N G), every head's ``(decay o C B^T) (dt x)`` (2 L^2 E), what
    the carried state gives (``C H``, 2 L N E) and the state's move
    (``B^T (decay o dt x)``, 2 L N E).  Bytes: the input and the output
    (float32, E a position each) once, ``B`` (twice: as rows and as
    columns) and ``C`` once, the running sums (heads a position, as
    rows and as columns) and ``H`` after the last position out."""
    e, n, length = channels_of(a), a["mamba_d_state"], a["mamba_chunk"]
    tokens = rows * prompt_len
    flops = tokens * (2 * length * n * a["mamba_groups"] + 2 * length * e
                      + 4 * n * e)
    nbytes = 4.0 * (2 * tokens * e + 3 * tokens * bc_width(a)
                    + 3 * tokens * a["mamba_heads"] + rows * e * n)
    return float(flops), nbytes


def latent_experts_needs(a: dict, rows: float, weight_bytes: int,
                         experts_hit: float, held_pairs: float
                         ) -> tuple[float, float]:
    """``(flops, bytes)`` of one ``E`` layer's routed experts in a
    decode step — the two grouped products, whatever runs them:
    ``experts_hit`` touched experts' two matrices once, the
    ``held_pairs`` (row, choice) pairs that fell to held experts in
    (latent wide), their hidden activations out and in (the squared relu
    between the products), the result out (latent wide).  ``rows`` is
    not read: the pairs are the program's own count."""
    del rows
    r, h = a["latent"], a["expert_hidden"]
    nbytes = float(weight_bytes) * (experts_hit * expert_params(a)
                                    + held_pairs * (2 * r + 2 * h))
    return float(2 * held_pairs * expert_params(a)), nbytes


def decode_step_needs(a: dict, *, rows: float, live_positions: float,
                      weight_bytes: int, kv_bytes: int,
                      experts_hit_share: float = 1.0
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences:
    every matrix outside the routed experts once, of the held experts
    those a step touches (``experts_hit_share`` of them, the program's
    own count), ``H`` and the windows read once and written once, the
    attention layer's live rows once, the logits written once in f32
    (the embedding is gathered and is not counted).  A token's products:
    every dense matrix, the head, and the routed experts its choices
    that fell to held experts reach (``held / all`` of
    ``experts_per_tok`` on average)."""
    mamba, attention, routed = layer_kinds(a)
    dense = dense_params(a) + a["hidden"] + a["vocab"] * a["hidden"]
    held = routed * held_experts(a) * expert_params(a)
    h, conv = needed_state_bytes(a, rows, weight_bytes)
    live = needed_cache_bytes(a, rows, live_positions, kv_bytes)
    kernel_flops, _ = ssd_step_needs(a, rows)
    per_token = routed * a["experts_per_tok"] * expert_params(a) \
        * held_experts(a) / a["num_experts"]
    flops = (rows * 2 * (dense + per_token) + mamba * kernel_flops
             + attention * rows * 4 * live_positions
             * a["heads"] * a["head_dim"])
    nbytes = ((dense + experts_hit_share * held) * weight_bytes
              + 2 * (h + conv) + live + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)


def prefill_needs(a: dict, *, rows: float, prompt_len: float,
                  weight_bytes: int, kv_bytes: int,
                  held_share: float | None = None) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts: every
    matrix outside the routed experts on every token, the routed
    experts on the pairs that fell to held experts only (``held_share``
    of a token's ``experts_per_tok`` choices: the program's own count,
    else ``held / all``), causal attention in the attention layer (half
    the square), the chunked recurrence's products, the head on the last
    position; every held weight once, the scans' inputs read and
    outputs written once (the states with them), the windows and the
    rows written once."""
    mamba, attention, routed = layer_kinds(a)
    tokens = rows * prompt_len
    if held_share is None:
        held_share = held_experts(a) / a["num_experts"]
    scan_flops, scan_bytes = ssd_scan_needs(a, rows, prompt_len)
    _, conv = needed_state_bytes(a, rows, weight_bytes)
    per_token = routed * a["experts_per_tok"] * expert_params(a) * held_share
    flops = (tokens * 2 * (dense_params(a) + per_token) + mamba * scan_flops
             + attention * tokens * 2 * prompt_len
             * a["heads"] * a["head_dim"]
             + rows * 2 * a["hidden"] * a["vocab"])
    nbytes = (held_params(a) * weight_bytes + mamba * scan_bytes
              + conv + needed_cache_bytes(a, rows, prompt_len, kv_bytes)
              + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)
