"""Operations and bytes a step of a state-space / attention hybrid
needs, from shapes alone (``least_time_s`` and the peaks are
``chipbench/roofline.py``'s).

*Needed* as there: every weight a step multiplies by once, outputs
once, the attention layers' live key and value rows once — and every
state-space layer's state ``H`` **read once and written once**, and its
convolution's window likewise: like a retention state and unlike a KV
cache they are rewritten whole each step.  Their size is the
configuration's (``E x N`` float32 values and ``(d_conv - 1) x E``
values of the compute type a sequence a layer), whatever layout the
program keeps them in.  What the program holds — its gauges
``decode.ssm.state_bytes`` / ``decode.ssm.conv_bytes`` /
``decode.cache.full_bytes`` — is only checked against the need
(:func:`check_held`): a layout that pads 16 states to 128 lanes holds
8x the need and would read a *lower* share for moving more, but it is
no layout the configuration allows, and the reader raises.
"""

from __future__ import annotations

#: the most the program may hold over the need (the KV layers' scratch
#: group and row, the ring's own, apart)
HELD_OVER_NEEDED = 1.10


def channels_of(a: dict) -> int:
    """``E``: the mixer's inner width by the configuration's
    ``model_args``."""
    return a["mamba_expand"] * a["hidden"]


def layer_kinds(a: dict) -> tuple[int, int]:
    """``(Mamba layers, attention layers)`` of the configuration."""
    attention = sum(l % a["attn_layer_period"] == a["attn_layer_offset"]
                    for l in range(a["num_layers"]))
    return a["num_layers"] - attention, attention


def mamba_mixer_params(a: dict) -> int:
    """Parameters of one Mamba mixer: ``in_proj`` (d x 2E), ``conv1d``
    (E x d_conv and its bias), ``x_proj`` (E x (R + 2N)), ``dt_proj``
    (R x E and its bias), ``A_log`` (E x N), ``D``, ``out_proj`` (E x
    d), and the three small norms (R + 2N)."""
    d, e = a["hidden"], channels_of(a)
    n, r, k = a["mamba_d_state"], a["mamba_dt_rank"], a["mamba_d_conv"]
    return (d * 2 * e + e * k + e + e * (r + 2 * n) + r * e + e + e * n + e
            + e * d + r + 2 * n)


def attention_mixer_params(a: dict) -> int:
    """q and o (d x heads*hd), k and v (d x kv*hd)."""
    qd, kvd = a["heads"] * a["head_dim"], a["kv_heads"] * a["head_dim"]
    return 2 * a["hidden"] * qd + 2 * a["hidden"] * kvd


def mlp_params(a: dict) -> int:
    """The dense SwiGLU's gate, up and down."""
    return 3 * a["hidden"] * a["mlp_hidden"]


def model_params(a: dict) -> int:
    """Every parameter of the model, the tied embedding once: the
    layers' mixers, MLPs and two norms each, the last norm."""
    mamba, attention = layer_kinds(a)
    d = a["hidden"]
    return (mamba * mamba_mixer_params(a)
            + attention * attention_mixer_params(a)
            + a["num_layers"] * (mlp_params(a) + 2 * d)
            + d + a["vocab"] * d)


def step_matrix_params(a: dict) -> int:
    """Parameters a decode step multiplies by: the layers' (the norms'
    scales and the mixers' vectors with them: all are read) and the
    head's matrix; the embedding is gathered, a row a token."""
    return model_params(a) - a["hidden"]


def needed_state_bytes(a: dict, rows: float, window_bytes: int
                       ) -> tuple[float, float]:
    """``(h, conv)``: bytes of ``rows`` sequences' states (float32) and
    windows (``window_bytes`` a value) over all Mamba layers."""
    mamba, _ = layer_kinds(a)
    e = channels_of(a)
    return (4.0 * mamba * rows * e * a["mamba_d_state"],
            float(window_bytes) * mamba * rows * e * (a["mamba_d_conv"] - 1))


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
    of ``H``, of the windows, or of the attention layers' rows (over
    ``max_len`` positions, the ring's scratch group and row apart: it
    holds ``groups + 1`` groups of ``max_len + 1`` rows, rounded up to
    whole tiles of 16)."""
    rows = counters["rows"]
    h, conv = needed_state_bytes(a, rows, counters["weight_bytes"])
    held_conv = counters.get("ssm_conv_bytes") or 0.0
    held_h = (counters.get("ssm_state_bytes") or 0.0) - held_conv
    # what the ring adds by design: one scratch group beside the one
    # group of a one-stage ring, one scratch row, whole tiles
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


def ssm_step_needs(a: dict, rows: float) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's call of the ``ssm_step``
    kernel: a layer's ``H`` read once and written once, the step and
    the input (float32, E a sequence each) and ``B``, ``C`` (N each)
    in, ``y`` (E) out, ``A`` once; an update and a read of ``E x N``
    values a sequence, 3 operations each."""
    e, n = channels_of(a), a["mamba_d_state"]
    nbytes = 4.0 * (2 * rows * e * n + 3 * rows * e + 2 * rows * n + e * n)
    return float(6 * rows * e * n), nbytes


def ssm_scan_needs(a: dict, rows: float, prompt_len: float
                   ) -> tuple[float, float]:
    """``(flops, bytes)`` of one call of the ``ssm_scan`` kernel over
    ``rows`` prompts of ``prompt_len`` positions: the step, the input,
    ``B`` and ``C`` in, ``y`` out (float32, once each) and ``H`` after
    the last position out."""
    e, n = channels_of(a), a["mamba_d_state"]
    tokens = rows * prompt_len
    nbytes = 4.0 * (3 * tokens * e + 2 * tokens * n + rows * e * n + e * n)
    return float(6 * tokens * e * n), nbytes


def decode_step_needs(a: dict, *, rows: float, live_positions: float,
                      weight_bytes: int, kv_bytes: int
                      ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences:
    every matrix once, ``H`` and the windows read once and written
    once, the attention layers' live rows once, the logits written once
    in f32 (the embedding is gathered and is not counted)."""
    mamba, attention = layer_kinds(a)
    w = step_matrix_params(a)
    h, conv = needed_state_bytes(a, rows, weight_bytes)
    live = needed_cache_bytes(a, rows, live_positions, kv_bytes)
    kernel_flops, _ = ssm_step_needs(a, rows)
    flops = (rows * 2 * w + mamba * kernel_flops
             + attention * rows * 4 * live_positions
             * a["heads"] * a["head_dim"])
    nbytes = (w * weight_bytes + 2 * (h + conv) + live
              + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)


def prefill_needs(a: dict, *, rows: float, prompt_len: float,
                  weight_bytes: int, kv_bytes: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts: every
    layer matrix on every token, causal attention in the attention
    layers (half the square), the recurrence, the head on the last
    position; every weight once, the scans' inputs read and outputs
    written once (the states with them), the windows and the rows
    written once."""
    mamba, attention = layer_kinds(a)
    tokens = rows * prompt_len
    layers = step_matrix_params(a) - a["vocab"] * a["hidden"]
    scan_flops, scan_bytes = ssm_scan_needs(a, rows, prompt_len)
    _, conv = needed_state_bytes(a, rows, weight_bytes)
    flops = (tokens * 2 * layers + mamba * scan_flops
             + attention * tokens * 2 * prompt_len
             * a["heads"] * a["head_dim"]
             + rows * 2 * a["hidden"] * a["vocab"])
    nbytes = (step_matrix_params(a) * weight_bytes + mamba * scan_bytes
              + conv + needed_cache_bytes(a, rows, prompt_len, kv_bytes)
              + rows * a["vocab"] * 4)
    return float(flops), float(nbytes)
