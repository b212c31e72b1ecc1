"""Operations and bytes a Brumby step needs, from shapes alone
(``least_time_s`` and the peaks are ``chipbench/roofline.py``'s).

*Needed* as there: every weight a step multiplies by once, outputs once
— and the retention state **read once and written once**: unlike a KV
cache, of which a step reads the live rows and writes one, a recurrent
state is rewritten whole.  Its size is the configuration's, not the
program's: a KV head's state is the symmetric square of a key of ``d``
values, ``D = d (d + 1) / 2`` rows (8256 at 128) of ``d + 1`` float32
values (``S [D, d]`` and ``z [D]``), whatever layout the program keeps
it in.  A layout that pads (the tiled square has 8704 rows) or holds
the full outer product (16384) moves more than is needed and reads a
*lower* share for it, not a higher one.  What the program holds — its
gauge ``decode.retention.state_bytes`` — is only checked against the
need (:func:`check_held`): more than the square by tiles of 8 takes
is a layout the configuration does not allow, and the reader raises.
"""

from __future__ import annotations


def head_dim_of(model_args: dict) -> int:
    """A head's values by the configuration's ``model_args``: the
    published ``head_dim``, or ``hidden / heads`` where none is given."""
    return model_args.get("head_dim") \
        or model_args["hidden"] // model_args["heads"]


def brumby_layer_params(n_embd: int, n_head: int, n_kv: int,
                        mlp_width: int, head_dim: int | None = None) -> int:
    """Matrix parameters of a layer: q and o (d x heads*hd), k and v (d
    x kv*hd), the decay's map (d x kv), and the MLP's gate, up and
    down."""
    hd = head_dim or n_embd // n_head
    return (2 * n_embd * n_head * hd + 2 * n_embd * n_kv * hd
            + n_embd * n_kv + 3 * n_embd * mlp_width)


def held_over_needed(head_dim: int) -> float:
    """The most the program may hold over the need: what the square by
    tiles of 8 takes, whose diagonal tiles hold both orders of a pair
    — ``(d + 8) / (d + 1)``, 8704 rows over 8256 = 1.054 at 128 — and
    half a percent; the full outer product would be 1.98."""
    return 1.005 * (head_dim + 8) / (head_dim + 1)


def state_rows(head_dim: int) -> int:
    """``D``: the rows of one KV head's state, the symmetric square of
    a key of ``head_dim`` values."""
    return head_dim * (head_dim + 1) // 2


def needed_state_bytes(*, n_layer: int, rows: float, n_kv: int,
                       head_dim: int) -> float:
    """Bytes of the state of ``rows`` sequences: ``n_layer x rows x n_kv
    x D x (head_dim + 1)`` float32 values."""
    return 4.0 * n_layer * rows * n_kv * state_rows(head_dim) \
        * (head_dim + 1)


def check_held(held: float | None, needed: float, head_dim: int) -> None:
    """Raise where the program holds (``held``: its gauge; None where a
    program has none) more than :func:`held_over_needed` times the
    state it needs."""
    allowed = held_over_needed(head_dim)
    if held and held > allowed * needed:
        raise ValueError(
            f"the program holds {held:.0f} B of retention state, "
            f"{held / needed:.3f} times the {needed:.0f} B the "
            f"configuration needs (allowed: {allowed:.3f})")


def retention_step_needs(*, rows: float, n_head: int, n_kv: int,
                         head_dim: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one layer's call of the ``retention_step``
    kernel: a layer's ``S`` (the state without its ``z``, which is
    updated beside the kernel) read once and written once; 2 D d
    operations a KV head for the update and 2 D d for each of its
    group's queries."""
    d_rows = state_rows(head_dim)
    s_bytes = 4.0 * rows * n_kv * d_rows * head_dim
    flops = rows * 2 * d_rows * head_dim * (n_kv + n_head)
    return float(flops), float(2 * s_bytes)


def brumby_decode_step_needs(*, n_layer: int, n_embd: int, n_head: int,
                             n_kv: int, mlp_width: int, vocab: int,
                             rows: float, weight_bytes: int,
                             head_dim: int | None = None
                             ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences: the
    layers' and the head's weights once, the whole state read once and
    written once, the logits written once in f32 (the embedding is
    gathered, a row a token, and is not counted)."""
    hd = head_dim or n_embd // n_head
    layer = brumby_layer_params(n_embd, n_head, n_kv, mlp_width, hd)
    kernel_flops, _ = retention_step_needs(rows=rows, n_head=n_head,
                                           n_kv=n_kv, head_dim=hd)
    state_bytes = needed_state_bytes(n_layer=n_layer, rows=rows, n_kv=n_kv,
                                     head_dim=hd)
    flops = rows * 2 * (n_layer * layer + n_embd * vocab) \
        + n_layer * kernel_flops
    nbytes = ((n_layer * layer + n_embd * vocab) * weight_bytes
              + 2 * state_bytes + rows * vocab * 4)
    return float(flops), float(nbytes)


def brumby_prefill_needs(*, n_layer: int, n_embd: int, n_head: int,
                         n_kv: int, mlp_width: int, vocab: int, rows: float,
                         prompt_len: float, weight_bytes: int,
                         head_dim: int | None = None
                         ) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens by the cheapest form there is at that length:
    every matrix on every token; the retention in its attention form
    (half of the square, scores and values: ``2 t d`` a token a query
    head) — read through the state a token would cost ``2 D d`` a head,
    more than that under ``D`` positions —; the state built once, ``2 D
    d`` a token a KV head; the head on the last position alone.  Bytes:
    every weight once, the state written once."""
    hd = head_dim or n_embd // n_head
    layer = brumby_layer_params(n_embd, n_head, n_kv, mlp_width, hd)
    d_rows = state_rows(hd)
    state_bytes = needed_state_bytes(n_layer=n_layer, rows=rows, n_kv=n_kv,
                                     head_dim=hd)
    tokens = rows * prompt_len
    flops = (n_layer * tokens * (2 * layer + n_head * 2 * prompt_len * hd
                                 + n_kv * 2 * d_rows * (hd + 1))
             + rows * 2 * n_embd * vocab)
    nbytes = ((n_layer * layer + n_embd * vocab) * weight_bytes
              + state_bytes + rows * vocab * 4)
    return float(flops), float(nbytes)
