"""Operations and bytes an OLMoE step needs, from shapes alone (the
GPT-2 counts and ``least_time_s`` are in ``chipbench/roofline.py``).

*Needed* as there: every weight a step multiplies by once, every live
key/value row once, outputs once.  Of the experts only those a step
*touches* are needed — which ones is the one data-dependent quantity, so
it comes in as ``experts_hit_share``, from the program's own
``decode.moe.*`` counters.  In prefill every expert is touched and the
count is bound by operations: ``top_k`` experts a token, not all of
them.
"""

from __future__ import annotations


def olmoe_layer_params(n_embd: int, n_experts: int, expert_width: int
                       ) -> tuple[int, int, int]:
    """``(attention, router, one expert)`` matrix parameters of a layer:
    q, k, v and o; the router; an expert's gate, up and down."""
    return 4 * n_embd * n_embd, n_embd * n_experts, 3 * n_embd * expert_width


def olmoe_decode_step_needs(*, n_layer: int, n_embd: int, vocab: int,
                            n_experts: int, expert_width: int, top_k: int,
                            rows: float, live_positions: float,
                            experts_hit_share: float,
                            weight_bytes: int, kv_bytes: int
                            ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step of ``rows`` sequences over
    ``live_positions`` cached positions: attention and router weights
    once, the *touched* experts' weights once (``experts_hit_share`` of
    them, a layer, on average), every live key and value row once, the
    head once, the logits written once in f32."""
    attn, router, expert = olmoe_layer_params(n_embd, n_experts,
                                              expert_width)
    flops = rows * (n_layer * (2 * (attn + router + top_k * expert)
                               + 4 * live_positions * n_embd)
                    + 2 * n_embd * vocab)
    nbytes = (n_layer * (attn + router
                         + experts_hit_share * n_experts * expert)
              * weight_bytes
              + n_embd * vocab * weight_bytes
              + rows * n_layer * 2 * live_positions * n_embd * kv_bytes
              + rows * vocab * 4)
    return float(flops), float(nbytes)


def olmoe_prefill_needs(*, n_layer: int, n_embd: int, n_head: int,
                        vocab: int, n_experts: int, expert_width: int,
                        top_k: int, rows: float, prompt_len: float,
                        weight_bytes: int, kv_bytes: int
                        ) -> tuple[float, float]:
    """``(flops, bytes)`` of one prefill of ``rows`` prompts of
    ``prompt_len`` tokens: q/k/v/o and the router on every token,
    ``top_k`` experts a token, causal attention (half of the square),
    the head on the last position alone.  Bytes: every weight once, the
    key and value rows written once."""
    del n_head      # the attention's count does not depend on the split
    attn, router, expert = olmoe_layer_params(n_embd, n_experts,
                                              expert_width)
    tokens = rows * prompt_len
    flops = (n_layer * (tokens * 2 * (attn + router + top_k * expert)
                        + rows * 2 * prompt_len * prompt_len * n_embd)
             + rows * 2 * n_embd * vocab)
    nbytes = (n_layer * (attn + router + n_experts * expert) * weight_bytes
              + n_embd * vocab * weight_bytes
              + tokens * n_layer * 2 * n_embd * kv_bytes
              + rows * vocab * 4)
    return float(flops), float(nbytes)
