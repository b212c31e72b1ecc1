"""The routed-experts half of a layer, written once: a router chooses
``k`` of a layer's experts a token (:func:`route_top_k`, a rule a
family), the (row, choice) pairs are sorted by expert and each expert
computes its own (:func:`expert_dispatch` where the layer holds all its
experts, :func:`expert_dispatch_held` where it holds one chip's share),
and the weighted sum comes back in float32 with the counters of what
was routed.  :func:`routed_experts` is that layer; every routed family
of ``models/`` calls it with its own facts and keeps what is its own
(which norm feeds the router, where the residual is added).  An expert
is one of two forms, told by the stacks it is given: SwiGLU (``gate``,
``up``, ``down``: :func:`grouped_swiglu`) or two matrices with a named
activation between them (``up``, ``down``: :func:`grouped_mlp`;
Nemotron-3's squared relu); and the rows the experts multiply may be
other than the rows the router reads (``rows``: a latent projection of
the stream, a quarter as wide) — the weighted sum then leaves in the
experts' width and the family projects it back up, once a token.  The
2021 switch op (``graph/ops.py::MoE``) takes the two pieces it needs.
The products the dispatchers end in are ``ops/grouped.py``'s kernels.

A family that brings a new routing rule adds it to
:func:`route_top_k`; nothing else of a new routed family lives here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .grouped import grouped_gate_up, grouped_product

#: the scoring rules :func:`route_top_k` knows
SCORING_RULES = ("softmax", "sigmoid", "softmax_of_chosen", "noaux_tc",
                 "softmax_bias")


def route_top_k(logits, k: int, scoring: str = "softmax", *, bias=None,
                scale: float = 1.0, eps: float = 1e-20):
    """Scores over the experts in float32, then the ``k`` largest:
    ``(expert ids [..., k], their weights [..., k])``.  ``scoring`` is
    the family's rule, named by the block that calls (never a user's
    flag): ``"softmax"`` — probabilities over all experts, used as the
    softmax gave them, not renormalised (OLMoE, ``models/olmoe.py``);
    ``"sigmoid"`` — an independent score an expert, renormalised over
    the chosen ``k`` (command-a-plus, ``models/cohere_moe.py``);
    ``"softmax_of_chosen"`` — the ``k`` largest logits, then a softmax
    over those ``k`` values alone (Granite 4.0-H,
    ``models/granite_hybrid.py``); ``"noaux_tc"`` — sigmoid scores
    ``p``, the ``k`` largest of ``p + bias`` (``bias`` [experts]: it
    chooses and never weighs), the chosen ``p`` over their sum plus
    ``eps`` (the caller's published term) times ``scale`` (Kimi K2,
    ``models/kimi_k2.py``; ``models/lfm2_moe.py``); ``"softmax_bias"`` —
    probabilities ``p`` over *all* columns (a layer's routed experts
    and, behind them, its zero-compute ones), the ``k`` largest of ``p
    + bias`` (the bias chooses and never weighs), the chosen ``p``
    multiplied by ``scale`` and **not renormalised** (LongCat-Flash,
    ``models/longcat_flash.py``; an id past the routed experts names a
    zero-compute expert, :func:`zero_expert_pairs`)."""
    if scoring not in SCORING_RULES:
        raise ValueError(f"scoring must be one of {SCORING_RULES}, "
                         f"got {scoring!r}")
    logits = logits.astype(jnp.float32)
    if scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        p, eid = lax.top_k(probs, k)
        return eid, p
    if scoring == "softmax_of_chosen":
        top, eid = lax.top_k(logits, k)
        return eid, jax.nn.softmax(top, axis=-1)
    if scoring == "noaux_tc":
        probs = jax.nn.sigmoid(logits)
        _, eid = lax.top_k(probs + bias.astype(jnp.float32), k)
        p = jnp.take_along_axis(probs, eid, axis=-1)
        return eid, scale * p / (jnp.sum(p, axis=-1, keepdims=True) + eps)
    if scoring == "softmax_bias":
        probs = jax.nn.softmax(logits, axis=-1)
        _, eid = lax.top_k(probs + bias.astype(jnp.float32), k)
        return eid, scale * jnp.take_along_axis(probs, eid, axis=-1)
    p, eid = lax.top_k(jax.nn.sigmoid(logits), k)
    return eid, p / jnp.sum(p, axis=-1, keepdims=True)


def expert_dispatch(x, eid, gate, num_experts: int, expert_fn):
    """Routed experts on rows grouped by expert: every (row, choice) pair
    is computed, by its own expert only, and each expert's weights are
    read once however many rows chose it.  No capacity, nothing dropped.

    ``x`` [T, d]; ``eid``/``gate`` [T, k] (:func:`route_top_k`).
    ``expert_fn(xs, group_sizes, es)`` maps the [T*k, d] rows sorted by
    expert (``es`` [T*k] names each row's expert, ``group_sizes`` [E]
    counts them: the arguments of ``lax.ragged_dot``) to [T*k, d_out].
    Returns ``(sum_k gate * expert_k(x) [T, d_out] in float32, as it was
    summed, group_sizes)``."""
    t, k = eid.shape
    flat = eid.reshape(t * k)
    order = jnp.argsort(flat, stable=True)       # slots, grouped by expert
    sizes = jnp.sum(flat[:, None] == jnp.arange(num_experts)[None, :],
                    axis=0, dtype=jnp.int32)
    ys = expert_fn(x[order // k], sizes, flat[order])
    ys = ys[jnp.argsort(order)].reshape(t, k, -1)       # back to row order
    y = jnp.sum(ys.astype(jnp.float32)
                * gate[..., None].astype(jnp.float32), axis=1)
    return y, sizes


def grouped_swiglu(xs, experts, sizes):
    """The routed experts' SwiGLU on rows sorted by expert: ``xs [rows,
    d]``, ``experts`` the stacks ``gate`` / ``up [E, d, h]`` and ``down
    [E, h, d]``, ``sizes [E]`` rows each (they may sum to less than the
    rows: the ``expert_fn`` of both dispatchers).  ``[rows, d]`` in
    ``xs``'s type.  Which way a product goes — the kernel that streams
    the touched matrices once, or the one that tiles a prompt's rows —
    is its static shape's choice (``ops/grouped.py``)."""
    a = grouped_gate_up(xs, experts["gate"], experts["up"], sizes)
    return grouped_product(a, experts["down"], sizes)


#: what may stand between a two-matrix expert's ``up`` and ``down``
#: (:func:`grouped_mlp`), by the name a block gives: float32 in, float32
#: out
ACTIVATIONS = {"relu2": lambda a: jnp.square(jax.nn.relu(a))}


def _activation(name: str):
    if name not in ACTIVATIONS:
        raise ValueError(f"activation must be one of {tuple(ACTIVATIONS)}, "
                         f"got {name!r}")
    return ACTIVATIONS[name]


def grouped_mlp(xs, experts, sizes, activation: str):
    """The routed experts without a gate, on rows sorted by expert:
    ``act(xs up) down`` with ``up [E, k, h]``, ``down [E, h, n]`` — two
    :func:`~defer_tpu.ops.grouped.grouped_product` calls, the activation
    between them taken in float32 of the first product as it was
    rounded to ``xs``'s type (an elementwise pass over ``[rows, h]``,
    the compiler's to fuse).  ``[rows, n]`` in ``xs``'s type."""
    a = grouped_product(xs, experts["up"], sizes)
    a = _activation(activation)(a.astype(jnp.float32)).astype(xs.dtype)
    return grouped_product(a, experts["down"], sizes)


def shared_mlp(h, up, down, activation: str):
    """A shared expert without a gate on ``h`` [T, d]: ``act(h up)
    down`` in float32 sums, the activation on the float32 product:
    ``[T, n]`` float32."""
    a = jnp.dot(h, up, preferred_element_type=jnp.float32)
    return jnp.dot(_activation(activation)(a).astype(h.dtype), down,
                   preferred_element_type=jnp.float32)


#: the most (row, choice) pairs one grouped product of
#: :func:`expert_dispatch_held` takes: a prompt's pairs beyond it are
#: worked off run by run, as many runs as hold the pairs that fell to
#: held experts
_HELD_RUN = 4096


def expert_dispatch_held(x, eid, gate, held: tuple[int, int], expert_fn):
    """:func:`expert_dispatch` for a layer that holds experts
    ``held[0] .. held[1] - 1`` of those its router chooses among (one
    chip's share of a layer under expert parallelism): the pairs that
    fell to a held expert are computed, by that expert; the pairs that
    fell elsewhere are another chip's, and are **not computed** — they
    are sorted behind the held ones and no product sees them, not even
    as zeros.  No capacity, nothing held is dropped.

    ``x`` [T, d]; ``eid``/``gate`` [T, k] over *all* experts
    (:func:`route_top_k`: the weights stay those of the full choice).
    ``expert_fn(xs, group_sizes)`` maps rows sorted by held expert
    (``group_sizes`` [held experts]; they may sum to less than the
    rows: the rest are no expert's) to [rows, d_out].  Returns ``(the
    held pairs' weighted sum [T, d_out] in float32, group_sizes)``.

    Up to :data:`_HELD_RUN` pairs are one grouped product.  A prompt
    has more; its sorted pairs are taken a run at a time, in a loop
    whose trip count is the number of runs that hold held pairs — with
    1/8 of the experts held, 1/8 of the runs."""
    lo, hi = held
    n_held = hi - lo
    t, k = eid.shape
    pairs = t * k
    flat = eid.reshape(pairs) - lo
    mine = jnp.logical_and(flat >= 0, flat < n_held)
    key = jnp.where(mine, flat, n_held)          # absent: sorted last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                    axis=0, dtype=jnp.int32)
    weight = jnp.where(mine, gate.reshape(pairs).astype(jnp.float32), 0.0)
    run = min(pairs, _HELD_RUN)

    def one_run(y, start, slots):
        """The sorted pairs ``start .. start + run - 1`` added to ``y``."""
        ends = jnp.cumsum(sizes)
        part = jnp.clip(ends - start, 0, run) \
            - jnp.clip(ends - sizes - start, 0, run)
        rows = slots // k
        ys = expert_fn(x[rows], part).astype(jnp.float32)
        # rows behind the last group are no expert's: whatever the
        # product left there must not reach the sum
        live = (start + jnp.arange(run) < ends[-1])[:, None]
        return y.at[rows].add(
            jnp.where(live, ys * weight[slots][:, None], 0.0))

    d_out = jax.eval_shape(
        expert_fn, jax.ShapeDtypeStruct((run,) + x.shape[1:], x.dtype),
        jax.ShapeDtypeStruct(sizes.shape, sizes.dtype)).shape[-1]
    y = jnp.zeros((t, d_out), jnp.float32)
    if run == pairs:
        return one_run(y, 0, order), sizes
    # whole runs only: the tail of the order is padded with pair 0,
    # which ``live`` masks
    padded = jnp.concatenate(
        [order, jnp.zeros((-pairs % run,), order.dtype)])

    def body(i, y):
        start = i * run
        return one_run(y, start, lax.dynamic_slice(padded, (start,), (run,)))

    return lax.fori_loop(0, (jnp.sum(sizes) + run - 1) // run, body, y), sizes


def zero_expert_pairs(x, eid, gate, num_experts: int):
    """The third fate of a (row, choice) pair, beside
    :func:`expert_dispatch_held`'s two (a held expert's: computed;
    another chip's: left out): a pair whose id is ``>= num_experts``
    fell to a **zero-compute expert**, the identity — it adds ``weight
    * x`` and multiplies by no matrix.  Such pairs are never sorted nor
    dispatched (to the held dispatcher they are no expert's): all of a
    row's are one multiply of ``x`` by the sum of their weights.

    ``x`` [T, d]; ``eid``/``gate`` [T, k] (:func:`route_top_k` over
    real and zero columns).  Returns ``(x * sum of the zero pairs'
    weights [T, d] in float32, the number of zero pairs)``."""
    zero = eid >= num_experts
    weight = jnp.sum(jnp.where(zero, gate.astype(jnp.float32), 0.0), axis=-1)
    return x.astype(jnp.float32) * weight[:, None], \
        jnp.sum(zero, dtype=jnp.int32)


def held_range(experts_held, num_experts: int) -> tuple[int, int]:
    """The routed experts ``[lo, hi)`` a layer holds of the
    ``num_experts`` its router chooses among: a block's ``experts_held``
    (None: all of them), checked."""
    lo, hi = experts_held or (0, num_experts)
    if not 0 <= lo < hi <= num_experts:
        raise ValueError(f"experts_held {experts_held} is no range of "
                         f"{num_experts} routed experts")
    return lo, hi


def route(h, router, k: int, scoring: str, scale: float = 1.0,
          eps: float = 1e-20):
    """``(ids [T, k], weights [T, k])`` of the normed stream ``h`` [T, d] by
    the router's ``w`` [d, columns] and ``bias`` (:func:`route_top_k`)."""
    # the logits leave the product in float32: rounded, they would flip
    # the last of the chosen at near-ties
    return route_top_k(
        jnp.dot(h, router["w"], preferred_element_type=jnp.float32),
        k, scoring=scoring, bias=router.get("bias"), scale=scale, eps=eps)


def routed_experts(h, router, experts, *, k: int, scoring: str,
                   num_experts: int, held: tuple[int, int] | None = None,
                   scale: float = 1.0, eps: float = 1e-20,
                   zero_experts: int = 0, shared=None, sow=None,
                   rows=None, activation: str | None = None):
    """The routed experts of one layer on the normed stream ``h`` [T, d]:
    ``(the pairs' weighted sum [T, d], the shared experts' [T, d] or
    None)``, both float32 and neither added to anything — where the
    residual is and what multiplies a branch is the family's.

    ``router``, ``k`` / ``scoring`` / ``scale`` / ``eps`` are :func:`route`'s;
    ``experts`` the stacks of one of the two forms an expert has:
    ``gate`` / ``up`` / ``down``, what :func:`grouped_swiglu` reads, or
    ``up`` / ``down`` alone with ``activation`` naming what stands
    between them (:func:`grouped_mlp`, :data:`ACTIVATIONS`).  ``rows``
    [T, r], where given, are the rows the experts multiply in place of
    ``h`` (a latent projection of it; the router and the shared experts
    still read ``h``): the pairs' sum then comes back ``[T, n]``, as
    wide as the experts' ``down`` leaves it, and is the caller's to
    project.  ``held`` is
    what the layer holds of the ``num_experts`` its router chooses
    among: None — all of them, one grouped product over every pair
    (:func:`expert_dispatch`); ``(lo, hi)`` — that share, whose pairs
    alone are computed (:func:`expert_dispatch_held`; ``experts`` then
    stacks ``hi - lo``).  ``zero_experts`` router columns behind the
    ``num_experts`` are zero-compute experts, whose pairs are added
    here (:func:`zero_expert_pairs`).  ``shared`` — the leaves
    ``(gate, up, down)`` of the shared experts side by side, every
    token's — is one SwiGLU on ``h`` (a family whose shared expert has
    no gate computes it itself, :func:`shared_mlp`, and hands none in).

    A dict ``sow`` takes the choice (``moe.chosen`` / ``moe.weights``
    [T, k]: no statistics) and the step's counters, the names a block
    lists as its ``decode_stats``: ``moe.assignments`` (every pair),
    ``moe.experts_hit`` and ``moe.load_max`` (of the experts held) and,
    with ``held``, ``moe.held_assignments`` (the pairs computed here);
    with ``zero_experts``, ``moe.zero_assignments`` and
    ``moe.real_assignments``."""
    columns = num_experts + zero_experts
    if router["w"].shape[-1] != columns:
        raise ValueError(f"a router of {router['w'].shape[-1]} columns for "
                         f"{num_experts} routed + {zero_experts} "
                         "zero-compute experts")
    eid, gate = route(h, router, k, scoring, scale, eps)

    if "gate" in experts:
        def expert_fn(xs, sizes, _es=None):
            return grouped_swiglu(xs, experts, sizes)
    else:
        def expert_fn(xs, sizes, _es=None):
            return grouped_mlp(xs, experts, sizes, activation)

    seen = h if rows is None else rows
    if held is None:
        out, sizes = expert_dispatch(seen, eid, gate, num_experts, expert_fn)
    else:
        out, sizes = expert_dispatch_held(seen, eid, gate, held, expert_fn)
    if zero_experts:
        zero, zeros = zero_expert_pairs(h, eid, gate, num_experts)
    if shared is not None:
        gate_w, up_w, down_w = shared
        shared = jnp.dot(jax.nn.silu(h @ gate_w) * (h @ up_w), down_w,
                         preferred_element_type=jnp.float32)
    if sow is not None:
        sow["moe.chosen"], sow["moe.weights"] = eid, gate
        pairs = jnp.int32(eid.size)
        if held is None:
            sow["moe.assignments"] = jnp.sum(sizes)
        else:
            sow["moe.assignments"] = pairs
            sow["moe.held_assignments"] = jnp.sum(sizes)
        sow["moe.experts_hit"] = jnp.sum(sizes > 0, dtype=jnp.int32)
        sow["moe.load_max"] = jnp.max(sizes)
        if zero_experts:
            sow["moe.zero_assignments"] = zeros
            sow["moe.real_assignments"] = pairs - zeros
    return (out + zero if zero_experts else out), shared
