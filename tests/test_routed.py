"""The routed-experts layer (``ops/routed.py::routed_experts``) is the
one place a routed family's second half is written: each family's block
calls it with the block's own facts, and what the block computes and
sows is what the function gives when it is called with those facts
directly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu import models
from defer_tpu.graph.ops import rms_norm
from defer_tpu.models.cohere_moe import layer_norm
from defer_tpu.ops.routed import (held_range, route, routed_experts,
                                  shared_mlp)

T = 12
CHOICE = {"moe.chosen", "moe.weights"}


def _shared(p):
    return tuple(p[f"shared_{nm}"]["w"] for nm in ("gate", "up", "down"))


def _olmoe(op, p, x):
    h = rms_norm(x, p["ln2"]["scale"], op.rms_eps)
    facts = dict(k=op.experts_per_tok, scoring="softmax",
                 num_experts=op.num_experts)
    return (lambda sow: op._finish(p, x, jnp.zeros_like(x), sow), h, facts,
            lambda routed, _: x + routed)


def _cohere(op, p, x):
    h = layer_norm(x, p["ln"]["scale"], op.ln_eps)
    facts = dict(k=op.experts_per_tok, scoring="sigmoid",
                 num_experts=op.num_experts, held=op.held, shared=_shared(p))
    y = jnp.zeros((T, op.num_heads * op.head_dim), x.dtype)
    return (lambda sow: op._finish(p, x, y, sow), h, facts,
            lambda routed, shared: x + routed + shared / op.num_shared)


def _granite(op, p, x):
    h = rms_norm(x, p["ln2"]["scale"], op.rms_eps)
    facts = dict(k=op.experts_per_tok, scoring="softmax_of_chosen",
                 num_experts=op.num_experts, held=op.held, shared=_shared(p))
    return (lambda sow: op.expert_half(p, x, x.dtype, sow, updates=3), h,
            facts, lambda routed, shared:
            x + op.residual_multiplier * (routed + shared))


def _kimi(op, p, x):
    facts = dict(k=op.experts_per_tok, scoring="noaux_tc",
                 num_experts=op.num_experts, held=op.held,
                 scale=op.routed_scale, shared=_shared(p))
    return (lambda sow: op._ffn(p, x, sow), x, facts,
            lambda routed, shared: routed + shared)


def _longcat(op, p, x):
    facts = dict(k=op.experts_per_tok, scoring="softmax_bias",
                 num_experts=op.num_experts, held=op.held,
                 scale=op.routed_scale, zero_experts=op.zero_experts)
    return (lambda sow: op._moe(p, x, sow), x, facts,
            lambda routed, _: routed)


FAMILIES = {
    "olmoe_tiny": ("block_1", _olmoe),
    "cohere_moe_tiny": ("block_3", _cohere),
    "granite_hybrid_tiny": ("block_2", _granite),
    "kimi_k2_tiny": ("block_1", _kimi),
    "longcat_flash_tiny": ("block_0", _longcat),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_a_blocks_routed_half_is_the_one_layer_with_its_facts(family):
    block, facts_of = FAMILIES[family]
    graph = getattr(models, family)()
    op = graph.nodes[block].op
    p = graph.init(jax.random.key(0))[block]
    x = jax.random.normal(jax.random.key(1), (T, 64), jnp.float32)
    half, h, facts, finish = facts_of(op, p, x)
    sown, direct = {}, {}
    got = half(sown)
    routed, shared = routed_experts(h, p["router"], p["experts"], **facts,
                                    sow=direct)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(finish(routed, shared)))
    # the block sows exactly what it lists, and the choice beside it
    assert set(sown) - CHOICE == set(op.decode_stats)
    assert CHOICE <= set(sown)
    # what is the layer's of it is the layer's: the same names, the
    # same numbers (the rest is the family's own: ``ssm.updates``)
    assert set(direct) - CHOICE == {nm for nm in op.decode_stats
                                    if nm.startswith("moe.")}
    for name, value in direct.items():
        np.testing.assert_array_equal(np.asarray(sown[name]),
                                      np.asarray(value))
    # every pair is counted once, the held ones among them
    pairs = T * facts["k"]
    assert int(sown["moe.assignments"]) == pairs
    assert int(sown.get("moe.held_assignments", pairs)) <= pairs
    eid, gate = route(h, p["router"], facts["k"], facts["scoring"],
                      facts.get("scale", 1.0))
    np.testing.assert_array_equal(np.asarray(sown["moe.chosen"]),
                                  np.asarray(eid))
    np.testing.assert_array_equal(np.asarray(sown["moe.weights"]),
                                  np.asarray(gate))


@pytest.mark.parametrize("held", [None, (128, 256)],
                         ids=["all-held", "a-quarter-held"])
def test_two_matrix_experts_on_latent_rows_22_of_512(held):
    """The layer with an expert that is ``(up, down)`` and a named
    activation, on rows (``rows=u``) other than the rows the router and
    the shared expert read (``h``): 22 of 512 by ``noaux_tc``, all held
    or experts 128-255; the pairs' sum leaves in the latent width;
    against the pairs one by one.  The family's shared expert, two
    matrices and the same activation on ``h``, is ``shared_mlp``."""
    rng = np.random.default_rng(7)
    f32 = jnp.float32
    t, d, r, w, sh, n, k = 5, 24, 8, 12, 16, 512, 22
    lo, hi = held or (0, n)
    h = jnp.asarray(rng.normal(size=(t, d)), f32)
    u = jnp.asarray(rng.normal(size=(t, r)), f32)
    router = {"w": jnp.asarray(rng.normal(size=(d, n)), f32) / 5,
              "bias": jnp.asarray(rng.normal(size=(n,)), f32) * 0.01}
    ex = {"up": jnp.asarray(rng.normal(size=(hi - lo, r, w)), f32) / 3,
          "down": jnp.asarray(rng.normal(size=(hi - lo, w, r)), f32) / 3}
    shared = (jnp.asarray(rng.normal(size=(d, sh)), f32) / 5,
              jnp.asarray(rng.normal(size=(sh, d)), f32) / 4)
    sow = {}
    got, none = jax.jit(lambda h, u: routed_experts(
        h, router, ex, k=k, scoring="noaux_tc", num_experts=n, held=held,
        scale=5.0, rows=u, activation="relu2", sow=sow))(h, u)
    got_shared = shared_mlp(h, *shared, "relu2")
    assert none is None
    eid, gate = route(h, router, k, "noaux_tc", 5.0)
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 5.0, rtol=1e-5)
    want = np.zeros((t, r), np.float32)
    for i in range(t):
        for j in range(k):
            e = int(eid[i, j]) - lo
            if 0 <= e < hi - lo:
                a = jnp.square(jax.nn.relu(u[i] @ ex["up"][e]))
                want[i] += float(gate[i, j]) * np.asarray(a @ ex["down"][e])
    assert got.shape == (t, r) and got_shared.shape == (t, d)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(got_shared),
        np.asarray(jnp.square(jax.nn.relu(h @ shared[0])) @ shared[1]),
        atol=1e-4, rtol=1e-4)
    assert set(sow) >= CHOICE | {"moe.assignments", "moe.experts_hit",
                                 "moe.load_max"}
    assert ("moe.held_assignments" in sow) == (held is not None)


def test_the_layer_refuses_a_router_of_other_columns():
    graph = models.olmoe_tiny()
    p = graph.init(jax.random.key(0))["block_0"]
    h = jnp.zeros((T, 64), jnp.float32)
    with pytest.raises(ValueError, match="a router of 8 columns"):
        routed_experts(h, p["router"], p["experts"], k=2, scoring="softmax",
                       num_experts=8, zero_experts=4)
    assert held_range(None, 8) == (0, 8) and held_range((2, 4), 8) == (2, 4)
    with pytest.raises(ValueError, match="no range of 8 routed experts"):
        held_range((4, 9), 8)
