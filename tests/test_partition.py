"""Partitioner tests: the core invariant is stitched-stages ≡ full model
(what the reference's construct_model implicitly guarantees,
reference src/dag_util.py:27-31)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.graph.analysis import valid_cut_points


def _compose(stages, params, x):
    y = x
    for s in stages:
        y = s.fn(s.select_params(params), y)
    return y


def test_stage_structure():
    g = resnet_tiny()
    cuts = ["add", "add_2"]
    stages = partition(g, cuts)
    assert len(stages) == 3
    assert stages[0].input_name == g.input_name
    assert stages[0].output_name == "add"
    assert stages[-1].output_name == g.output_name
    # every graph node appears in exactly one stage
    all_nodes = [n for s in stages for n in s.node_names]
    assert sorted(all_nodes) == sorted(g.topo_order)


def test_partition_equivalence_resnet_tiny():
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    full = g.apply(params, x)
    for cuts in (["add_1"], ["add", "add_1", "add_2"]):
        stitched = _compose(partition(g, cuts), params, x)
        np.testing.assert_allclose(np.asarray(full), np.asarray(stitched),
                                   rtol=2e-5, atol=2e-5)


def test_auto_partition_equivalence():
    g = resnet_tiny()
    params = g.init(jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 32, 32, 3))
    full = g.apply(params, x)
    stages = partition(g, num_stages=4)
    assert len(stages) == 4
    stitched = _compose(stages, params, x)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stitched),
                               rtol=2e-5, atol=2e-5)


def test_invalid_cut_rejected():
    """Non-articulation cuts must fail loudly (the reference silently
    requires single-tensor cuts — SURVEY.md §3.5)."""
    g = resnet_tiny()
    valid = set(valid_cut_points(g))
    interior = next(n for n in g.topo_order
                    if n not in valid and n != g.output_name)
    with pytest.raises(ValueError, match="single-tensor"):
        partition(g, [interior])
    with pytest.raises(ValueError, match="not a node"):
        partition(g, ["nope"])
    with pytest.raises(ValueError, match="topological"):
        partition(g, ["add_2", "add"])


# -- the routed-expert block: cuts by cost --------------------------------------

def test_olmoe_block_flops_count_the_experts_a_token_uses():
    """``flops()`` of the routed-expert block: q/k/v/o, attention, the
    router and ``experts_per_tok`` experts a token — not all of them."""
    from defer_tpu.models import olmoe
    g = olmoe(2, 2048, 16, 1024)
    node = g.nodes["block_0"]
    got = node.op.flops((g.nodes["embeddings"].out_spec,), node.out_spec)
    t, d, h = 1024, 2048, 1024
    want = (2 * t * d * 4 * d + 4 * t * t * d + 2 * t * d * 64
            + 8 * 2 * t * 3 * d * h)
    assert got == want
    all_64 = want + (64 - 8) * 2 * t * 3 * d * h
    assert all_64 / got > 5.5


@pytest.mark.parametrize("vocab, want", [
    # a head that costs nothing beside a block: the even cuts of
    # gpt_stage_cuts(16, 4), four blocks a stage
    (64, ["block_3", "block_7", "block_11"]),
    # the published vocabulary: the head costs 1.4 blocks (206 of 146
    # MFLOP a token at 1024 positions), so the stage that carries it
    # gets three blocks and the second five: cuts by cost, not by count
    (50304, ["block_3", "block_8", "block_12"])])
def test_olmoe_16_layers_over_4_stages_cut_by_cost(vocab, want):
    from defer_tpu.models import gpt_stage_cuts, olmoe
    g = olmoe(16, 2048, 16, 1024, vocab=vocab)
    stages = partition(g, num_stages=4)
    assert [s.output_name for s in stages[:-1]] == want
    if vocab == 64:
        assert want == gpt_stage_cuts(16, 4)
    blocks = [sum(nm.startswith("block_") for nm in s.node_names)
              for s in stages]
    assert sum(blocks) == 16 and max(blocks) - min(blocks) <= 2
