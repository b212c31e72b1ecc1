"""Flash-attention kernel: equivalence with reference attention.

Runs the identical Pallas kernel in interpreter mode on the CPU backend
(SURVEY.md §4: fake-backend testing), so the math under test is exactly what
compiles for the MXU on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from defer_tpu.ops import flash_attention
from defer_tpu.parallel.ring_attention import full_attention

# default CPU matmuls may run at reduced precision; the comparison below is
# between two f32 implementations, so the tolerance covers that
TOL = 5e-3


@pytest.mark.parametrize("shape,causal", [
    ((2, 3, 64, 64, 16), False),
    ((1, 2, 100, 100, 24), True),     # non-multiple of block: padding path
    ((2, 2, 37, 53, 8), False),       # Tq != Tk
    ((1, 1, 130, 130, 64), True),     # spills into a second q block
    ((2, 5, 600, 600, 64), True),     # heads of 64 past a block of 512
    ((1, 3, 90, 300, 64), True),      # Tq != Tk, bottom-right aligned
    ((1, 2, 200, 200, 64), False),    # the rectangle keeps its own kernel
])
def test_matches_reference(shape, causal):
    b, h, tq, tk, d = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, h, tq, d))
    k = jax.random.normal(ks[1], (b, h, tk, d))
    v = jax.random.normal(ks[2], (b, h, tk, d))
    out = flash_attention(q, k, v, causal=causal)
    ref = full_attention(q, k, v, causal=causal)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_multi_block_k_loop():
    """Accumulation across several K/V blocks (the online-softmax carry)."""
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 32, 16))
    k = jax.random.normal(ks[1], (1, 2, 96, 16))
    v = jax.random.normal(ks[2], (1, 2, 96, 16))
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    ref = full_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_causal_masks_future():
    """Output at position t must not depend on keys/values after t."""
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (1, 1, 16, 8))
    k = jax.random.normal(ks[1], (1, 1, 16, 8))
    v = jax.random.normal(ks[2], (1, 1, 16, 8))
    out1 = flash_attention(q, k, v, causal=True)
    # perturb the last key/value; all but the last position must be unchanged
    k2 = k.at[:, :, -1].add(7.0)
    v2 = v.at[:, :, -1].add(-3.0)
    out2 = flash_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(out1[:, :, :-1]),
                               np.asarray(out2[:, :, :-1]), atol=1e-6)
    assert not np.allclose(np.asarray(out1[:, :, -1]),
                           np.asarray(out2[:, :, -1]))


def test_causal_decode_attends_to_full_prefix():
    """Tq=1 against a long K/V prefix (KV-cache decode): bottom-right
    causal alignment must admit every prefix position."""
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (1, 2, 1, 16))
    k = jax.random.normal(ks[1], (1, 2, 48, 16))
    v = jax.random.normal(ks[2], (1, 2, 48, 16))
    out = flash_attention(q, k, v, causal=True)
    ref = full_attention(q, k, v, causal=False)  # full prefix visible
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)
    # chunked-decode shape (Tq=5 against Tk=48): bottom-right alignment,
    # oracle is full_attention's own bottom-right causal mask
    q5 = jax.random.normal(ks[0], (1, 2, 5, 16))
    out5 = flash_attention(q5, k, v, causal=True)
    ref5 = full_attention(q5, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out5), np.asarray(ref5),
                               atol=TOL, rtol=TOL)


def test_bfloat16_io():
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 2, 64, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 64, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 64, 32), jnp.bfloat16)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                         v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def test_transformer_block_flash_matches_xla():
    """The graph-level TransformerBlock gives the same output under both
    attention implementations."""
    from defer_tpu.graph.ir import GraphBuilder
    from defer_tpu.graph.ops import TransformerBlock

    outs = {}
    for impl in ("xla", "flash"):
        b = GraphBuilder(f"blk_{impl}")
        x = b.input((24, 32), jnp.float32)
        y = b.add(TransformerBlock(num_heads=2, attn_impl=impl), x,
                  name="blk")
        g = b.build()
        params = g.init(jax.random.key(4))
        xin = jax.random.normal(jax.random.key(5), (2, 24, 32))
        outs[impl] = np.asarray(g.apply(params, xin))
    np.testing.assert_allclose(outs["flash"], outs["xla"],
                               atol=TOL, rtol=TOL)


# -- grouped queries and a window (the band kernel) ----------------------------

def _banded_reference(q, k, v, window):
    """Masked softmax: query head j on KV head j // (H / Hkv), row t over
    keys s with 0 <= t - s < window (bottom-right aligned)."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    t_q, t_k = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision="highest") / np.sqrt(q.shape[-1])
    t = jnp.arange(t_q)[:, None] + (t_k - t_q)
    seen = jnp.arange(t_k)[None, :] <= t
    if window is not None:
        seen &= t - jnp.arange(t_k)[None, :] < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


@pytest.mark.parametrize("h,hkv", [(4, 4), (4, 2), (16, 1)],
                         ids=["mha", "gqa2", "mqa16"])
@pytest.mark.parametrize("t,window,block", [
    (40, None, 16),     # grouped queries alone, causal
    (12, 16, 8),        # shorter than the window
    (16, 16, 8),        # exactly the window
    (50, 16, 8),        # over it, and no multiple of the block
    (70, 24, 16),       # a window that is no multiple of the block
    (64, 1, 16),        # a row sees itself alone
    (512, None, 128),   # 4 x 4 blocks of four lane groups: the sum a lane
    (520, 300, 128),    # the same under a window, ragged
    (200, None, 64),    # ragged, inner and edge pairs: 4 x 4's triangle
], ids=["causal", "under", "at", "over-ragged", "odd-window", "window1",
        "lanes", "lanes-window", "ragged-inner-and-edge"])
def test_band_kernel_matches_the_masked_softmax(h, hkv, t, window, block):
    ks = jax.random.split(jax.random.key(t), 3)
    q = jax.random.normal(ks[0], (2, h, t, 16))
    k = jax.random.normal(ks[1], (2, hkv, t, 16))
    v = jax.random.normal(ks[2], (2, hkv, t, 16))
    out = flash_attention(q, k, v, causal=True, window=window,
                          block_q=block, block_k=block)
    ref = _banded_reference(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_band_kernel_decode_alignment_and_default_blocks():
    """Tq < Tk (bottom-right aligned) at the band path's own block size,
    in bfloat16."""
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (1, 8, 24, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 700, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 700, 32), jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, window=600)
    ref = _banded_reference(*(a.astype(jnp.float32) for a in (q, k, v)), 600)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=3e-2, rtol=3e-2)


def _kernel_names(jaxpr) -> list:
    """The ``pallas_call`` names of a jaxpr, at any depth, in order."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernel_names(sub)
    return names


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("t_q,t_k", [(200, 200), (72, 200)],
                         ids=["prompt", "decode-aligned"])
def test_full_head_causal_takes_the_paired_kernel(dtype, tol, t_q, t_k):
    """GPT-2's geometry cut small — as many KV heads as query heads, of
    64, no window — in blocks of 64, so that a head has inner and edge
    pairs: the causal triangle's pairs and no others, every one live.
    Operands go to the products in their own type: float32 ones stay
    far inside what a bfloat16 ``p`` would cost (its 8 bits: ~4e-3)."""
    from defer_tpu.obs.registry import REGISTRY
    from defer_tpu.ops.flash_attention import live_pairs
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (2, 5, t_q, 64), dtype)
    k = jax.random.normal(ks[1], (2, 5, t_k, 64), dtype)
    v = jax.random.normal(ks[2], (2, 5, t_k, 64), dtype)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    assert out.dtype == dtype
    pairs = live_pairs(t_q, t_k, 64, 64, None).shape[1]
    assert pairs == {200: 10, 72: 7}[t_q]
    steps = REGISTRY.gauge("prefill.flash.grid_steps").value
    assert steps == 2 * 5 * pairs
    assert REGISTRY.gauge("prefill.flash.live_steps").value == steps
    ref = _banded_reference(*(a.astype(jnp.float32) for a in (q, k, v)),
                            None)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("hkv,window,causal,name", [
    (4, None, True, "flash_causal"),
    (2, None, True, "flash_grouped"),
    (4, 8, True, "flash_band"),
    (2, 8, True, "flash_band"),
    (4, None, False, None),
])
def test_a_trace_tells_the_kernels_apart(hkv, window, causal, name):
    """A call's Pallas kernel is named by kind (what a device trace's
    readers select by): a full-head causal call's is its own — head-major
    and token-major alike, so a ``breakdown`` compares it under one name
    across PR 70 —, and the non-causal rectangle's call carries none."""
    from defer_tpu.ops.flash_attention import flash_causal_columns
    q = jnp.zeros((1, 4, 40, 16))
    k = jnp.zeros((1, hkv, 40, 16))
    jaxpr = jax.make_jaxpr(lambda q, k: flash_attention(
        q, k, k, causal=causal, window=window))(q, k)
    assert _kernel_names(jaxpr.jaxpr) == [name]
    if causal:
        jaxpr = jax.make_jaxpr(lambda q, k: flash_causal_columns(
            _columns(q), _columns(k), _columns(k), heads=4, kv_heads=hkv,
            window=window))(q, k)
        assert _kernel_names(jaxpr.jaxpr) == [name]


@pytest.mark.parametrize("family", ["gpt_tiny", "olmoe_tiny"])
def test_a_full_head_family_s_block_is_the_same_under_both_paths(family):
    """The two families whose prompts are full-head causal calls: a
    block's prompt form through the paired kernel against plain XLA."""
    from defer_tpu import models
    graph = getattr(models, family)(seq_len=48)
    params = graph.init(jax.random.key(0))
    op, p = graph.nodes["block_1"].op, params["block_1"]
    assert op.kv_heads == op.num_heads and op.window is None
    width = jax.tree.leaves(p["ln1"])[0].size
    x = jax.random.normal(jax.random.key(2), (2, 40, width))
    flash = type(op)(**{**vars(op), "attn_impl": "flash"})
    xla = type(op)(**{**vars(op), "attn_impl": "xla"})
    np.testing.assert_allclose(np.asarray(flash.apply(p, x)),
                               np.asarray(xla.apply(p, x)),
                               atol=2e-5, rtol=2e-5)


def _columns(x):
    """``[b, h, t, d]`` token-major: ``[b, t, h * d]``."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _token_major_case(h, d, t_q, t_k, dtype, batch=1):
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (batch, h, t_q, d), dtype)
    k = jax.random.normal(ks[1], (batch, h, t_k, d), dtype)
    v = jax.random.normal(ks[2], (batch, h, t_k, d), dtype)
    return q, k, v


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d,t_q,t_k,block", [
    (25, 64, 896, 896, None),   # gpt2xl_long_prompt: 12 lane rows and a
                                # half, two blocks of 512 over 896 rows
    (4, 64, 512, 512, None),    # the batch cells' prompt: one pair
    (3, 32, 200, 200, 64),      # four heads a lane row, 96 columns of 128
    (2, 64, 8, 40, 16),         # 8 queries on 40 keys, bottom-right
], ids=["25x64x896", "4x64x512", "3x32x200", "2x64x8on40"])
def test_token_major_matches_the_masked_softmax(h, d, t_q, t_k, block,
                                                dtype, tol):
    """``flash_causal_columns`` on the projection's own columns against
    the masked softmax in float32.  The interpreter fills what a block
    holds beyond the operand with NaN, as the chip may: the last lane
    row of an odd head count and the rows past the prompt's end."""
    from defer_tpu.ops.flash_attention import flash_causal_columns
    q, k, v = _token_major_case(h, d, t_q, t_k, dtype)
    out = flash_causal_columns(_columns(q), _columns(k), _columns(v),
                               heads=h, block_q=block, block_k=block)
    assert out.shape == (1, t_q, h * d) and out.dtype == dtype
    ref = _banded_reference(*(a.astype(jnp.float32) for a in (q, k, v)),
                            None)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(_columns(ref)), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h,d,t_q,t_k", [(3, 64, 40, 40), (5, 32, 24, 56)],
                         ids=["3x64", "5x32-decode-aligned"])
def test_token_major_reads_nothing_beyond_its_sizes(h, d, t_q, t_k, dtype):
    """The odd head count's last lane row, and the last blocks' rows,
    with NaN planted in everything beyond the real columns and rows: the
    output's real part is finite and what the exact operands give.  (A
    zeroed query times a NaN key is NaN, and so is a masked score's zero
    weight times a NaN value: the kernel zeroes the keys' dead columns
    and the values' dead rows.)"""
    from defer_tpu.ops.flash_attention import (_lane_row_attention,
                                               flash_causal_columns)
    q, k, v = (_columns(a) for a in _token_major_case(h, d, t_q, t_k, dtype,
                                                      batch=2))
    cols = h * d

    def planted(x):
        big = jnp.full((2, 64, 256), jnp.nan, dtype)
        return big.at[:, :x.shape[1], :cols].set(x)

    exact = flash_causal_columns(q, k, v, heads=h, block_q=16, block_k=16)
    out = _lane_row_attention(planted(q), planted(k), planted(v), d=d,
                              t_q=t_q, t_k=t_k, cols=cols, block_q=16,
                              block_k=16, interpret=True)[:, :t_q, :cols]
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(exact, np.float32))


@pytest.mark.parametrize("h,hkv,d,window,heads_a_block,name", [
    (4, 4, 64, None, 2, "flash_causal"),    # GPT-2's: two a lane row
    (3, 3, 32, None, 4, "flash_causal"),
    (2, 2, 128, None, 1, "flash_causal"),   # OLMoE's: a head a block
    (4, 2, 64, None, 1, "flash_grouped"),   # LFM2's: out of scope, kept
    (4, 4, 64, 24, 1, "flash_band"),
    (2, 2, 96, None, 1, "flash_causal"),    # no whole fraction of 128
], ids=["64", "32", "128", "grouped-64", "window-64", "96"])
def test_one_entry_takes_the_block_its_shapes_name(h, hkv, d, window,
                                                   heads_a_block, name):
    """No argument chooses the path: the same public call holds a lane
    row of heads a block where full, unwindowed heads are a whole
    fraction of 128 wide, and a head a block everywhere else — and is
    then ``flash_attention(causal=True)`` on the same operands laid
    head-major.  The Pallas call keeps its name by kind."""
    from defer_tpu.obs.registry import REGISTRY
    from defer_tpu.ops.flash_attention import flash_causal_columns
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (2, h, 72, d))
    k = jax.random.normal(ks[1], (2, hkv, 72, d))
    v = jax.random.normal(ks[2], (2, hkv, 72, d))

    def call(q, k, v):
        return flash_causal_columns(q, k, v, heads=h, kv_heads=hkv,
                                    window=window, block_q=32, block_k=32)
    cols = tuple(_columns(a) for a in (q, k, v))
    assert _kernel_names(jax.make_jaxpr(call)(*cols).jaxpr) == [name]
    out = call(*cols)
    assert REGISTRY.gauge("prefill.flash.heads_a_block").value \
        == heads_a_block
    pairs = REGISTRY.gauge("prefill.flash.grid_steps").value
    head_major = flash_attention(q, k, v, causal=True, window=window,
                                 block_q=32, block_k=32)
    assert REGISTRY.gauge("prefill.flash.heads_a_block").value == 1
    # a lane row's block is its heads' blocks: that many fewer steps
    assert REGISTRY.gauge("prefill.flash.grid_steps").value \
        == pairs * h // -(-h // heads_a_block)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_columns(head_major)),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,hidden", [(2, 32), (3, 192), (4, 256)],
                         ids=["gpt_tiny", "3-heads-of-64", "4-heads-of-64"])
def test_a_gpt_block_hands_its_columns_over_as_they_lie(heads, hidden):
    """``apply_with_kv`` of a GPT block under ``"flash"`` — the
    projection's columns token-major into ``flash_causal``, no head
    split in the program — against ``"xla"``: the same output, and the
    K and V columns it returns to the cache bit for bit."""
    from defer_tpu.graph.ir import ShapeSpec
    from defer_tpu.models.gpt import CausalTransformerBlock
    flash = CausalTransformerBlock(heads, attn_impl="flash")
    xla = CausalTransformerBlock(heads, attn_impl="xla")
    p = flash.init(jax.random.key(0), (ShapeSpec((40, hidden)),))
    x = jax.random.normal(jax.random.key(2), (2, 40, hidden))
    jaxpr = jax.make_jaxpr(flash.apply_with_kv)(p, x)
    assert _kernel_names(jaxpr.jaxpr) == ["flash_causal"]
    assert "transpose" not in {e.primitive.name for e in jaxpr.jaxpr.eqns}
    (y, k, v), (y_ref, k_ref, v_ref) = (
        op.apply_with_kv(p, x) for op in (flash, xla))
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))


#: sha256 of ``flash_attention.lower(q, k, k).as_text()`` (non-causal)
#: on the tree before full-head causal calls left ``_attn_kernel``
#: (PR 57's): operand shapes and type, the text's hash
_PARENT_NON_CAUSAL = [
    ((2, 3, 37, 16), (2, 3, 53, 16), jnp.float32,
     "f47629e2a1a875084ac654321fa6561087b6ac6929a20680dff5af7c2b9efca2"),
    ((1, 2, 200, 64), (1, 2, 200, 64), jnp.bfloat16,
     "fa5e15fdf7ee5f328e3a9e348db5929107dbe1ef374e22ff307f880fea9088b1"),
]


@pytest.mark.parametrize("q_shape,k_shape,dtype,sha", _PARENT_NON_CAUSAL,
                         ids=["f32-ragged", "bf16-heads-of-64"])
def test_the_non_causal_call_is_the_parent_s_program(q_shape, k_shape,
                                                     dtype, sha):
    """``_attn_kernel`` lost its causal branch and nothing else: the
    non-causal call lowers to the text it lowered to before, so it
    returns what it returned, bit for bit."""
    import hashlib
    q = jax.ShapeDtypeStruct(q_shape, dtype)
    k = jax.ShapeDtypeStruct(k_shape, dtype)
    text = flash_attention.lower(q, k, k).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == sha


# (t_q, t_k, block_q, block_k, window) of the pair list's cases
_PAIR_CASES = [
    (512, 512, 16, 16, 64),         # 4 blocks of window over 32 of keys
    (512, 512, 16, 16, None),
    (8192, 8192, 512, 512, 4096),   # the cells' window layers
    (8192, 8192, 512, 512, None),   # their full layers, and Kimi's
    (16, 528, 16, 16, 64),          # decode-aligned
    (50, 50, 8, 8, 16),             # ragged
    (70, 70, 16, 16, 24),           # a window that is no multiple
    (24, 700, 512, 512, 600),       # decode-aligned, one query block
    (40, 40, 16, 8, None),          # unlike blocks
]


def _allowed(t_q, t_k, window):
    """The mask itself: [t_q, t_k] booleans."""
    r = np.arange(t_q)[:, None] + (t_k - t_q)
    s = np.arange(t_k)[None, :]
    seen = s <= r
    if window is not None:
        seen &= r - s < window
    return seen


@pytest.mark.parametrize("case,count", zip(_PAIR_CASES[:5],
                                           [150, 528, 108, 136, 5]))
def test_pair_list_counts_the_band_s_blocks(case, count):
    """The grid walks the band's blocks, not a rectangle over them: at
    the cells' size 136 pairs a head of 256 in a full layer, 108 in a
    window layer (the old key axis, the widest band's, made 144)."""
    from defer_tpu.ops.flash_attention import live_pairs
    assert live_pairs(*case).shape == (3, count)


@pytest.mark.parametrize("case", _PAIR_CASES)
def test_pair_list_flags_fall_once_a_query_block(case):
    """Query-block-major, key blocks ascending; the first and the last
    flag fall once a query block each, on its first and last pair."""
    from defer_tpu.ops.flash_attention import _FIRST, _LAST, live_pairs
    qi, kb, bits = live_pairs(*case)
    blocks = -(-case[0] // case[2])
    assert sorted(set(qi)) == list(range(blocks))
    assert np.all(np.diff(qi) >= 0)
    for b in range(blocks):
        mine = qi == b
        assert np.all(np.diff(kb[mine]) == 1)
        assert list(bits[mine] & _FIRST) == [_FIRST] + [0] * (mine.sum() - 1)
        assert list(bits[mine] & _LAST) == [0] * (mine.sum() - 1) + [_LAST]


@pytest.mark.parametrize("case", [c for c in _PAIR_CASES if c[0] < 8192])
def test_pair_list_is_the_mask_s_blocks(case):
    """Against the mask: every listed pair holds an allowed (query,
    key), no allowed one lies in an unlisted pair, and a pair is an
    edge exactly when it holds a forbidden one too (padding counted)."""
    from defer_tpu.ops.flash_attention import _EDGE, live_pairs
    t_q, t_k, bq, bk, window = case
    nq, nk = -(-t_q // bq), -(-t_k // bk)
    seen = np.zeros((nq * bq, nk * bk), bool)
    seen[:t_q, :t_k] = _allowed(t_q, t_k, window)
    # a padded query row is no query: it forbids nothing
    full = seen.copy()
    full[t_q:] = True
    some = seen.reshape(nq, bq, nk, bk).any(axis=(1, 3))
    every = full.reshape(nq, bq, nk, bk).all(axis=(1, 3))
    qi, kb, bits = live_pairs(*case)
    listed = np.zeros_like(some)
    listed[qi, kb] = True
    assert np.array_equal(listed, some)
    # an edge where the block forbids something; a block marked an edge
    # that forbids nothing (only through padded rows) is merely masked
    assert np.all((bits & _EDGE != 0)[~every[qi, kb]])


def test_pair_list_of_queries_ahead_of_every_key():
    """t_q > t_k: the leading query blocks see no key; each is listed
    once, as an edge, so its rows are written (zeros)."""
    from defer_tpu.ops.flash_attention import _EDGE, live_pairs
    qi, kb, bits = live_pairs(32, 8, 8, 8, None)
    assert list(qi) == [0, 1, 2, 3] and list(kb) == [0, 0, 0, 0]
    assert np.all(bits & _EDGE != 0)
    q = jax.random.normal(jax.random.key(0), (1, 2, 32, 8))
    k = jax.random.normal(jax.random.key(1), (1, 1, 8, 8))
    out = flash_attention(q, k, k, causal=True, block_q=8, block_k=8)
    assert np.all(np.asarray(out[:, :, :24]) == 0)
    ref = _banded_reference(q[:, :, 24:], k, k, None)
    np.testing.assert_allclose(np.asarray(out[:, :, 24:]), np.asarray(ref),
                               atol=TOL, rtol=TOL)


def test_flash_gauges_count_the_steps_that_work():
    """``prefill.flash.grid_steps`` / ``.live_steps`` of the newest
    traced call: alike wherever every query has a key."""
    from defer_tpu.obs.registry import REGISTRY
    q = jnp.zeros((2, 4, 70, 8))
    k = jnp.zeros((2, 2, 70, 8))
    flash_attention(q, k, k, causal=True, window=24, block_q=16,
                    block_k=16)
    steps = REGISTRY.gauge("prefill.flash.grid_steps").value
    assert steps == 2 * 4 * 12
    assert REGISTRY.gauge("prefill.flash.live_steps").value == steps
    # 40 queries on 16 keys: three query blocks of five ahead of every key
    flash_attention(jnp.zeros((1, 2, 40, 8)), jnp.zeros((1, 1, 16, 8)),
                    jnp.zeros((1, 1, 16, 8)), causal=True, block_q=8,
                    block_k=8)
    assert REGISTRY.gauge("prefill.flash.grid_steps").value == 2 * 6
    assert REGISTRY.gauge("prefill.flash.live_steps").value == 2 * 3
    # full heads at the path's own blocks: 896 rows are two blocks of
    # 512, three pairs a head
    flash_attention(jnp.zeros((1, 2, 896, 8)), jnp.zeros((1, 2, 896, 8)),
                    jnp.zeros((1, 2, 896, 8)), causal=True)
    assert REGISTRY.gauge("prefill.flash.grid_steps").value == 2 * 3
    assert REGISTRY.gauge("prefill.flash.live_steps").value == 2 * 3
    assert REGISTRY.gauge("prefill.flash.heads_a_block").value == 1
    # the same prompt token-major on GPT-2's 25 heads of 64: 13 lane
    # rows of two heads, two query blocks of 448 on key blocks of 512
    from defer_tpu.ops.flash_attention import flash_causal_columns
    cols = jnp.zeros((1, 896, 1600))
    flash_causal_columns(cols, cols, cols, heads=25)
    assert REGISTRY.gauge("prefill.flash.grid_steps").value == 13 * 3
    assert REGISTRY.gauge("prefill.flash.live_steps").value == 13 * 3
    assert REGISTRY.gauge("prefill.flash.heads_a_block").value == 2


def test_band_kernel_refuses_what_it_is_not():
    q = jnp.zeros((1, 4, 8, 8))
    k = jnp.zeros((1, 3, 8, 8))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, k, causal=True)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q[:, :2], q[:, :2], causal=False)
