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
], ids=["causal", "under", "at", "over-ragged", "odd-window", "window1"])
def test_band_kernel_matches_the_masked_softmax(h, hkv, t, window, block):
    if h == hkv and window is None:
        pytest.skip("the default path: tested above")
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


def test_band_kernel_skips_blocks_outside_the_band():
    """The key axis of the grid holds the band's blocks, not the
    prompt's: at 4 blocks of window over 32 of keys, 5 steps a query
    block and not 32; at the cell's size 9 of 16 in a window layer."""
    from defer_tpu.ops.flash_attention import band_key_steps
    assert band_key_steps(512, 512, 16, 16, 64) == 5
    assert band_key_steps(512, 512, 16, 16, None) == 32
    assert band_key_steps(8192, 8192, 512, 512, 4096) == 9
    assert band_key_steps(8192, 8192, 512, 512, None) == 16
    assert band_key_steps(16, 528, 16, 16, 64) == 5      # decode-aligned


def test_band_kernel_refuses_what_it_is_not():
    q = jnp.zeros((1, 4, 8, 8))
    k = jnp.zeros((1, 3, 8, 8))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k, k, causal=True)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q[:, :2], q[:, :2], causal=False)
