"""The retention state's format (``defer_tpu/ops/retention.py``) against
its plain oracle and against the attention form, on the CPU (the kernel
in interpreter mode).

Tolerances.  Everything here is float32.  The state ``S`` is a sum of
products and agrees to rounding: ``S_TOL`` 2e-5 of its largest entry
over 2048 positions (measured 2e-6).  The output ``y`` divides two such
sums, and the recurrent form computes ``(q.k)^2`` as ``phi(q).phi(k)``,
a sum of ``D`` products that nearly cancel when ``q.k`` is small: where
the weights of a position sum to 1e-3 (the first few positions of a
sequence) the quotient carries 1e-4 of error whatever the order of the
sums, so ``y`` is compared from position ``SETTLED`` on at ``Y_TOL``
1e-4 of its largest (measured 3e-6) and before it at 2e-3.  A state
held in bfloat16 misses ``S_TOL`` by two orders (asserted below).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.ops import retention as R

KV, G, D_HEAD = 2, 3, 16
S_TOL, Y_TOL, SETTLED = 2e-5, 1e-4, 8


def _inputs(b, t, seed=0, d=D_HEAD, kv=KV, g=G):
    rng = np.random.default_rng(seed)
    f32 = jnp.float32
    return (jnp.asarray(rng.normal(size=(b, t, kv * g * d)), f32),
            jnp.asarray(rng.normal(size=(b, t, kv * d)), f32),
            jnp.asarray(rng.normal(size=(b, t, kv * d)), f32),
            # the long-memory case: decays near 1
            jnp.asarray(-rng.uniform(0.0, 0.1, size=(b, t, kv)), f32))


def _rel(got, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(got) - want).max() / np.abs(want).max())


def _steps(fmt, q, k, v, lg, layer, cast=None):
    """Every position through :meth:`RetentionFormat.step`, in one
    compiled scan: ``(y [b, t, ..], layer)``."""
    def body(layer, xs):
        y, layer = fmt.step(*xs, layer)
        if cast is not None:        # a state held in a narrower type
            layer = jax.tree.map(
                lambda a: a.astype(cast).astype(jnp.float32), layer)
        return layer, y

    layer, ys = jax.jit(lambda layer, *xs: jax.lax.scan(
        body, layer, tuple(a.swapaxes(0, 1) for a in xs)))(
            layer, q, k, v, lg)
    return ys.swapaxes(0, 1), layer


@pytest.fixture(scope="module")
def long_run():
    """2048 positions with log-decays in [-0.1, 0): the oracle's
    attention form and explicit state, the kernel step by step, and the
    prefill in four chunks of 512 (the module's own chunk, 2048, would
    hold the whole run: the state between chunks would not be read)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(R, "CHUNK", 512)
        yield _long_run()


def _long_run():
    fmt = R.RetentionFormat(KV, D_HEAD)
    q, k, v, lg = _inputs(1, 2048)
    heads = fmt._heads(q, k, v, lg)
    want_y, want = jax.jit(R.prefill_reference)(*heads)
    empty = fmt.layer(fmt.zeros(1, 1), 0)
    step_y, step_layer = _steps(fmt, q, k, v, lg, empty)
    pre_y, pre_layer = jax.jit(fmt.prefill)(q, k, v, lg, empty)
    return dict(fmt=fmt, inputs=(q, k, v, lg), want_y=want_y.reshape(
        1, 2048, -1), want=want, step=(step_y, step_layer),
        prefill=(pre_y, pre_layer))


@pytest.mark.parametrize("path", ["step", "prefill"])
def test_both_paths_match_the_attention_form_over_2048_positions(long_run,
                                                                 path):
    y, layer = long_run[path]
    want_y, want = long_run["want_y"], long_run["want"]
    assert _rel(layer["S"], want["S"]) < S_TOL
    assert _rel(layer["z"], want["z"]) < S_TOL
    assert _rel(y[:, SETTLED:], want_y[:, SETTLED:]) < Y_TOL
    assert _rel(y[:, :SETTLED], want_y[:, :SETTLED]) < 2e-3


def test_the_chunked_state_equals_the_step_by_step_state(long_run):
    (_, by_step), (_, by_chunk) = long_run["step"], long_run["prefill"]
    assert _rel(by_chunk["S"], by_step["S"]) < S_TOL
    assert _rel(by_chunk["z"], by_step["z"]) < S_TOL


def test_a_state_held_in_bfloat16_fails_the_tolerance(long_run):
    """The tolerance above tells a narrower state apart: rounded to
    bfloat16 after every step, the state of the same run misses it by
    two orders of magnitude."""
    fmt, (q, k, v, lg) = long_run["fmt"], long_run["inputs"]
    _, layer = _steps(fmt, q[:, :512], k[:, :512], v[:, :512], lg[:, :512],
                      fmt.layer(fmt.zeros(1, 1), 0), cast=jnp.bfloat16)
    _, want = jax.jit(R.prefill_reference)(*fmt._heads(
        q[:, :512], k[:, :512], v[:, :512], lg[:, :512]))
    assert _rel(layer["S"], want["S"]) > 100 * S_TOL


def test_the_kernel_is_the_oracles_step():
    """One step from a state that is not empty, at a head of 128 (the
    width the chip runs) and at 16: kernel against ``step_reference``."""
    for d, g in ((128, 5), (16, 3)):
        fmt = R.RetentionFormat(1, d)
        rng = np.random.default_rng(d)
        item = {key: jnp.asarray(rng.normal(size=s.shape), jnp.float32)
                for key, s in fmt.buffers(2).items()}
        q, k, v, lg = _inputs(2, 1, seed=d, d=d, kv=1, g=g)
        y, layer = jax.jit(fmt.step)(q[:, 0], k[:, 0], v[:, 0], lg[:, 0],
                                     item)
        want_y, want = R.step_reference(*(a[:, 0] for a in fmt._heads(
            q, k, v, lg)), item)
        assert _rel(layer["S"], want["S"]) < 1e-6
        assert _rel(layer["z"], want["z"]) < 1e-6
        assert _rel(y, want_y.reshape(2, -1)) < 1e-4


def test_a_bubble_changes_nothing_and_groups_keep_apart():
    """With groups the step touches its own group alone; with ``valid``
    false it touches nothing (the identity update), as does a prefill's
    bubble."""
    fmt = R.RetentionFormat(KV, D_HEAD, groups=3)
    assert R.RetentionFormat.decode_slot(False, 7) is False
    q, k, v, lg = _inputs(2, 4, seed=3)
    rng = np.random.default_rng(9)
    layer = {key: jnp.asarray(rng.normal(size=s.shape), jnp.float32)
             for key, s in fmt.buffers(2).items()}
    _, after = jax.jit(fmt.step)(q[:, 0], k[:, 0], v[:, 0], lg[:, 0], layer,
                                 jnp.int32(1), True)
    for key in ("S", "z"):
        changed = np.abs(np.asarray(after[key] - layer[key])).reshape(
            3, -1).max(1)
        assert changed[0] == 0 and changed[2] == 0 and changed[1] > 0
    _, same = jax.jit(fmt.step)(q[:, 0], k[:, 0], v[:, 0], lg[:, 0], layer,
                                jnp.int32(1), False)
    _, same2 = jax.jit(fmt.prefill)(q, k, v, lg, layer,
                                    fmt.prefill_slot(False, jnp.int32(2)))
    for key in ("S", "z"):
        np.testing.assert_array_equal(same[key], layer[key])
        np.testing.assert_array_equal(same2[key], layer[key])


def test_the_tiled_power_is_a_symmetric_power():
    """``phi(q, query=True) . phi(k) = (q.k)^2``; the state has
    ``(d/8)(d/8+1)/2 * 64`` rows (8704 at 128, where the untiled power
    has 8256 and the outer product 16384); ``dense`` unpacks it to the
    symmetric ``[d, d]`` form."""
    assert R.state_rows(128) == 8704 and R.state_rows(16) == 192
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(5, 32)), jnp.float32)
            for _ in range(2))
    np.testing.assert_allclose(
        (R.phi(q, query=True) * R.phi(k)).sum(-1), (q * k).sum(-1) ** 2,
        rtol=1e-5)
    full = R.dense(np.asarray(R.phi(k)), axis=-1)
    np.testing.assert_allclose(
        full, np.asarray(k)[:, :, None] * np.asarray(k)[:, None, :],
        rtol=1e-6)
    with pytest.raises(ValueError, match="no retention state"):
        R.dense(np.zeros((100, 4)))


def test_the_state_is_float32_with_no_scratch_and_counts_its_bytes():
    fmt = R.RetentionFormat(8, 128, groups=1)
    bufs = fmt.buffers(16)
    assert bufs["S"].shape == (1, 16, 8, 8704, 128)     # no scratch group
    assert bufs["z"].shape == (1, 16, 8, 8704)
    assert all(b.dtype == jnp.float32 for b in bufs.values())
    assert fmt.state_bytes(16, 8) == 8 * 16 * 8 * 8704 * 129 * 4
    assert not hasattr(fmt, "scratch_position")
    assert not hasattr(fmt, "reparent")
