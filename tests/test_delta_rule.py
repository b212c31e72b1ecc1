"""``ops/delta_rule.py``: the delta-rule state's format against the
recurrence token by token — the step kernel (interpreter mode), the
chunked (WY) prefill, the bubble, the layout and the gauges."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.ops import conv_window, delta_rule, layered

H, D = 4, 16


def draw(seed, b, t, h=H, d=D, *, fastest=0.0):
    """Seeded operands of ``t`` positions as the layer makes them: unit
    ``k``, ``q`` a unit vector over ``sqrt(d)``, log-decays from
    ``-exp(-6)`` to ``-exp(fastest)`` a channel, ``beta`` in (0, 2)."""
    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(jax.random.normal(ks[0], (b, t, h, d))) / np.sqrt(d)
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, d), minval=-6.0,
                                    maxval=fastest))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def flat(a):
    return a.reshape(a.shape[:-2] + (-1,))


def fmt_of(chunk=8, groups=2, h=H, d=D, dtype=jnp.float32):
    return delta_rule.DeltaFormat(h, d, 4, chunk, dtype, groups=groups)


def prefilled(fmt, ops, b, group=1):
    q, k, v, g, beta = ops
    layer = fmt.layer(fmt.zeros(b, 1), 0)
    return fmt.prefill(flat(q), flat(k), flat(v), flat(g), beta, layer,
                       fmt.prefill_slot(True, group))


# -- the chunked prefill is the recurrence ---------------------------------------

@pytest.mark.parametrize("t, chunk", [
    (16, 8),        # chunks that end at the prompt's end
    (21, 8),        # the last chunk ends inside its positions
    (5, 8),         # a prompt shorter than a chunk
    (64, 64), (37, 16), (1, 8)],
    ids=lambda v: str(v))
def test_the_chunked_prefill_is_the_recurrence(t, chunk):
    ops = draw(t, 3, t)
    want_o, want_s = delta_rule.prefill_reference(*ops)
    fmt = fmt_of(chunk)
    o, layer = prefilled(fmt, ops, 3)
    assert o.shape == (3, t, H * D) and o.dtype == jnp.float32
    np.testing.assert_allclose(o.reshape(want_o.shape), want_o, atol=2e-6)
    np.testing.assert_allclose(delta_rule.dense(layer["S"][1], H), want_s,
                               atol=5e-6)
    assert not np.asarray(layer["S"][0]).any()      # the other group


def test_a_fast_channel_overflows_exp_minus_g_and_not_the_chunk():
    """Log-decays down to ``-e^1.5`` a position: inside a chunk of 64
    the running sum passes -250 and ``exp(-G)`` alone is ``inf`` in
    float32; the chunked form takes differences first and is the
    recurrence still."""
    ops = draw(2, 2, 96, fastest=1.5)
    # one channel a head at the fastest rate throughout
    g = ops[3].at[..., 0].set(-np.exp(1.5))
    ops = ops[:3] + (g,) + ops[4:]
    with np.errstate(over="ignore"):
        assert np.isinf(np.exp(-np.cumsum(np.asarray(g[:, :64]), axis=1),
                               dtype=np.float32)).any()
    want_o, want_s = delta_rule.prefill_reference(*ops)
    o, layer = prefilled(fmt_of(64), ops, 2)
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o.reshape(want_o.shape), want_o, atol=5e-6)
    np.testing.assert_allclose(delta_rule.dense(layer["S"][1], H), want_s,
                               atol=2e-5)


def test_the_write_reads_the_state():
    """What parts the delta rule from a decay-and-add recurrence: fed
    the same key twice at ``beta`` 1 under no decay the state holds the
    *second* value there, not the sum."""
    k = jnp.zeros((1, 2, 1, 8)).at[..., 0].set(1.0)
    v = jnp.stack([jnp.full((1, 1, 8), 3.0), jnp.full((1, 1, 8), 5.0)], 1)
    zero, one = jnp.zeros((1, 2, 1, 8)), jnp.ones((1, 2, 1))
    o, s = delta_rule.prefill_reference(k, k, v, zero, one)
    np.testing.assert_allclose(s[0, 0, 0], 5.0)
    np.testing.assert_allclose(o[0, 1, 0], 5.0)
    fmt = delta_rule.DeltaFormat(1, 8, 4, 8, jnp.float32)
    got, layer = fmt.prefill(flat(k), flat(k), flat(v), flat(zero), one,
                             fmt.layer(fmt.zeros(1, 1), 0))
    np.testing.assert_allclose(got[0, 1], 5.0, atol=1e-6)
    np.testing.assert_allclose(delta_rule.dense(layer["S"], 1)[0, 0, 0],
                               5.0, atol=1e-6)


# -- the step kernel ------------------------------------------------------------

@pytest.mark.parametrize("h, d, groups", [(4, 16, 2), (64, 128, 1),
                                          (2, 8, None), (8, 16, 3)])
def test_the_step_is_the_recurrences(h, d, groups):
    """Prefill, then steps one token at a time: outputs and states are
    the recurrence's over the whole text — at the published shape (64
    heads of 128: two value channels a lane row) and at shapes whose
    heads fill no lane row."""
    b, t, plen = 2, 6, 3
    ops = draw(h * d, b, t, h, d)
    want_o, want_s = delta_rule.prefill_reference(*ops)
    fmt = fmt_of(4, groups, h, d)
    group = None if groups is None else groups - 1
    head = tuple(a[:, :plen] for a in ops)
    layer = fmt.layer(fmt.zeros(b, 1), 0)
    _, layer = fmt.prefill(*(flat(a) for a in head[:4]), head[4], layer,
                           fmt.prefill_slot(True, group))
    for pos in range(plen, t):
        q, k, v, g, beta = (a[:, pos] for a in ops)
        o, layer = fmt.step(flat(q), flat(k), flat(v), flat(g), beta, layer,
                            group=group, valid=True)
        assert o.shape == (b, h * d) and o.dtype == jnp.float32
        np.testing.assert_allclose(o.reshape(b, h, d), want_o[:, pos],
                                   atol=5e-6)
    s = layer["S"] if groups is None else layer["S"][group]
    np.testing.assert_allclose(delta_rule.dense(s, h), want_s, atol=5e-6)


def test_a_step_of_the_format_is_step_reference():
    b = 3
    q, k, v, g, beta = (a[:, 0] for a in draw(5, b, 1))
    s0 = delta_rule.prefill_reference(*draw(6, b, 9))[1]
    want_o, want_s = delta_rule.step_reference(q, k, v, g, beta, s0)
    fmt = fmt_of(groups=None)
    # the buffer as the format lays a dense state
    rows, lanes = delta_rule.fold(H, D)
    layer = {"conv": fmt.zeros(b, 1)["conv"][0],
             "S": s0.transpose(0, 2, 3, 1).reshape(b, D, rows, lanes)}
    np.testing.assert_array_equal(delta_rule.dense(layer["S"], H), s0)
    o, layer = fmt.step(flat(q), flat(k), flat(v), flat(g), beta, layer)
    np.testing.assert_allclose(o.reshape(want_o.shape), want_o, atol=2e-6)
    np.testing.assert_allclose(delta_rule.dense(layer["S"], H), want_s,
                               atol=2e-6)


def test_a_bubble_leaves_the_state_and_the_window_bit_for_bit():
    b = 2
    fmt = fmt_of(groups=1)
    ops = draw(7, b, 10)
    _, layer = prefilled(fmt, ops, b, group=0)
    u = jnp.asarray(np.random.default_rng(1).normal(size=(b, 10, 3 * H * D)),
                    jnp.float32)
    _, layer = fmt.prefill_shift(u, layer, fmt.prefill_slot(True, 0))
    assert np.asarray(layer["S"]).any() and np.asarray(layer["conv"]).any()
    q, k, v, g, beta = (a[:, 0] for a in draw(8, b, 1))
    _, after = fmt.shift(u[:, 0], layer, group=0,
                         valid=fmt.decode_slot(False, 0))
    _, after = fmt.step(flat(q), flat(k), flat(v), flat(g), beta, after,
                        group=0, valid=fmt.decode_slot(False, 0))
    _, after = fmt.prefill(*(flat(a) for a in ops[:4]), ops[4], after,
                           fmt.prefill_slot(False, 0))
    _, after = fmt.prefill_shift(u, after, fmt.prefill_slot(False, 0))
    for key in ("S", "conv"):
        assert np.asarray(after[key]).tobytes() \
            == np.asarray(layer[key]).tobytes(), key
    # and a real step moves both
    _, moved = fmt.step(flat(q), flat(k), flat(v), flat(g), beta, layer,
                        group=0, valid=True)
    assert not np.array_equal(moved["S"], layer["S"])


def test_a_piece_of_a_prefill_lands_from_its_row_on():
    """Two sequences of a group of five, from row 2: the other rows
    keep what they held."""
    fmt = fmt_of(groups=2)
    ops = draw(9, 2, 7)
    layer = fmt.layer(fmt.zeros(5, 1), 0)
    _, layer = fmt.prefill(*(flat(a) for a in ops[:4]), ops[4], layer,
                           fmt.prefill_slot(True, 1, 2))
    want = delta_rule.prefill_reference(*ops)[1]
    got = delta_rule.dense(layer["S"][1], H)
    np.testing.assert_allclose(got[2:4], want, atol=5e-6)
    assert not got[:2].any() and not got[4:].any()
    assert not np.asarray(layer["S"][0]).any()


# -- the format ---------------------------------------------------------------------

def test_the_formats_buffers_gauges_and_bytes():
    fmt = delta_rule.DeltaFormat(64, 128, 4, 64, jnp.bfloat16, groups=1)
    assert isinstance(fmt, conv_window.Window)
    assert fmt.keys == ("conv", "S") and fmt.conv_width == 24576
    bufs = fmt.buffers(192)
    assert list(bufs) == ["conv", "S"]
    # taps lead; key channel outermost, two value channels a lane row
    assert bufs["conv"].shape == (1, 3, 192, 24576)
    assert bufs["conv"].dtype == jnp.bfloat16
    assert bufs["S"].shape == (1, 192, 128, 64, 128)
    assert bufs["S"].dtype == jnp.float32
    assert delta_rule.fold(64, 128) == (64, 128)
    # the state's own bytes: nothing is padded
    state, window = 192 * 64 * 128 * 128 * 4, 3 * 192 * 24576 * 2
    assert fmt.state_bytes(192, 3) == 3 * (state + window)
    assert fmt.gauges(192, 1) == {"decode.delta.state_bytes": state,
                                  "decode.delta.window_bytes": window}
    assert fmt.rows_read(5, 9) == {}
    # heads that fill no lane row lie a value channel a row
    assert delta_rule.fold(4, 16) == (16, 4)
    small = fmt_of(groups=None)
    assert small.buffers(5)["S"].shape == (5, 16, 16, 4)
    assert small.buffers(5)["conv"].shape == (3, 5, 192)
    # a scratch-free memory: a slot is "is the step real" and no address
    assert fmt.decode_slot(True, 7) is True
    assert fmt.prefill_slot(False, 1) == (1, False)
    assert fmt.prefill_slot(True, 1, 4) == (1, True, 4)


def test_a_state_of_unlike_layers_lies_side_by_side():
    from defer_tpu.ops import kv_cache, ssm
    fmts = (kv_cache.KVCacheFormat(2, 16, 12, jnp.float32, groups=2),
            fmt_of(groups=2),
            ssm.SsmFormat(128, 8, 4, jnp.float32, groups=2))
    shapes = layered.shapes_by_layer(fmts, 3)
    assert list(shapes) == ["k", "v", "conv", "S", "h"]
    assert [s is None for s in shapes["S"]] == [True, False, True]
    assert [s is None for s in shapes["conv"]] == [True, False, False]
    assert shapes["conv"][1].shape == (2, 3, 3, 192)
    assert layered.totals(fmts, lambda f: f.gauges(3, 2)).keys() >= {
        "decode.delta.state_bytes", "decode.delta.window_bytes",
        "decode.ssm.conv_bytes"}


def test_dense_unpacks_what_the_format_lays():
    rng = np.random.default_rng(0)
    s = rng.normal(size=(2, 64, 128, 128)).astype(np.float32)    # b h k v
    laid = s.transpose(0, 2, 3, 1).reshape(2, 128, 64, 128)
    np.testing.assert_array_equal(delta_rule.dense(laid, 64), s)
    # lane j of row r of key channel c: value 2 r + j // 64, head j % 64
    assert laid[1, 5, 7, 70] == s[1, 6, 5, 15]


# -- the kernel under the TPU's compiler -----------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_step_compiles_for_the_v5e_in_place(one_chip, monkeypatch):
    """At the published shape and the cell's 192 sequences the TPU's
    compiler takes the kernel, the state's result aliases its argument
    and nothing of a state's size is a temporary."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        fmt = delta_rule.DeltaFormat(64, 128, 4, 64, jnp.bfloat16, groups=1)
        b, e = 192, 64 * 128

        def arg(shape, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        layer = {k: arg(s.shape, s.dtype)
                 for k, s in fmt.buffers(b).items()}
        compiled = jax.jit(
            lambda q, k, v, g, beta, layer: fmt.step(
                q, k, v, g, beta, layer, group=0, valid=True),
            donate_argnums=5).lower(
                arg((b, e)), arg((b, e)), arg((b, e)), arg((b, e)),
                arg((b, 64)), layer).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
        compilation_cache.reset_cache()
    text = compiled.as_text()
    assert len(__import__("re").findall(r"%delta_step(?:\.\d+)? = ",
                                        text)) == 1
    m = compiled.memory_analysis()
    state = 192 * 64 * 128 * 128 * 4
    assert m.alias_size_in_bytes >= state
    assert m.temp_size_in_bytes < state // 100
