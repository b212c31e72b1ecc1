"""The KV cache's format (``defer_tpu/ops/kv_cache.py``): its buffers,
its three writes, its attention — and that nothing outside it knows the
layout: both decode engines give the same tokens over a format that
keeps the same rows in another axis order.
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu.models import gpt_tiny
from defer_tpu.ops import kv_cache
from defer_tpu.ops.kv_cache import (KVCacheFormat, attend_blocks,
                                    attend_einsum, live_slots, quantize_rows,
                                    write_kv_rows)
from defer_tpu.runtime.decode import PipelinedDecoder
from defer_tpu.serve.engine import ContinuousBatchEngine, DecodeRequest

KV, HD, L = 2, 8, 9


def _fmt(quantized=False, groups=None):
    return KVCacheFormat(KV, HD, L, jnp.float32, quantized=quantized,
                         groups=groups)


def _random_layer(fmt, batch, rng):
    """One layer's buffers filled with noise (int8 rows, small scales)."""
    out = {}
    for key, s in fmt.buffers(batch).items():
        if s.dtype == jnp.int8:
            out[key] = jnp.asarray(rng.integers(-127, 128, s.shape), jnp.int8)
        elif len(key) == 2:     # a scale
            out[key] = jnp.asarray(rng.uniform(0.01, 0.1, s.shape),
                                   jnp.float32)
        else:
            out[key] = jnp.asarray(rng.standard_normal(s.shape), jnp.float32)
    return out


def _dequantized(item):
    """[b, kv, L, hd] float keys and values of an item without groups."""
    k, v = (np.asarray(item[key], np.float32) for key in ("k", "v"))
    if "ks" in item:
        k = k * np.asarray(item["ks"])[..., None]
        v = v * np.asarray(item["vs"])[..., None]
    return k, v


# -- rows and buffers ---------------------------------------------------------

def test_quantize_row_roundtrip():
    rng = np.random.default_rng(0)
    row = jnp.asarray(rng.standard_normal((3, 2, 7, 16)) * 5)
    q, s = quantize_rows(row)
    assert q.dtype == jnp.int8 and s.shape == (3, 2, 7)
    dq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    err = np.abs(dq - np.asarray(row))
    bound = np.abs(np.asarray(row)).max(-1) / 127.0 * 0.5 + 1e-7
    assert (err <= bound[..., None] + 1e-5).all()


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
@pytest.mark.parametrize("groups", [None, 3], ids=["slots", "groups"])
def test_buffers_and_state_of_both_holders(quantized, groups):
    """Slots alone: exactly the positions.  With groups: a scratch group
    and a scratch row more.  A state is one buffer a layer under each
    key, behind the holder's own axes."""
    fmt = _fmt(quantized, groups)
    bufs = fmt.buffers(5)
    assert tuple(bufs) == fmt.keys
    assert fmt.keys == (("k", "v", "ks", "vs") if quantized else ("k", "v"))
    lead, length = ((), L) if groups is None else ((groups + 1,), L + 1)
    for key in ("k", "v"):
        assert bufs[key].shape == lead + (5, KV, length, HD)
        assert bufs[key].dtype == (jnp.int8 if quantized else jnp.float32)
    if quantized:
        assert bufs["ks"].shape == bufs["vs"].shape == lead + (5, KV, length)
        assert bufs["ks"].dtype == jnp.float32
    if groups is not None:
        assert (fmt.scratch_group, fmt.scratch_position) == (groups, L)
    state = fmt.zeros(5, 4, lead=(2,))
    state["mine"] = jnp.ones(3)         # a holder's own entry
    for key, s in bufs.items():
        assert [b.shape for b in state[key]] == [(2,) + s.shape] * 4
        assert all(b.dtype == s.dtype and not b.any() for b in state[key])
    layer = fmt.layer(state, 2)
    assert tuple(layer) == fmt.keys
    marked = {key: buf + 1 for key, buf in layer.items()}
    new = fmt.with_layer(state, 2, marked)
    assert new["mine"] is state["mine"]
    for key in fmt.keys:
        assert [int(b.min()) for b in new[key]] == [0, 0, 1, 0]


# -- the three writes ---------------------------------------------------------

@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_write_position_and_attend_are_the_cache_half_of_a_step(quantized):
    """The format half of a block's one-token step: the rows of the new
    columns fit one position of every buffer, land at ``pos`` and touch
    nothing else, and attention over the item is plain softmax attention
    over the (dequantized) live positions (the kernel for float rows,
    the einsums for int8: the format's own field chooses)."""
    fmt, rng = _fmt(quantized), np.random.default_rng(5)
    b, pos, heads = 3, 4, 4             # two query heads a KV head
    layer = _random_layer(fmt, b, rng)
    k_new, v_new = (jnp.asarray(rng.standard_normal((b, KV * HD)),
                                jnp.float32) for _ in range(2))
    rows = fmt.rows(k_new, v_new)
    assert {key: r.shape for key, r in rows.items()} == \
        {key: c.shape[:2] + (1,) + c.shape[3:] for key, c in layer.items()}
    got = fmt.write_position(layer, rows, pos)
    for key, c in layer.items():
        want = c.at[:, :, pos: pos + 1].set(rows[key].astype(c.dtype))
        np.testing.assert_array_equal(np.asarray(got[key]), np.asarray(want))
    if not quantized:
        np.testing.assert_array_equal(
            np.asarray(got["k"][:, :, pos]).reshape(b, KV * HD),
            np.asarray(k_new))

    q = jnp.asarray(rng.standard_normal((b, heads * HD)), jnp.float32)
    y = fmt.attend(q, got, pos)
    k, v = _dequantized(got)
    qh = np.asarray(q).reshape(b, KV, heads // KV, HD)
    att = np.einsum("bkgd,bkld->bkgl", qh, k[:, :, : pos + 1]) / math.sqrt(HD)
    att = np.exp(att - att.max(-1, keepdims=True))
    att /= att.sum(-1, keepdims=True)
    want = np.einsum("bkgl,bkld->bkgd", att, v[:, :, : pos + 1])
    np.testing.assert_allclose(np.asarray(y), want.reshape(b, heads * HD),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_attend_takes_each_sequences_own_position(quantized):
    fmt, rng = _fmt(quantized), np.random.default_rng(6)
    layer = _random_layer(fmt, 3, rng)
    q = jnp.asarray(rng.standard_normal((3, 2 * KV * HD)), jnp.float32)
    pos = jnp.asarray([0, 5, L - 1], jnp.int32)
    got = fmt.attend(q, layer, pos)
    for i, p in enumerate(pos):
        want = fmt.attend(q[i: i + 1],
                          {key: c[i: i + 1] for key, c in layer.items()},
                          int(p))
        np.testing.assert_allclose(np.asarray(got[i: i + 1]),
                                   np.asarray(want), rtol=1e-6, atol=1e-6)


# -- the attention kernel against the einsums it replaces -----------------------

def _attend_case(hd, dtype, g, groups, length, batch=4, kv=2, seed=0):
    """A format of plain rows, one layer of noise (NaN wherever nothing
    may read: the scratch group and the scratch row) and a query."""
    rng = np.random.default_rng(seed)
    fmt = KVCacheFormat(kv, hd, length, dtype, groups=groups)
    assert not fmt.joined
    layer = {key: jnp.asarray(rng.standard_normal(s.shape), dtype)
             for key, s in fmt.buffers(batch).items()}
    if groups is not None:
        layer = {key: buf.at[fmt.scratch_group].set(jnp.nan)
                 .at[:, :, :, fmt.scratch_position].set(jnp.nan)
                 for key, buf in layer.items()}
    q = jnp.asarray(rng.standard_normal((batch, kv * g * hd)), dtype)
    return fmt, layer, q


def _oracle(q, layer, pos, group=None, fmt=None):
    """The kept einsums in f32 over the group's clean item (head-major:
    ``fmt``'s word where it holds the rows otherwise)."""
    item = {key: jnp.nan_to_num((buf if group is None else buf[group])
                                .astype(jnp.float32))
            for key, buf in layer.items()}
    if fmt is not None:
        item = fmt.head_major(item)
    return np.asarray(attend_einsum(q.astype(jnp.float32), item, pos))


@pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hd,groups", [
    (64, None), (128, None), (32, 2), (128, 2)],
    ids=["64-slots", "128-slots", "32-groups", "128-groups"])
def test_kv_attend_is_the_einsums_over_live_rows(hd, dtype, g, groups):
    """Both block shapes, both row types, one query a KV head and a
    group of them; 300 (301) positions are two blocks, the second
    ragged.  With groups: the ring's call, a scalar position and the
    group an index, and neither the scratch group nor the scratch row
    (NaN) reaches the output.  (Under a lane row the ring's heads are
    32 wide here: its heads of 64 hold joined rows since PR 68 and read
    them through the other kernel —
    ``test_a_ring_step_over_heads_of_64_is_a_slice_and_the_einsums``.)"""
    fmt, layer, q = _attend_case(hd, dtype, g, groups, 300)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    if groups is None:
        pos = jnp.asarray([0, 130, 255, 299], jnp.int32)
        got = fmt.attend(q, layer, pos)
        want = _oracle(q, layer, pos)
    else:
        got = fmt.attend(q, layer, jnp.int32(200), group=jnp.int32(1))
        want = _oracle(q, layer, jnp.int32(200), 1)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("positions", [
    [0, 0, 0], [127, 128, 129], [255, 256, 383], [60, 200, 419]],
    ids=["first", "block-edges", "later-edges", "mixed-and-last"])
@pytest.mark.parametrize("hd", [64, 128])
def test_kv_attend_at_each_sequences_own_position(monkeypatch, request, hd,
                                                  positions):
    """Position 0, mid-block, a block's last and first, the buffer's
    last; 420 positions in blocks of 128 are four blocks, the last
    ragged (a block is as many lane rows as ``_BLOCK_BYTES`` hold)."""
    monkeypatch.setattr(kv_cache, "_BLOCK_BYTES", 2 * hd * 128 * 4)
    attend_blocks.cache_clear()         # sizes are reckoned once a shape
    request.addfinalizer(attend_blocks.cache_clear)
    assert attend_blocks(2, hd, 420, 4) == (2, 128)
    fmt, layer, q = _attend_case(hd, jnp.float32, 2, None, 420, batch=3)
    pos = jnp.asarray(positions, jnp.int32)
    # a fresh trace: the block size is read when the kernel is built
    got = kv_cache.kv_attend.__wrapped__(
        q, layer["k"][None], layer["v"][None], pos, jnp.zeros(1, jnp.int32))
    np.testing.assert_allclose(np.asarray(got), _oracle(q, layer, pos),
                               rtol=2e-6, atol=2e-6)


def test_kv_attend_of_a_bubble_step_is_finite():
    """A bubble writes the scratch row and attends at it: numbers
    nobody reads, but numbers."""
    fmt, layer, q = _attend_case(32, jnp.bfloat16, 1, 2, 300)
    layer = {key: jnp.nan_to_num(buf) for key, buf in layer.items()}
    got = fmt.attend(q, layer, jnp.int32(fmt.scratch_position),
                     group=jnp.int32(0))
    assert np.isfinite(np.asarray(got, np.float32)).all()


# -- the step that writes while it attends -------------------------------------------

#: positions of a sequence of 650 whose blocks hold 256: a block's first
#: row, its last (the block is full *and* holds ``pos``), either lane row
#: of a block, the first and the last position, and a bubble's scratch row
_STEP_LENGTH = 650
_STEP_POSITIONS = {"first": 0, "block-first": 256, "block-last": 255,
                   "first-half": 300, "second-half": 450, "last": 649,
                   "scratch": 650}


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


def _step_case(monkeypatch, request, hd, dtype, g, groups, seed=0):
    """:func:`_attend_case` over 650 positions in blocks of 256 (the
    shape is this section's own: the kernels are traced once a shape,
    whatever ``_BLOCK_BYTES`` then says), and a step's new rows."""
    size = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(kv_cache, "_BLOCK_BYTES", 2 * hd * 256 * size)
    attend_blocks.cache_clear()         # sizes are reckoned once a shape
    request.addfinalizer(attend_blocks.cache_clear)
    fmt, layer, q = _attend_case(hd, dtype, g, groups, _STEP_LENGTH,
                                 batch=3, seed=seed)
    assert attend_blocks(2, hd, _STEP_LENGTH + (groups is not None),
                         size) == (2, 256)
    rng = np.random.default_rng(seed + 1)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((3, 2 * hd)),
                                  jnp.float32) for _ in range(2)))
    return fmt, layer, q, rows


def _assert_step_is_the_two_calls(fmt, q, layer, rows, pos, group):
    """``fmt.step`` against ``write_position`` then ``attend`` from the
    same buffers: the output and every buffer as bits.  Returns the
    step's ``(out, layer)``."""
    wrote = fmt.write_position(layer, rows, pos, group=group)
    want = fmt.attend(q, wrote, pos, group=group)
    got, stepped = fmt.step(q, layer, rows, pos, group=group)
    assert got.dtype == q.dtype and got.shape == q.shape
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert set(stepped) == set(layer)
    for key, buf in wrote.items():
        assert stepped[key].dtype == buf.dtype
        np.testing.assert_array_equal(_bits(stepped[key]), _bits(buf))
    return got, stepped


@pytest.mark.parametrize("hd,groups,at", [
    # slots' heads of 32 and 64; the ring's of 32 and 16 (its heads of
    # 64 hold joined rows since PR 68 and their write is a slice)
    (hd, groups, at) for groups, widths in ((None, (32, 64)), (2, (32, 16)))
    for hd in widths for at in _STEP_POSITIONS
    if groups or at != "scratch"])      # slots alone have no scratch row
@pytest.mark.parametrize("g", [1, 4], ids=["mha", "gqa"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_step_is_write_position_then_attend_bit_for_bit(
        monkeypatch, request, hd, dtype, g, groups, at):
    """Under a lane row a step is one kernel (``kv_step``), and it gives
    what the two calls give: the output and every buffer, bit for bit —
    the rows are float32 and the buffers' type rounds them before the
    attention reads them, in both."""
    fmt, layer, q, rows = _step_case(monkeypatch, request, hd, dtype, g,
                                     groups)
    assert fmt.writes_in_attention
    pos = jnp.int32(_STEP_POSITIONS[at])
    group = None if groups is None else jnp.int32(1)
    got, _ = _assert_step_is_the_two_calls(fmt, q, layer, rows, pos, group)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    # and it is one kernel: neither of the two calls' is reached
    monkeypatch.setattr(kv_cache, "write_kv_rows", None)
    monkeypatch.setattr(kv_cache, "kv_attend", None)
    fmt.step(q, layer, rows, pos, group=group)


def test_a_bubble_step_touches_the_scratch_row_of_its_group_only(
        monkeypatch, request):
    """A bubble writes the scratch row and attends at it: numbers nobody
    reads, but numbers, and no other row or group moves."""
    fmt, layer, q, rows = _step_case(monkeypatch, request, 32, jnp.bfloat16,
                                     1, 2)
    layer = {key: jnp.nan_to_num(buf) for key, buf in layer.items()}
    got, stepped = fmt.step(q, layer, rows, jnp.int32(fmt.scratch_position),
                            group=jnp.int32(0))
    assert np.isfinite(np.asarray(got, np.float32)).all()
    for key, buf in layer.items():
        want = np.asarray(buf).copy()
        want[0, :, :, fmt.scratch_position] = np.asarray(
            rows[key].astype(buf.dtype))[:, :, 0]
        np.testing.assert_array_equal(_bits(stepped[key]), _bits(want))


def test_step_over_blocks_of_heads(monkeypatch, request):
    """Two blocks of heads a sequence: each stores its own heads' lane
    row, once."""
    monkeypatch.setattr(kv_cache, "_BLOCK_BYTES", 32 * 128 * 4)
    attend_blocks.cache_clear()         # sizes are reckoned once a shape
    request.addfinalizer(attend_blocks.cache_clear)
    assert attend_blocks(2, 32, 421, 4) == (1, 128)
    fmt, layer, q = _attend_case(32, jnp.float32, 2, 2, 420, batch=3)
    assert fmt.writes_in_attention
    rng = np.random.default_rng(5)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((3, 2 * 32)),
                                  jnp.float32) for _ in range(2)))
    for pos in (0, 127, 200, 419, 420):
        _assert_step_is_the_two_calls(fmt, q, layer, rows, jnp.int32(pos),
                                      jnp.int32(0))


class _WritesItsOwnWay(KVCacheFormat):
    """On the lanes by its geometry, but not the base's writer."""

    def write_position(self, layer, rows, pos, group=None):
        return super().write_position(layer, rows, pos, group)


@pytest.mark.parametrize("fmt", [
    KVCacheFormat(2, 128, 40, jnp.float32, groups=2),
    KVCacheFormat(2, 64, 40, jnp.float32, quantized=True, groups=2),
    KVCacheFormat(2, 128, 40, jnp.bfloat16, groups=2, query_group=8),
    KVCacheFormat(2, 64, 40, jnp.float32, groups=2, window=16),
    _WritesItsOwnWay(2, 32, 40, jnp.float32, groups=2),
    KVCacheFormat(4, 64, 40, jnp.bfloat16, groups=2, query_group=4),
    KVCacheFormat(25, 64, 40, jnp.bfloat16, groups=2),
    KVCacheFormat(4, 64, 40, jnp.float32, groups=2),
], ids=["lane-rows", "int8", "joined", "ring-buffer", "subclass",
        "joined-hd64", "joined-hd64-one-query-25", "joined-hd64-one-query-4"])
def test_a_format_off_the_lanes_steps_through_its_two_calls(monkeypatch,
                                                            fmt):
    """Heads of a lane row, int8 rows, joined rows (of heads of 128, or
    of heads of 64 two a lane row: a group's, or on the ring one query a
    head — GPT-2's 25, the last lane row half a phantom head, and an
    even count), a ring buffer: a position's rows lie together (or land
    in another block than the last live one), there is nothing to fuse,
    and ``step`` is the write and then the attention over what it wrote
    — the calls the blocks made themselves until PR 54.  So is a
    subclass's whose write is its own."""
    assert fmt.writes_in_attention == isinstance(fmt, _WritesItsOwnWay)
    rng = np.random.default_rng(3)
    layer = {key: jnp.asarray(rng.integers(-9, 9, s.shape), s.dtype)
             for key, s in fmt.buffers(3).items()}
    width = fmt.kv_heads * fmt.head_dim
    q = jnp.asarray(rng.standard_normal((3, width * fmt.query_group)),
                    fmt.dtype)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((3, width)), fmt.dtype)
                      for _ in range(2)))
    calls = []

    def spy(name):
        real = getattr(KVCacheFormat, name)

        def call(self, *args, **kwargs):
            calls.append((name, args, kwargs))
            return real(self, *args, **kwargs)
        monkeypatch.setattr(KVCacheFormat, name, call)

    spy("write_position")
    spy("attend")
    monkeypatch.setattr(kv_cache, "kv_step", None)       # never reached
    pos, group = jnp.int32(5), jnp.int32(1)
    got, stepped = fmt.step(q, layer, rows, pos, group=group)
    assert [name for name, _, _ in calls] == ["write_position", "attend"]
    (_, wrote, _), (_, read, _) = calls
    assert wrote[0] is layer and wrote[1] is rows and wrote[2] is pos
    assert read[0] is q and read[1] is stepped and read[2] is pos
    monkeypatch.undo()
    want = fmt.attend(q, fmt.write_position(layer, rows, pos, group=group),
                      pos, group=group)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def _ring_step_jaxpr(model, num_stages, **kw):
    """A ring decoder of ``gpt_tiny`` (3 sequences a group, 24
    positions) and the jaxpr of its 4-step decode program."""
    graph, params = model
    dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                           microbatch=3, max_len=24, **kw)
    a, caches = dec._init_state()
    i32 = jnp.int32(0)
    return dec, jax.make_jaxpr(dec._get_decode_fn(4, False, None))(
        dec._w, jnp.zeros((num_stages, 3, 5), jnp.int32), i32, i32, i32,
        jnp.uint32(0), jnp.float32(0), jnp.zeros((num_stages, 3), jnp.int32),
        i32, i32, a, caches)


def _kernel_names(jaxpr) -> list:
    """The ``pallas_call`` names of a jaxpr, at any depth, in order."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernel_names(sub)
    return names


@pytest.mark.parametrize("num_stages,kv", [(1, "buffer"), (2, "buffer"),
                                           (1, "int8")])
def test_a_ring_step_holds_one_cache_kernel_a_layer(model, num_stages, kv):
    """The lowered GPT ring step: one ``kv_step`` a layer, no
    ``kv_write_rows`` and no ``kv_attend``, and the gauge
    ``decode.kv.fused_layers`` reads a stage's layers; int8 rows take
    the format's two calls (einsums, no kernel) and the gauge reads 0.
    No layer attends over joined rows (heads under a lane row, one query
    a head): ``decode.kv.joined_layers`` is set, to 0."""
    from defer_tpu.obs.registry import REGISTRY
    REGISTRY.gauge("decode.kv.fused_layers").set(-1)
    REGISTRY.gauge("decode.kv.joined_layers").set(-1)
    dec, jaxpr = _ring_step_jaxpr(model, num_stages, kv_cache=kv)
    fused = dec.l_max if kv == "buffer" else 0
    assert _kernel_names(jaxpr.jaxpr) == ["kv_step"] * (fused * num_stages)
    assert REGISTRY.gauge("decode.kv.fused_layers").value == fused
    assert REGISTRY.gauge("decode.kv.joined_layers").value == 0


@pytest.mark.parametrize("kv,hd,length,itemsize,want", [
    (25, 64, 769, 2, (25, 256)),     # gpt2-xl's ring: 0.8 MB a block
    (25, 64, 192, 4, (25, 128)),     # the engine's f32 slots
    (16, 128, 1281, 2, (16, 256)),   # OLMoE's ring: 1 MiB
    (2, 8, 9, 4, (2, 9)),            # under a lane row: the whole item
    (64, 128, 4096, 4, (16, 128)),   # heads split before positions grow
])
def test_attend_blocks_hold_a_megabyte(kv, hd, length, itemsize, want):
    assert attend_blocks(kv, hd, length, itemsize) == want


def _cache_slices(jaxpr, item):
    """Names of the equations, at any depth, that cut an array with an
    item's dimensions out of another."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("slice", "dynamic_slice", "gather"):
            for out in eqn.outvars:
                if sorted(d for d in out.aval.shape if d != 1) == item:
                    found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _cache_slices(sub, item)
    return found


@pytest.mark.parametrize("engine", ["ring", "slots"])
def test_a_step_cuts_no_item_out_of_a_cache_buffer(model, engine):
    """Neither engine's step holds a ``slice`` / ``dynamic_slice`` that
    produces an array of one group's (one batch of slots') size: the
    attention reads the buffers where they lie, once a layer (the
    ring's writes the step's rows too: ``kv_step``)."""
    graph, params = model
    if engine == "ring":
        dec, jaxpr = _ring_step_jaxpr(model, 2)
        shape = dec.state_format.buffers(3)["k"].shape[1:]
        layers = dec.l_max * 2          # a call a block and a stage
        kernel = "kv_step"
    else:
        eng = ContinuousBatchEngine(graph, params, num_stages=2, width=3)
        jaxpr = jax.make_jaxpr(eng._step_fn(False))(
            eng.params, eng._caches, eng._prev_ids, *eng._blank_rows())
        shape = eng.kv_format.buffers(3)["k"].shape
        layers = len(eng._caches["k"])
        kernel = "kv_attend"
    assert _cache_slices(jaxpr.jaxpr, sorted(d for d in shape if d != 1)) == []
    assert str(jaxpr).count(f"name={kernel}") >= 1
    assert str(jaxpr).count(kernel) >= layers


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
def test_write_position_of_a_group_touches_that_group_only(quantized):
    fmt, rng = _fmt(quantized, groups=3), np.random.default_rng(7)
    layer = _random_layer(fmt, 2, rng)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((2, KV * HD)),
                                  jnp.float32) for _ in range(2)))
    for g, pos in ((1, 4), (fmt.scratch_group, fmt.scratch_position)):
        got = fmt.write_position(layer, rows, jnp.int32(pos),
                                 group=jnp.int32(g))
        for key, c in layer.items():
            want = np.asarray(c).copy()
            want[g, :, :, pos] = np.asarray(rows[key].astype(c.dtype))[:, :, 0]
            np.testing.assert_array_equal(np.asarray(got[key]), want)


@pytest.mark.parametrize("quantized,groups", [
    (False, 2), (True, 2), (False, None), (True, None)],
    ids=["buffer", "int8", "slots", "slots-int8"])
def test_write_prefix_is_the_rows_written_one_by_one(quantized, groups):
    """A prompt's columns written at once equal ``rows`` +
    ``write_position`` a position, int8 scales included.  In a format
    without groups (the serving engine's) the slot is a sequence alone:
    that sequence's rows are the ones written one by one, and every
    other sequence keeps its bits."""
    fmt, rng = _fmt(quantized, groups=groups), np.random.default_rng(8)
    t, at = 5, 1
    batch, b, group = (2, 2, at) if groups else (3, 1, None)

    def cut(layer):
        """The sequences a position's rows are written to."""
        return layer if groups else {key: buf[at:at + b]
                                     for key, buf in layer.items()}

    layer = _random_layer(fmt, batch, rng)
    k, v = (jnp.asarray(rng.standard_normal((b, t, KV * HD)), jnp.float32)
            for _ in range(2))
    got = fmt.write_prefix(layer, k, v, jnp.int32(at))
    want = cut(layer)
    for p in range(t):
        want = fmt.write_position(want, fmt.rows(k[:, p], v[:, p]), p,
                                  group=group)
    for key in fmt.keys:
        np.testing.assert_array_equal(np.asarray(cut(got)[key]),
                                      np.asarray(want[key]))
        if groups is None:
            others = np.arange(batch) != at
            np.testing.assert_array_equal(np.asarray(got[key])[others],
                                          np.asarray(layer[key])[others])


@pytest.mark.parametrize("shape,positions", [
    ((3, 2, 16, 8), [0, 15, 7]),            # one window holds the item
    ((4, 2, 192, 8), [0, 127, 128, 191]),   # 1.5 windows: the cell's L
    ((2, 3, 300, 16), [255, 299]),
])
def test_write_kv_rows_touches_one_position_a_sequence(shape, positions):
    """The engine's row-writer: sequence i's row lands at pos[i], at
    window edges and in a partial last window too, and every other
    element keeps its bits."""
    rng = np.random.default_rng(5)
    w, kv, _, hd = shape
    cache = rng.normal(size=shape).astype(np.float32)
    rows = rng.normal(size=(w, kv, 1, hd)).astype(np.float32)
    got = jax.jit(write_kv_rows)(cache, rows,
                                 jnp.asarray(positions, jnp.int32))
    want = cache.copy()
    for i, p in enumerate(positions):
        want[i, :, p] = rows[i, :, 0]
    np.testing.assert_array_equal(np.asarray(got), want)


def test_write_slots_is_the_row_writer_on_every_buffer():
    fmt, rng = _fmt(), np.random.default_rng(9)
    layer = _random_layer(fmt, 3, rng)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((3, KV * HD)),
                                  jnp.float32) for _ in range(2)))
    pos = jnp.asarray([8, 0, 3], jnp.int32)
    got = fmt.write_slots(layer, rows, pos)
    for key, c in layer.items():
        want = np.asarray(c).copy()
        for i, p in enumerate(pos):
            want[i, :, int(p)] = np.asarray(rows[key])[i, :, 0]
        np.testing.assert_array_equal(np.asarray(got[key]), want)
    with pytest.raises(NotImplementedError, match="unquantized"):
        _fmt(quantized=True).write_slots(layer, rows, pos)


# -- a list of live slots: the serving engine's calls ---------------------------

#: lists over 16 slots: one, three apart, every one, the last alone
_LISTS = [[5], [1, 7, 15], list(range(16)), [15]]
_LIST_IDS = ["one", "three", "all-16", "last"]


def test_live_slots_pads_with_the_last_and_ends_with_the_count():
    np.testing.assert_array_equal(
        live_slots([1, 7, 15], 16), [1, 7, 15] + [15] * 13 + [3])
    np.testing.assert_array_equal(live_slots((0,), 3), [0, 0, 0, 1])
    every = live_slots(range(4), 4)
    np.testing.assert_array_equal(every, [0, 1, 2, 3, 4])
    assert every.dtype == np.int32


@pytest.mark.parametrize("slots", _LISTS, ids=_LIST_IDS)
def test_write_kv_rows_with_a_list_touches_the_listed_slots_only(slots):
    """The listed slots' one position each — positions in all three
    windows of 300, window edges among them — and every other byte of
    the buffer as it was: an unlisted slot's window holds NaN where its
    row would land, and keeps it."""
    rng = np.random.default_rng(21)
    shape = (16, 2, 300, 8)
    positions = [0, 127, 128, 255, 256, 299, 5, 200, 130, 64, 257, 1, 290,
                 129, 126, 298]
    cache = rng.normal(size=shape).astype(np.float32)
    for i, at in enumerate(positions):
        if i not in slots:
            cache[i, :, at] = np.nan
    rows = rng.normal(size=(16, 2, 1, 8)).astype(np.float32)
    got = jax.jit(write_kv_rows)(cache, rows,
                                 jnp.asarray(positions, jnp.int32),
                                 live=jnp.asarray(live_slots(slots, 16)))
    want = cache.copy()
    for i in slots:
        want[i, :, positions[i]] = rows[i, :, 0]
    np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("slots", _LISTS, ids=_LIST_IDS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("hd", [64, 128])
def test_kv_attend_with_a_list_is_the_einsums_on_the_listed_slots(
        hd, dtype, slots):
    """Both block shapes and both row types: a listed slot's output is
    the einsums' over its own live rows, in the first block, the second
    and the ragged third of 300; an unlisted slot's keys and values
    (NaN here) are never read and its output is zeros."""
    fmt, layer, q = _attend_case(hd, dtype, 2, None, 300, batch=16, seed=3)
    listed = np.zeros(16, bool)
    listed[slots] = True
    layer = {key: jnp.where(listed[:, None, None, None], buf, jnp.nan)
             for key, buf in layer.items()}
    pos = jnp.asarray([0, 130, 255, 299, 17, 128, 127, 256, 64, 200, 1, 290,
                       129, 126, 298, 131], jnp.int32)
    got = fmt.attend(q, layer, pos, live=jnp.asarray(live_slots(slots, 16)))
    assert got.dtype == q.dtype and got.shape == q.shape
    got = np.asarray(got, np.float32)
    tol = 2e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got[listed], _oracle(q, layer, pos)[listed],
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(got[~listed], 0.0)


@pytest.mark.parametrize("slots", [[0, 2], [1], [0, 1, 2]],
                         ids=["ends", "middle", "all"])
def test_kv_attend_with_a_list_over_blocks_of_heads_and_positions(
        monkeypatch, request, slots):
    """Two blocks of heads and four of positions a slot: the look-ahead
    of a dead position block names the next block of heads, then the
    next *listed* slot's first block, and a step past the list the last
    live step's blocks; the results are the einsums'."""
    monkeypatch.setattr(kv_cache, "_BLOCK_BYTES", 64 * 128 * 4)
    attend_blocks.cache_clear()         # sizes are reckoned once a shape
    request.addfinalizer(attend_blocks.cache_clear)
    assert attend_blocks(2, 64, 420, 4) == (1, 128)
    fmt, layer, q = _attend_case(64, jnp.float32, 2, None, 420, batch=3)
    pos = jnp.asarray([60, 200, 419], jnp.int32)
    # a fresh trace: the block size is read when the kernel is built
    got = np.asarray(kv_cache.kv_attend.__wrapped__(
        q, layer["k"][None], layer["v"][None], pos, jnp.zeros(1, jnp.int32),
        jnp.asarray(live_slots(slots, 3))))
    want = _oracle(q, layer, pos)
    listed = np.zeros(3, bool)
    listed[slots] = True
    np.testing.assert_allclose(got[listed], want[listed], rtol=2e-6,
                               atol=2e-6)
    np.testing.assert_array_equal(got[~listed], 0.0)


@pytest.mark.parametrize("call", ["write", "attend-64", "attend-128"])
def test_a_list_of_every_slot_is_the_call_without_one(call):
    """``n = width``: the list is the identity and both kernels give the
    call without a list, bit for bit."""
    rng = np.random.default_rng(23)
    every = jnp.asarray(live_slots(range(16), 16))
    pos = jnp.asarray(rng.integers(0, 300, 16), jnp.int32)
    if call == "write":
        cache = rng.normal(size=(16, 2, 300, 8)).astype(np.float32)
        rows = rng.normal(size=(16, 2, 1, 8)).astype(np.float32)
        got, want = (write_kv_rows(cache, rows, pos, live=live)
                     for live in (every, None))
    else:
        fmt, layer, q = _attend_case(int(call[7:]), jnp.float32, 2, None,
                                     300, batch=16)
        got, want = (fmt.attend(q, layer, pos, live=live)
                     for live in (every, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("fmt", [
    KVCacheFormat(KV, HD, L, jnp.float32, quantized=True),
    KVCacheFormat(2, 128, 32, jnp.bfloat16, query_group=8),
], ids=["int8", "joined"])
def test_a_format_that_cannot_walk_a_list_raises(fmt):
    """Neither the einsums of int8 rows nor ``kv_attend_joined`` take a
    list: such a format says so, as ``write_slots`` does for what it
    cannot write."""
    layer = fmt.zeros(3, 1)
    layer = {key: bufs[0] for key, bufs in layer.items()}
    q = jnp.zeros((3, fmt.kv_heads * fmt.query_group * fmt.head_dim),
                  fmt.dtype)
    pos, live = jnp.zeros(3, jnp.int32), jnp.asarray(live_slots([1], 3))
    with pytest.raises(NotImplementedError, match="list of live"):
        fmt.attend(q, layer, pos, live=live)
    with pytest.raises(NotImplementedError, match="unquantized"):
        fmt.write_slots(layer, fmt.rows(q[:, :fmt.kv_heads * fmt.head_dim],
                                        q[:, :fmt.kv_heads * fmt.head_dim]),
                        pos, live)
    assert np.isfinite(np.asarray(fmt.attend(q, layer, pos),
                                  np.float32)).all()


def test_reparent_moves_one_groups_rows_in_every_layer():
    fmt, rng = _fmt(quantized=True, groups=2), np.random.default_rng(10)
    state = {key: tuple(_random_layer(fmt, 4, rng)[key] for _ in range(2))
             for key in fmt.keys}
    state["beam_cum"] = jnp.ones((2, 4))
    parents = jnp.asarray([1, 1, 3, 2], jnp.int32)
    got = fmt.reparent(state, jnp.int32(1), parents)
    assert got["beam_cum"] is state["beam_cum"]
    for key in fmt.keys:
        for old, new in zip(state[key], got[key]):
            want = np.asarray(old).copy()
            want[1] = want[1][np.asarray(parents)]
            np.testing.assert_array_equal(np.asarray(new), want)


# -- another format in its place ------------------------------------------------

class PositionsLeading(KVCacheFormat):
    """The same rows kept positions-first (``[positions, (groups,) batch,
    kv_heads(, head_dim)]``): what a holder that indexed a buffer's axes
    itself, or built one from a shape of its own, could not run on."""

    @property
    def _axis(self):            # where the real format keeps positions
        return 2 if self.groups is None else 3

    def _out(self, layer):
        return {key: jnp.moveaxis(buf, self._axis, 0)
                for key, buf in layer.items()}

    def _in(self, layer):
        return {key: jnp.moveaxis(buf, 0, self._axis)
                for key, buf in layer.items()}

    def buffers(self, batch):
        def first(s):
            shape = list(s.shape)
            shape.insert(0, shape.pop(self._axis))
            return jax.ShapeDtypeStruct(tuple(shape), s.dtype)
        return {key: first(s) for key, s in super().buffers(batch).items()}

    def write_position(self, layer, rows, pos, group=None):
        return self._out(super().write_position(self._in(layer), rows, pos,
                                                group))

    def write_slots(self, layer, rows, pos, live=None):
        return self._out(super().write_slots(self._in(layer), rows, pos,
                                             live))

    def write_prefix(self, layer, k, v, group):
        return self._out(super().write_prefix(self._in(layer), k, v, group))

    def reparent(self, state, group, parents):
        def each(fn, st):
            return {key: tuple(fn({key: b})[key] for b in bufs)
                    if key in self.keys else bufs for key, bufs in st.items()}
        return each(self._out,
                    super().reparent(each(self._in, state), group, parents))

    def attend(self, q, layer, pos, group=None, live=None):
        return super().attend(q, self._in(layer), pos, group, live)


@pytest.fixture(scope="module")
def model():
    graph = gpt_tiny(seq_len=24)
    return graph, graph.init(jax.random.key(3))


def _prompts(n, plen):
    return np.random.default_rng(4).integers(0, 97, (n, plen)).astype(np.int32)


@pytest.mark.parametrize("kv", ["buffer", "int8"])
@pytest.mark.parametrize("num_stages,beam", [(1, 1), (4, 1), (2, 2)])
def test_the_ring_runs_on_a_format_with_positions_leading(
        model, monkeypatch, num_stages, beam, kv):
    """The ring builds, shards, writes and reads its caches through the
    format alone: with another layout in the format's place (no program
    option: the class is replaced on its module) every path gives the
    tokens it gives on the real one — fused prefill, teacher-forced
    prompts, beam search's re-parenting."""
    graph, params = model
    prompt = _prompts(8 // beam, 5)
    calls = [dict(prefill=True, token_chunk=2), {}] if beam == 1 else [{}]

    def tokens():
        dec = PipelinedDecoder(graph, params, num_stages=num_stages,
                               microbatch=8 // num_stages, max_len=24,
                               kv_cache=kv, beam_width=beam)
        return dec, [dec.generate(prompt, 6, **kw) for kw in calls]

    _, want = tokens()
    monkeypatch.setattr(kv_cache, "KVCacheFormat", PositionsLeading)
    dec, got = tokens()
    assert isinstance(dec.state_format, PositionsLeading)
    _, caches = dec._init_state()
    assert caches["k"][0].shape[1] == 24 + 1     # [stage, positions, ...]
    for w, g in zip(want, got):
        np.testing.assert_array_equal(g, w)


def test_the_engine_runs_on_a_format_with_positions_leading(
        model, monkeypatch):
    """Slots at three different positions in one step, joined at
    different steps: the same answers over the other layout."""
    graph, params = model
    prompts = [_prompts(1, n)[0] for n in (3, 7, 5)]
    join_at = {0: 0, 1: 2, 2: 5}

    def answers():
        eng = ContinuousBatchEngine(graph, params, num_stages=2, width=3)
        seen = []

        def stagger(e, queue):
            while queue and e.steps >= join_at[queue[0].request_id]:
                e.join(queue.pop(0))
            seen.append({s.pos for s in e._slots if s is not None})

        out = eng.run_all(
            [DecodeRequest(prompt=p, max_new_tokens=6, request_id=i)
             for i, p in enumerate(prompts)], joiner=stagger)
        assert any(len(ps) == 3 for ps in seen)
        return eng, out

    _, want = answers()
    monkeypatch.setattr(kv_cache, "KVCacheFormat", PositionsLeading)
    eng, got = answers()
    assert eng._caches["k"][0].shape == (24, 3, 2, 16)
    for rid, ids in want.items():
        np.testing.assert_array_equal(got[rid], ids)


# -- ring buffers and joined rows ----------------------------------------------

def _dense_attention(q, k, v, pos, window=None):
    """softmax(q k / sqrt hd) v over positions ``<= pos`` (and, with a
    window, the ``window`` newest): q [b, nh, hd], k / v [b, kv, T, hd]."""
    b, nh, hd = q.shape
    g = nh // k.shape[1]
    k, v = np.repeat(k, g, axis=1), np.repeat(v, g, axis=1)
    s = np.einsum("bhd,bhtd->bht", q, k) / math.sqrt(hd)
    t = np.arange(k.shape[2])[None, None, :]
    seen = t <= pos
    if window is not None:
        seen &= pos - t < window
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bht,bhtd->bhd", p, v).reshape(b, nh * hd)


@pytest.mark.parametrize("quantized", [False, True], ids=["buffer", "int8"])
@pytest.mark.parametrize("groups", [None, 2], ids=["slots", "groups"])
def test_a_ring_buffer_holds_window_rows_and_the_scratch_row(quantized,
                                                             groups):
    fmt = KVCacheFormat(KV, HD, 20, jnp.float32, quantized=quantized,
                        groups=groups, window=6)
    lead = () if groups is None else (groups + 1,)
    assert fmt.buffers(3)["k"].shape == lead + (3, KV, 6 + (groups
                                                             is not None), HD)
    assert fmt.rows_held == 6 and fmt.scratch_position == 6
    assert fmt.bubble_slot == -1
    assert KVCacheFormat(KV, HD, 20, jnp.float32).rows_held == 20
    with pytest.raises(ValueError, match="never wraps"):
        KVCacheFormat(KV, HD, 6, jnp.float32, window=6)
    with pytest.raises(NotImplementedError):
        fmt.write_slots({}, {}, jnp.zeros(3, jnp.int32))


#: (queries a KV head, a head's width, the rows' type, KV heads)
_WINDOW_CASES = {
    "g1": (1, 8, jnp.float32, 2), "g4": (4, 8, jnp.float32, 2),
    "g4-hd64-bf16": (4, 64, jnp.bfloat16, 2),
    "g16-joined": (16, 128, jnp.float32, 2),
    "g16-joined-bf16": (16, 128, jnp.bfloat16, 2),
    "g8-joined": (8, 128, jnp.float32, 2),
    "g20-joined-bf16": (20, 128, jnp.bfloat16, 2),
    "g1-hd128": (1, 128, jnp.float32, 2),
    "g2-joined": (2, 128, jnp.float32, 2),
    "g4-joined": (4, 128, jnp.float32, 2),
    "g4-joined-bf16": (4, 128, jnp.bfloat16, 2),
    "g5-joined-bf16": (5, 128, jnp.bfloat16, 2),
    "g7-joined": (7, 128, jnp.float32, 2),
    "g2-hd64": (2, 64, jnp.float32, 2), "g4-hd64": (4, 64, jnp.float32, 2),
    "g1-hd64": (1, 64, jnp.float32, 2),
    "g4-hd64-kv3": (4, 64, jnp.float32, 3),
    "g3-hd32-kv4": (3, 32, jnp.bfloat16, 4),
    "g1-hd64-kv25-bf16": (1, 64, jnp.bfloat16, 25),
    "g1-hd64-kv5": (1, 64, jnp.float32, 5),
}


@pytest.mark.parametrize("case", _WINDOW_CASES)
@pytest.mark.parametrize("plen", [3, 6, 7, 17], ids=[
    "under", "at", "over", "wrapped-twice"])
def test_ring_buffer_steps_are_attention_over_the_window(case, plen):
    """``write_prefix`` of a prompt under, at and over the window, then
    decode steps across the next wraps: every step's attention is the
    dense attention over the ``window`` newest positions — through the
    vector kernel (one query a KV head of 8 or of 128, and a group over
    heads that pair into no lane row: heads of 8, four of 32) and
    through joined rows on the matrix unit (2 to 20 queries a KV head of
    128, a group that fills a sublane tile or not; in bfloat16 the two
    heads' rows are thin and both sequences share a block — and the same
    over heads of 64, two a lane row: the pair is one head of 128 to the
    kernel, its queries side by side; an odd count of them — three,
    five, GPT-2's 25 — pairs off with a phantom head of zeros; and with
    one query a head, which this holder's groups make two query rows a
    lane row, the whole row's heads side by side are one head).  The
    rule is the geometry's and the holder's: float rows, heads of whole
    lane rows or pairs of halves, two query rows a lane row."""
    g, hd, dtype, kv = _WINDOW_CASES[case]
    w, total, b = 6, 30, 2
    fmt = KVCacheFormat(kv, hd, total, dtype, groups=1, window=w,
                        query_group=g)
    per = {128: 1, 64: 2}.get(hd, 0)
    assert fmt.joined == (per * g >= 2)
    a_row_a_position = dataclasses.replace(fmt, window=None)
    assert a_row_a_position.joined == fmt.joined
    assert a_row_a_position.writes_in_attention == (
        not fmt.joined and hd < 128)
    if fmt.joined:
        # whole sublane tiles of positions: the window, the scratch
        # row, and padding
        assert fmt.buffers(b)["k"].shape == (
            2, b, 16, -(-kv // per) * per * hd)
    rng = np.random.default_rng(plen)
    q = rng.standard_normal((b, total, kv * g * hd)).astype(np.float32)
    k = rng.standard_normal((b, total, kv * hd)).astype(np.float32)
    v = rng.standard_normal((b, total, kv * hd)).astype(np.float32)
    qd, kd, vd = (jnp.asarray(a, dtype) for a in (q, k, v))

    @jax.jit
    def run(qd, kd, vd):
        layer = fmt.layer(fmt.zeros(b, 1), 0)
        layer = fmt.write_prefix(layer, kd[:, :plen], vd[:, :plen],
                                 fmt.prefill_slot(True, 0))
        ys = []
        for pos in range(plen, plen + 9):
            slot = fmt.decode_slot(True, jnp.int32(pos))
            layer = fmt.write_position(
                layer, fmt.rows(kd[:, pos], vd[:, pos]), slot, group=0)
            ys.append(fmt.attend(qd[:, pos], layer, slot, group=0))
        # a bubble writes the scratch row and reads something finite
        bubble = fmt.decode_slot(False, jnp.int32(3))
        after = fmt.write_position(
            layer, fmt.rows(kd[:, 0] * 0 + 7, vd[:, 0]), bubble, group=0)
        return jnp.stack(ys), fmt.attend(qd[:, 0], after, bubble, group=0), \
            layer, after

    ys, y_bubble, layer, after = run(qd, kd, vd)
    f32 = [np.asarray(a.astype(jnp.float32)) for a in (qd, kd, vd)]
    heads = [a.reshape(b, total, -1, hd).transpose(0, 2, 1, 3) for a in f32]
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    for i, pos in enumerate(range(plen, plen + 9)):
        want = _dense_attention(heads[0][:, :, pos], heads[1], heads[2],
                                pos, window=w)
        np.testing.assert_allclose(np.asarray(ys[i], np.float32), want,
                                   atol=tol, err_msg=f"pos {pos}")
    assert np.isfinite(np.asarray(y_bubble, np.float32)).all()
    # the bubble touched the scratch row alone
    for key in ("k", "v"):
        a, c = np.asarray(layer[key][0], np.float32), \
            np.asarray(after[key][0], np.float32)
        rows = (slice(None), slice(0, w)) if fmt.joined \
            else (slice(None), slice(None), slice(0, w))
        np.testing.assert_array_equal(a[rows], c[rows])
    # and the oracle learns the same window
    item = fmt.head_major({key: buf[0] for key, buf in layer.items()})
    last = plen + 8
    got = attend_einsum(qd[:, last], item, last, window=w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ys[-1], np.float32), atol=tol)


#: (KV heads, queries a head, a position a sequence), over 1100
#: positions of heads of 128: 16 queries on two KV heads — in float32
#: 1 KB a position, a sequence a block — and Jamba's 20 on one, whose
#: thin rows share a block of 512 positions among sequences: all at one
#: position; each at its own, on both sides of a block's edge, at 0 and
#: at the last row; six and two sequences, which eight a block (four in
#: float32) do not divide.  And the groups under a sublane tile — 2, 4,
#: 5 and 7 queries a head: a head's rows of the queries and of the
#: softmax's state are a slice at ``h * g``, inside a tile or across two
#: — on one KV head and on granite's eight (2 KB a position in bfloat16,
#: a block of 512; 4 KB in float32, of 256): inside the first block, on
#: a block's last row, in a later block.  And heads of 64 (a fourth
#: entry: a head's width), two a lane row: LFM2's eight in groups of 2,
#: 4 and 7 (1 KB a position in bfloat16, a block of 1024) and two, whose
#: thin rows share a block
_JOINED_CASES = {
    "block0": (2, 16, [5, 300]),
    "edge": (2, 16, [511, 512]),
    "three-blocks": (2, 16, [1029, 0]),
    "thin-one-position": (1, 20, [700] * 4),
    "thin-edges": (1, 20, [0, 511, 512, 1099]),
    "thin-six": (1, 20, [5, 300, 511, 512, 1029, 0]),
    "thin-two": (1, 20, [1029, 0]),
    **{f"g{g}-kv{kv}": (kv, g, [5, 511, 700])
       for g in (2, 4, 5, 7) for kv in (1, 8)},
    **{f"g{g}-kv{kv}-hd64": (kv, g, [5, 511, 700], 64)
       for g in (2, 4, 7) for kv in (2, 8)},
}


@pytest.mark.parametrize("groups", [None, 2], ids=["slots", "groups"])
@pytest.mark.parametrize("case", _JOINED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_joined_attention_at_each_sequences_own_position(case, dtype, groups):
    """A matrix's rows of queries a KV head over joined rows (the
    heads of a lane row one head to the kernel, where they are 64
    wide), each sequence at its own position, over several position
    blocks and a length that is no multiple of the block: the einsums
    over the live rows.  Every row nothing may read is NaN — the tiles' padding, and
    with groups (the ring's call: the group an index) the scratch row
    and the scratch group."""
    kv, g, positions, *hd = _JOINED_CASES[case]
    hd, length, b = hd[0] if hd else 128, 1100, len(positions)
    fmt = KVCacheFormat(kv, hd, length, dtype, groups=groups, query_group=g)
    assert fmt.joined
    sequences, rows = kv_cache.joined_block_rows(
        kv, hd, fmt.buffers(b)["k"].shape[-2], jnp.dtype(dtype).itemsize, b)
    row = kv * hd * jnp.dtype(dtype).itemsize
    if row < 1024:
        # the cap engages, and a block holds as many of the sequences
        # as a megabyte holds and divide them
        assert rows == 512 and sequences == max(
            d for d in range(1, 2048 // row + 1) if b % d == 0) > 1
    else:
        # a megabyte of a sequence's positions, in whole lane rows
        assert sequences == 1 and rows == (1 << 20) // row < length
    rng = np.random.default_rng(7)
    layer = {key: jnp.asarray(rng.standard_normal(s.shape), dtype)
             .at[..., length:, :].set(jnp.nan)
             for key, s in fmt.buffers(b).items()}
    group = None
    if groups is not None:
        group = jnp.int32(1)
        layer = {key: buf.at[fmt.scratch_group].set(jnp.nan)
                 for key, buf in layer.items()}
    q = jnp.asarray(rng.standard_normal((b, kv * g * hd)), dtype)
    pos = jnp.asarray(positions, jnp.int32)
    got = jax.jit(fmt.attend)(q, layer, pos, group)
    want = attend_einsum(q.astype(jnp.float32), {
        key: jnp.nan_to_num(buf.astype(jnp.float32)) for key, buf
        in fmt.head_major({key: buf if groups is None else buf[1]
                           for key, buf in layer.items()}).items()}, pos)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want),
        atol=2e-5 if dtype == jnp.float32 else 3e-2)


#: bfloat16 formats as the cells hold them — (KV heads, queries a
#: head, positions, window, sequences a group) — with their buffers'
#: rows, the attention's block over them and a head's width where it is
#: not 128 (LFM2's two heads a lane row: Mellum2's 1 KB a position;
#: GPT-2's 25 and a phantom, 3328 B a position: a sequence's 256
#: positions a block)
_CELL_BLOCKS = {
    "mellum2-full": ((4, 8, 28672, None, 16), 28688, (1, 1024)),
    "mellum2-window": ((4, 8, 28672, 1024, 16), 1040, (1, 1024)),
    "commandaplus-full": ((8, 16, 12288, None, 16), 12304, (1, 512)),
    "commandaplus-window": ((8, 16, 12288, 4096, 16), 4112, (1, 512)),
    "jamba2": ((1, 20, 4352, None, 256), 4368, (8, 512)),
    "jamba2-a-group-of-two": ((1, 20, 4352, None, 2), 4368, (2, 512)),
    "granite4h": ((8, 4, 3072, None, 64), 3088, (1, 512)),
    "lfm2moe": ((8, 4, 2559, None, 128), 2560, (1, 1024), 64),
    "gpt2xl": ((25, 1, 768, None, 8), 784, (1, 256), 64),
    "gpt2xl-long-prompt": ((25, 1, 928, None, 8), 944, (1, 256), 64),
    "gpt2xl-pipe4": ((25, 1, 768, None, 2), 784, (1, 256), 64),
}


@pytest.mark.parametrize("cell", _CELL_BLOCKS)
def test_a_joined_block_is_sized_from_the_operands_shapes(cell):
    """A position of 1 KB and more keeps the block it had, one
    sequence's run of as many positions as a megabyte holds (Mellum2's
    and command-a-plus's calls lower as they did); Jamba's 256 B a
    position stop at 512 positions and fill the block with sequences —
    and the format's gauges say which."""
    (kv, g, positions, window, b), length, want, *hd = _CELL_BLOCKS[cell]
    hd = hd[0] if hd else 128
    fmt = KVCacheFormat(kv, hd, positions, jnp.bfloat16, groups=1,
                        window=window, query_group=g)
    # whole lane rows of columns: GPT-2's 25 heads of 64 hold a 26th
    held = -(-kv * hd // 128) * 128
    assert fmt.joined and fmt.buffers(b)["k"].shape == (
        2, b, length, held)
    assert kv_cache.joined_block_rows(held // hd, hd, length, 2, b) == want
    said = fmt.gauges(b, 1)
    assert (said["decode.cache.block_sequences"],
            said["decode.cache.block_positions"]) == want
    assert {"decode.cache.block_sequences",
            "decode.cache.block_positions"} <= fmt.largest
    plain = KVCacheFormat(kv, hd, positions, jnp.bfloat16,
                          window=window).gauges(b, 1)
    assert plain["decode.cache.block_sequences"] == 0
    assert plain["decode.cache.block_positions"] == 0


@pytest.mark.parametrize("hd", [128, 64])
def test_a_joined_prefix_of_a_piece_lands_at_its_sequences(hd):
    """``prefill_slot`` with a row: a piece of a group, written from
    that sequence on, the rest of the group untouched — plain rows (one
    query a head of 128) and joined ones, of heads of 128 and of 64
    (there one query a head too: the ring's two query rows a lane
    row)."""
    for g in (1, 16):
        fmt = KVCacheFormat(2, hd, 12, jnp.float32, groups=2,
                            query_group=g)
        assert fmt.joined == (g == 16 or hd == 64)
        rng = np.random.default_rng(g)
        k = jnp.asarray(rng.standard_normal((2, 5, 2 * hd)), jnp.float32)
        layer = fmt.layer(fmt.zeros(4, 1), 0)
        slot = fmt.prefill_slot(True, 1, 2)
        out = jax.jit(lambda layer: fmt.write_prefix(layer, k, k + 1, slot))(
            layer)
        item = fmt.head_major({key: buf[1] for key, buf in out.items()})
        want = np.asarray(k).reshape(2, 5, 2, hd).transpose(0, 2, 1, 3)
        np.testing.assert_array_equal(np.asarray(item["k"])[2:, :, :5], want)
        np.testing.assert_array_equal(np.asarray(item["v"])[2:, :, :5],
                                      want + 1)
        assert not np.asarray(item["k"])[:2].any()
        assert not np.asarray(out["k"][0]).any()


# -- one query a head of 64 on the ring: GPT-2's rows off the lanes ------------

@pytest.mark.parametrize("hd,kv,g,groups,quantized,want", [
    (64, 25, 1, 2, False, True), (64, 8, 1, 2, False, True),
    (64, 25, 1, None, False, False), (64, 8, 1, None, False, False),
    (128, 16, 1, 2, False, False), (128, 16, 2, None, False, True),
    (64, 8, 4, None, False, True), (64, 3, 2, None, False, True),
    (64, 25, 1, 2, True, False), (32, 4, 4, 2, False, False),
], ids=["gpt2-ring", "even-ring", "gpt2-slots", "even-slots", "olmoe-ring",
        "group-of-128", "lfm2-slots", "odd-group-slots", "int8-ring",
        "heads-of-32"])
def test_joined_counts_the_query_rows_of_a_lane_row(hd, kv, g, groups,
                                                    quantized, want):
    """Float rows of whole lane rows — a head's, or two heads of 64 —
    are joined from two query rows a lane row on: a KV head's group
    whoever holds the format; a lane row's two heads with one query
    each where the holder writes every sequence of a group at one
    position (the ring: ``groups``), not in slots, whose writes and
    live list joined rows refuse; one query a head of 128 nowhere.  An
    odd count of halves holds one phantom head more."""
    fmt = KVCacheFormat(kv, hd, 40, jnp.bfloat16, quantized=quantized,
                        groups=groups, query_group=g)
    assert fmt.joined == want
    if not want:
        assert fmt.buffers(3)["k"].shape[-3:] == (kv, 40 + (groups
                                                            is not None), hd)
        return
    assert fmt.held_heads == kv + (hd == 64 and kv % 2)
    assert fmt.buffers(3)["k"].shape[-2:] == (48, fmt.held_heads * hd)
    assert fmt.buffers(3)["k"].shape[-1] % 128 == 0
    assert not fmt.writes_in_attention
    with pytest.raises(NotImplementedError):
        fmt.write_slots({}, {}, jnp.zeros(3, jnp.int32))


def _ring_of_64(kv, dtype, length=300, batch=3, seed=0):
    """The ring's format of ``kv`` heads of 64, one query a head, two
    groups, and a layer: noise in the heads' columns, zeros in the
    phantom's — as :meth:`zeros` makes them and every write leaves them
    — and NaN wherever nothing may read: the scratch group, the scratch
    row and the padding behind it."""
    fmt = KVCacheFormat(kv, 64, length, dtype, groups=2)
    assert fmt.joined
    rng = np.random.default_rng(seed)
    layer = {key: jnp.asarray(rng.standard_normal(s.shape), dtype)
             .at[..., kv * 64:].set(0)
             .at[fmt.scratch_group].set(jnp.nan)
             .at[:, :, fmt.scratch_position:].set(jnp.nan)
             for key, s in fmt.buffers(batch).items()}
    q = jnp.asarray(rng.standard_normal((batch, kv * 64)), dtype)
    rows = fmt.rows(*(jnp.asarray(rng.standard_normal((batch, kv * 64)),
                                  dtype) for _ in range(2)))
    return fmt, layer, q, rows


@pytest.mark.parametrize("at", ["first", "block-last", "block-first", "last",
                                "scratch"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("kv", [25, 4])
def test_a_ring_step_over_heads_of_64_is_a_slice_and_the_einsums(kv, dtype,
                                                                 at):
    """GPT-2's 25 heads (13 lane rows, the last half a phantom; a
    sequence's 256 positions a block in bfloat16, 128 in float32) and an
    even count (thin rows: several sequences a block): a step writes the
    position's row as a slice — the new rows, zeros in the phantom's
    columns, nothing else of any buffer — and attends over the rows ``<=
    pos`` as the einsums do, at position 0, a block's last and first
    row, the last position; a bubble's step writes the scratch row of
    its group and reads numbers."""
    fmt, layer, q, rows = _ring_of_64(kv, dtype)
    scratch = at == "scratch"
    pos = {"first": 0, "block-last": 255, "block-first": 256, "last": 299,
           "scratch": fmt.scratch_position}[at]
    group = 0 if scratch else 1
    if scratch:
        layer = {key: jnp.nan_to_num(buf) for key, buf in layer.items()}
    got, stepped = jax.jit(fmt.step)(q, layer, rows, jnp.int32(pos),
                                     jnp.int32(group))
    assert got.dtype == q.dtype and got.shape == q.shape
    for key, buf in layer.items():
        want = np.asarray(buf.astype(jnp.float32)).copy()
        want[group, :, pos] = np.asarray(rows[key].astype(jnp.float32))[:, 0]
        np.testing.assert_array_equal(
            np.asarray(stepped[key].astype(jnp.float32)), want)
        assert not want[group, :, pos, kv * 64:].any()
    if scratch:
        assert np.isfinite(np.asarray(got, np.float32)).all()
        return
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _oracle(q, stepped, pos, group, fmt),
        atol=2e-5 if dtype == jnp.float32 else 3e-2)


@pytest.mark.parametrize("kv", [25, 3])
def test_the_phantom_head_stays_zeros_and_head_major_drops_it(kv):
    """A prompt written whole, in pieces of a group and a row at a
    time leaves one buffer: the rows in the heads' columns, zeros in the
    phantom head's half lane row — which no write fills with anything
    else, so its scores are 0 and its output 0 — and ``head_major``
    hands the heads back without it."""
    b, t = 4, 7
    fmt = KVCacheFormat(kv, 64, 12, jnp.float32, groups=2)
    assert fmt.joined and fmt.held_heads == kv + 1
    rng = np.random.default_rng(kv)
    k = jnp.asarray(rng.standard_normal((b, t, kv * 64)), jnp.float32)
    empty = fmt.layer(fmt.zeros(b, 1), 0)
    assert empty["k"].shape == (3, b, 16, (kv + 1) * 64)
    whole = jax.jit(lambda layer: fmt.write_prefix(
        layer, k, k + 1, fmt.prefill_slot(True, 1)))(empty)

    @jax.jit
    def pieces(layer):
        for row in (0, 2):
            layer = fmt.write_prefix(layer, k[row:row + 2], k[row:row + 2] + 1,
                                     fmt.prefill_slot(True, 1, row))
        return layer

    @jax.jit
    def one_by_one(layer):
        for pos in range(t):
            layer = fmt.write_position(
                layer, fmt.rows(k[:, pos], k[:, pos] + 1), jnp.int32(pos),
                group=jnp.int32(1))
        return layer

    for other in (pieces(empty), one_by_one(empty)):
        for key in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(other[key]),
                                          np.asarray(whole[key]))
    for key, add in (("k", 0), ("v", 1)):
        buf = np.asarray(whole[key])
        assert not buf[..., kv * 64:].any()            # the phantom
        assert not buf[0].any() and not buf[2].any()    # the other groups
        np.testing.assert_array_equal(buf[1, :, :t, :kv * 64],
                                      np.asarray(k) + add)
    item = fmt.head_major({key: buf[1] for key, buf in whole.items()})
    assert item["k"].shape == (b, kv, 16, 64)
    np.testing.assert_array_equal(
        np.asarray(item["k"])[:, :, :t],
        np.asarray(k).reshape(b, t, kv, 64).transpose(0, 2, 1, 3))


@pytest.fixture(scope="module")
def model_of_64():
    """GPT's blocks at GPT-2's head width: three heads of 64, an odd
    count as GPT-2's 25 is."""
    from defer_tpu.models.gpt import gpt
    graph = gpt(2, 192, 3, 24, vocab=97)
    return graph, graph.init(jax.random.key(5))


@pytest.mark.parametrize("num_stages", [1, 2])
def test_a_ring_step_over_heads_of_64_attends_over_joined_rows(model_of_64,
                                                               num_stages):
    """The lowered ring step of a GPT-2-shaped model: a layer is two
    slices and one ``kv_attend`` — no ``kv_step``, no ``kv_write_rows``
    — and the gauges say which kernel ran: ``decode.kv.joined_layers``
    a stage's layers, ``decode.kv.fused_layers`` 0."""
    from defer_tpu.obs.registry import REGISTRY
    REGISTRY.gauge("decode.kv.fused_layers").set(-1)
    REGISTRY.gauge("decode.kv.joined_layers").set(-1)
    dec, jaxpr = _ring_step_jaxpr(model_of_64, num_stages)
    assert dec.state_format.joined and dec.state_format.held_heads == 4
    assert _kernel_names(jaxpr.jaxpr) == ["kv_attend"] * 2
    assert REGISTRY.gauge("decode.kv.fused_layers").value == 0
    assert REGISTRY.gauge("decode.kv.joined_layers").value == dec.l_max
    assert str(jaxpr).count("dynamic_update_slice") >= 4


def test_the_engine_over_heads_of_64_keeps_plain_rows_and_a_live_list(
        model_of_64):
    """The serving engine holds slots: a position a sequence, a list of
    live sequences — what plain rows take and joined rows refuse.  Its
    format of the same heads is plain, and its step is the row-writer a
    buffer and ``kv_attend`` a layer, each with the list among its
    scalar operands (group, positions, list)."""
    graph, params = model_of_64
    eng = ContinuousBatchEngine(graph, params, num_stages=1, width=3)
    assert not eng.kv_format.joined
    assert eng.kv_format.buffers(3)["k"].shape == (3, 3, 24, 64)
    jaxpr = jax.make_jaxpr(eng._step_fn(False))(
        eng.params, eng._caches, eng._prev_ids, *eng._blank_rows())

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield (eqn.params["name"],
                       eqn.params["grid_mapping"].num_index_operands)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    assert list(calls(jaxpr.jaxpr)) == [
        ("kv_write_rows", 3), ("kv_write_rows", 3), ("kv_attend", 3)] * 2
