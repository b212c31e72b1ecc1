"""The routed experts' grouped product (``defer_tpu/ops/grouped.py``):
its two kernels — a step's ``grouped_experts`` and a prompt's row-tiled
``grouped_rows`` — against ``lax.ragged_dot`` at tiny shapes in the
interpreter, the fused gate-and-up pass, the held dispatch on top of
it, and the shape rule in the four routed families' ring programs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

import defer_tpu.ops.routed as routed
from defer_tpu.ops.routed import expert_dispatch_held, grouped_swiglu
from defer_tpu.models import (cohere_moe_tiny, granite_hybrid_tiny,
                              longcat_flash_tiny, mellum_tiny, olmoe_tiny)
from defer_tpu.obs import REGISTRY
from defer_tpu.ops import grouped
from defer_tpu.runtime.decode import PipelinedDecoder

#: name: (rows, k, n, sizes a group)
CASES = {
    "empty groups between": (40, 24, 48, [3, 0, 17, 9, 0, 2]),
    "leading and trailing empty groups": (64, 32, 128, [0, 0, 30, 0, 34, 0]),
    "one group holds every row": (64, 128, 384, [0, 64, 0, 0]),
    "sizes sum to less than the rows": (48, 16, 32, [5, 0, 11, 7]),
    "no group has a row": (7, 8, 16, [0, 0, 0]),
    "boundaries off the 8- and 16-row tiles": (96, 64, 256,
                                               [7, 9, 1, 15, 17, 3, 33, 11]),
    "a group longer than two blocks": (160, 32, 128, [13, 77, 0, 70]),
    "k and n no multiple of a tile": (37, 20, 72, [10, 0, 19, 8]),
    "two column tiles": (32, 4096, 256, [9, 0, 23]),
}


def _operands(case, dtype, mats=1, seed=0):
    rows, k, n, sizes = CASES[case]
    ks = jax.random.split(jax.random.key(seed), 1 + mats)
    xs = jax.random.normal(ks[0], (rows, k), jnp.float32).astype(dtype)
    ws = tuple((jax.random.normal(key, (len(sizes), k, n), jnp.float32)
                / np.sqrt(k)).astype(dtype) for key in ks[1:])
    return xs, ws, jnp.asarray(sizes, jnp.int32)


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


#: the two Pallas calls under one contract.  The tiled one at 16-row
#: tiles, so that the cases' boundaries fall inside a tile (several
#: groups in one, ``"boundaries off ..."``), a group runs over several
#: tiles and the last tile is partial (40, 37 and 7 rows)
KERNELS = {"kernel": grouped.grouped_experts.__wrapped__,
           "tiled": grouped.grouped_rows.__wrapped__}
@pytest.fixture
def small_row_tiles(monkeypatch):
    monkeypatch.setattr(grouped, "_ROW_TILE", 16)


def paths(test):
    return pytest.mark.usefixtures("small_row_tiles")(
        pytest.mark.parametrize("path", list(KERNELS))(test))


@paths
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_is_ragged_dot(case, dtype, path, monkeypatch):
    if case == "two column tiles":
        monkeypatch.setattr(grouped, "_TILE_BYTES", 1 << 20)
    assert grouped._ROW_TILE == 16
    xs, (w,), sizes = _operands(case, dtype)
    got = KERNELS[path](xs, (w,), sizes)
    assert got.shape == (xs.shape[0], w.shape[-1]) and got.dtype == dtype
    live = np.arange(xs.shape[0]) < int(sizes.sum())
    want = _f32(lax.ragged_dot(xs, w, sizes))
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(_f32(got)[live], want[live], atol=tol,
                               rtol=tol)
    # rows behind the last group are no group's: zero, whatever came in
    assert np.isfinite(_f32(got)).all() and not _f32(got)[~live].any()
    np.testing.assert_allclose(
        _f32(got), _f32(grouped.grouped_reference(xs, (w,), sizes)),
        atol=tol, rtol=tol)


@paths
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", ["empty groups between",
                                  "sizes sum to less than the rows",
                                  "boundaries off the 8- and 16-row tiles"])
def test_gate_and_up_in_one_pass_are_the_two_products_and_silu(case, dtype,
                                                               path):
    xs, (g, u), sizes = _operands(case, dtype, mats=2)
    got = _f32(KERNELS[path](xs, (g, u), sizes))
    f32 = jnp.float32
    want = _f32(jax.nn.silu(lax.ragged_dot(xs, g, sizes,
                                           preferred_element_type=f32))
                * lax.ragged_dot(xs, u, sizes, preferred_element_type=f32))
    live = np.arange(xs.shape[0]) < int(sizes.sum())
    # the two products meet in float32 and are rounded once
    tol = 2e-5 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got[live], want[live], atol=tol, rtol=tol)
    assert not got[~live].any()


@paths
@pytest.mark.parametrize("mats", [1, 2], ids=["one matrix", "gate and up"])
def test_a_tail_of_garbage_rows_stays_out_of_the_groups(path, mats):
    """Rows behind the last group may hold anything finite or not: no
    group's product reads them into a row it owns (23 rows in groups:
    the last group and the tail share the second 16-row tile)."""
    xs, ws, sizes = _operands("sizes sum to less than the rows",
                              jnp.float32, mats=mats)
    held = int(sizes.sum())
    dirty = xs.at[held:].set(jnp.inf)
    got = KERNELS[path](dirty, ws, sizes)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(KERNELS[path](xs, ws, sizes)))
    assert not np.asarray(got)[held:].any()


def test_the_tiled_kernel_lists_a_row_tile_once_a_group_in_it():
    """``_visits``: 40 rows in tiles of 16, groups of 3, 0, 17, 9, 0
    and 2 rows and a tail of 9."""
    sizes = jnp.asarray(CASES["empty groups between"][3], jnp.int32)
    offsets, group, tile, fetch = grouped._visits(sizes, 40, 16)
    assert offsets.tolist() == [0, 3, 3, 20, 29, 29, 31, 40, 40]
    # tile 0: groups 0 and 2; tile 1: groups 2, 3, 5 and the tail
    # (6); tile 2: the tail; behind them an empty group on the same
    # tile and matrix
    assert tile.tolist() == [0, 0, 1, 1, 1, 1, 2, 2, 2]
    assert group.tolist() == [0, 2, 2, 3, 5, 6, 6, 7, 7]
    assert fetch.tolist() == [0, 2, 2, 3, 5, 5, 5, 5, 5]


@pytest.mark.parametrize("rows, groups, k, n, path", [
    (128, 64, 2048, 1024, "kernel"),        # OLMoE's step
    (128, 16, 4096, 4096, "kernel"),        # command-a-plus's step
    (640, 36, 4096, 768, "kernel"),         # granite-4.0-h's step
    (640, 36, 768, 4096, "kernel"),
    (128, 64, 2304, 896, "kernel"),         # Mellum2's step
    (128, 64, 896, 2304, "kernel"),
    (256, 12, 7168, 2048, "kernel"),        # Kimi's step
    (2816, 128, 1024, 2688, "kernel"),      # Nemotron-3-Super's step: the
    (2816, 128, 2688, 1024, "kernel"),      # latent rows up, and down
    (4096, 128, 1024, 2688, "tiled"),       # a run of its held prefill
    (4096, 128, 2688, 1024, "tiled"),
    (4096, 36, 4096, 768, "tiled"),         # a run of a held prefill
    (4096, 16, 4096, 4096, "tiled"),
    (4096, 12, 7168, 2048, "tiled"),
    (131072, 64, 2048, 1024, "tiled"),      # OLMoE's prompts
    (196608, 64, 2304, 896, "tiled"),       # Mellum2's prompts
    (196608, 64, 896, 2304, "tiled"),
])
def test_the_shape_rule_at_the_cells_shapes(rows, groups, k, n, path):
    names = [f"moe.grouped.{p}_products" for p in ("kernel", "tiled")]
    before = [REGISTRY.counter(name).value for name in names]
    assert grouped.takes_kernel(rows, groups, k, n, 2) is (path == "kernel")
    after = [REGISTRY.counter(name).value for name in names]
    assert [a - b for a, b in zip(after, before)] == [
        int(path == "kernel"), int(path == "tiled")]


@pytest.mark.parametrize("pairs_run", [4096, 64])
def test_the_held_dispatch_of_two_matrix_experts_on_latent_rows(
        monkeypatch, pairs_run):
    """``expert_dispatch_held`` with the two-matrix relu² expert under
    it (``grouped_mlp``: two grouped products, the squared relu between
    them) on rows a quarter as wide as the stream — 22 choices of 512
    experts a token, experts 128-255 held, in one run and in runs of 64
    pairs — against the pairs computed one by one; the sum comes back
    in the latent width."""
    monkeypatch.setattr(routed, "_HELD_RUN", pairs_run)
    rng = np.random.default_rng(3)
    f32 = jnp.float32
    t, k, r, h = 6, 22, 16, 24
    u = jnp.asarray(rng.normal(size=(t, r)), f32)
    eid = jnp.asarray(np.stack([rng.permutation(512)[:k] for _ in range(t)]))
    gate = jnp.asarray(rng.uniform(size=(t, k)), f32)
    ex = {"up": jnp.asarray(rng.normal(size=(128, r, h)), f32) / 4,
          "down": jnp.asarray(rng.normal(size=(128, h, r)), f32) / 5}
    got, sizes = jax.jit(lambda *a: expert_dispatch_held(
        *a, (128, 256), lambda xs, s: routed.grouped_mlp(
            xs, ex, s, "relu2")))(u, eid, gate)
    want = np.zeros((t, r), np.float32)
    held = 0
    for i in range(t):
        for j in range(k):
            e = int(eid[i, j]) - 128
            if 0 <= e < 128:
                held += 1
                a = jnp.square(jax.nn.relu(u[i] @ ex["up"][e]))
                want[i] += float(gate[i, j]) * np.asarray(a @ ex["down"][e])
    assert got.shape == (t, r) and int(sizes.sum()) == held > 0
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)
    with pytest.raises(ValueError, match="activation must be one of"):
        routed.grouped_mlp(u, ex, sizes, "gelu")


@pytest.mark.parametrize("pairs_run", [4096, 16])
def test_the_held_dispatch_on_the_kernel(monkeypatch, pairs_run):
    """``expert_dispatch_held`` end to end with the one SwiGLU under it —
    in one run, whose tail is no held expert's, and in runs of 16
    pairs — against the pairs computed one by one."""
    monkeypatch.setattr(routed, "_HELD_RUN", pairs_run)
    rng = np.random.default_rng(0)
    f32 = jnp.float32
    x = jnp.asarray(rng.normal(size=(24, 8)), f32)
    eid = jnp.asarray(np.stack([rng.permutation(16)[:4] for _ in range(24)]))
    gate = jnp.asarray(rng.uniform(size=(24, 4)), f32)
    ex = {"gate": jnp.asarray(rng.normal(size=(3, 8, 12)), f32),
          "up": jnp.asarray(rng.normal(size=(3, 8, 12)), f32),
          "down": jnp.asarray(rng.normal(size=(3, 12, 8)), f32)}
    asked = REGISTRY.counter("moe.grouped.kernel_products").value
    got, sizes = jax.jit(lambda *a: expert_dispatch_held(
        *a, (5, 8), lambda xs, s: grouped_swiglu(xs, ex, s)))(x, eid, gate)
    assert REGISTRY.counter("moe.grouped.kernel_products").value > asked
    want = np.zeros((24, 8), np.float32)
    for t in range(24):
        for j in range(4):
            e = int(eid[t, j]) - 5
            if 0 <= e < 3:
                a = jax.nn.silu(x[t] @ ex["gate"][e]) * (x[t] @ ex["up"][e])
                want[t] += float(gate[t, j]) * np.asarray(a @ ex["down"][e])
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)
    assert int(sizes.sum()) == int(((eid >= 5) & (eid < 8)).sum())


def _asked() -> np.ndarray:
    return np.array([REGISTRY.counter(f"moe.grouped.{name}_products").value
                     for name in ("kernel", "tiled")])


def _ring_programs(graph, plen):
    """The lowered text of a one-stage ring's decode and prefill
    program (``scripts/lowered_text_hashes.py`` lowers them so), each
    with what its lowering added to the rule's (kernel, tiled)
    counters."""
    params = graph.init(jax.random.key(0))
    dec = PipelinedDecoder(graph, params, num_stages=1, microbatch=2,
                           max_len=plen + 8)
    a, caches = dec._init_state()
    i32, u32, f32 = jnp.int32(0), jnp.uint32(0), jnp.float32(0)
    prompt = jnp.zeros((1, 2, plen), jnp.int32)
    before = _asked()
    decode = dec._get_decode_fn(2, False, None).lower(
        dec._w, prompt, i32, i32, i32, u32, f32,
        jnp.zeros((1, 2), jnp.int32), i32, i32, a, caches)
    between = _asked()
    prefill = dec._build_prefill_fn(plen, False, None).lower(
        dec._w, prompt, u32, f32, caches)
    return (decode.as_text(), between - before), \
        (prefill.as_text(), _asked() - between)


@pytest.mark.parametrize("family", [
    olmoe_tiny, cohere_moe_tiny, granite_hybrid_tiny, mellum_tiny,
    longcat_flash_tiny],
    ids=["olmoe", "command-a-plus", "granite-4.0-h", "mellum2",
         "longcat-flash"])
def test_a_step_takes_the_kernel_and_a_long_prompt_the_tiled_one(family):
    """The path is the product's static shape's: a step's few rows a
    group go to ``grouped_experts``, 512 positions' rows to
    ``grouped_rows``, and no program asks ``lax.ragged_dot``."""
    (decode, step), (prefill, prompt) = _ring_programs(
        family(seq_len=520), 512)
    assert "grouped_experts" in decode and "grouped_rows" not in decode
    assert step[0] > 0 and step[1] == 0
    assert "grouped_rows" in prefill and "grouped_experts" not in prefill
    assert prompt[0] == 0 and prompt[1] > 0
    assert "ragged" not in decode + prefill
