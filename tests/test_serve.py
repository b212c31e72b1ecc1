"""Serving front door: admission (WFQ/priorities/shedding), continuous
batching (chain + decode), request-scoped demux, and the edge cases the
admission layer must survive (greedy neighbors, shed-then-retry,
mid-decode disconnects).
"""

import json
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.models.gpt import gpt_tiny
from defer_tpu.obs import REGISTRY
from defer_tpu.plan import StageCostModel, max_batch_within_budget, \
    stage_ms_at_batch
from defer_tpu.runtime.node import ChainDispatcher, StageNode
from defer_tpu.serve import (AdmissionController, ContinuousBatchEngine,
                             DecodeRequest, ServeClient, TenantConfig,
                             WeightedFairQueue, poisson_trace)
from defer_tpu.serve import engine as engine_mod
from defer_tpu.serve.client import fetch_stats
from defer_tpu.serve.frontdoor import ChainBackend, ServeFrontDoor


# ---------------------------------------------------------------------------
# arrival traces
# ---------------------------------------------------------------------------

def test_poisson_trace_deterministic_and_bursty():
    a = poisson_trace(50.0, 4.0, seed=7, bursts=[(1.0, 2.0, 3.0)])
    b = poisson_trace(50.0, 4.0, seed=7, bursts=[(1.0, 2.0, 3.0)])
    assert a == b, "same seed must reproduce the same trace"
    assert a == sorted(a) and all(0 <= t < 4.0 for t in a)
    c = poisson_trace(50.0, 4.0, seed=8, bursts=[(1.0, 2.0, 3.0)])
    assert a != c
    # the burst window must actually run ~3x hot vs the steady phases
    in_burst = sum(1 for t in a if 1.0 <= t < 2.0)
    steady = sum(1 for t in a if t < 1.0 or t >= 2.0) / 3.0
    assert in_burst > 1.8 * steady, (in_burst, steady)


def test_poisson_trace_validates_phases():
    with pytest.raises(ValueError):
        poisson_trace(10, 1, bursts=[(0.5, 0.2, 2.0)])
    assert poisson_trace(0, 5) == []


# ---------------------------------------------------------------------------
# weighted-fair queuing
# ---------------------------------------------------------------------------

def test_wfq_fairness_bound_under_greedy_neighbor():
    """A greedy tenant pre-loading its whole queue cannot starve a
    steady neighbor: over any backlogged prefix the served counts track
    the weight ratio to within one unit per tenant (the SFQ bound)."""
    q = WeightedFairQueue()
    q.configure(TenantConfig("greedy", weight=1.0))
    q.configure(TenantConfig("steady", weight=1.0))
    for i in range(60):
        q.push("greedy", f"g{i}")  # the flood lands first
    for i in range(10):
        q.push("steady", f"s{i}")
    served = [q.pop()[0] for _ in range(70)]
    # while both are backlogged (first 20 pops), shares stay within the
    # fairness bound despite greedy's 60-deep head start
    for k in range(1, 21):
        g = served[:k].count("greedy")
        s = served[:k].count("steady")
        assert abs(g - s) <= 1, (k, g, s)


def test_wfq_weights_shape_the_share():
    q = WeightedFairQueue()
    q.configure(TenantConfig("heavy", weight=3.0))
    q.configure(TenantConfig("light", weight=1.0))
    for i in range(80):
        q.push("heavy", i)
        q.push("light", i)
    first = [q.pop()[0] for _ in range(40)]
    h, light = first.count("heavy"), first.count("light")
    # 3:1 weights -> ~3:1 service while both are backlogged
    assert 2.0 <= h / max(light, 1) <= 4.0, (h, light)


def test_wfq_strict_priority_preempts():
    q = WeightedFairQueue()
    q.configure(TenantConfig("bulk", weight=5.0, priority=0))
    q.configure(TenantConfig("interactive", weight=1.0, priority=1))
    for i in range(5):
        q.push("bulk", i)
    q.push("interactive", "now")
    assert q.pop()[0] == "interactive", \
        "higher priority level must drain first regardless of weights"
    assert q.pop()[0] == "bulk"


def test_wfq_reconfigure_moves_priority_level():
    """Review regression: re-configuring a tenant's priority must MOVE
    its queue (items included) to the new level, and a later drop must
    not corrupt the size accounting."""
    q = WeightedFairQueue()
    q.configure(TenantConfig("a", priority=0))
    q.configure(TenantConfig("b", priority=0))
    for i in range(3):
        q.push("a", i)
    q.push("b", "x")
    q.configure(TenantConfig("a", priority=1))  # promote mid-backlog
    assert q.pop()[0] == "a", "promoted tenant must drain first"
    assert q.qsize() == 3
    q.push("a", 99)  # new pushes land in the NEW level
    assert q.pop()[0] == "a"
    assert q.drop_tenant("a") == 2  # items 2 and 99 discarded
    assert q.qsize("a") == 0 and q.qsize() == 1  # b's unit intact
    assert q.pop() == ("b", "x") and q.qsize() == 0


def test_wfq_blocking_pop_and_drop_tenant():
    q = WeightedFairQueue()
    q.configure(TenantConfig("a"))
    assert q.pop(timeout=0.0) is None
    t = threading.Timer(0.05, lambda: q.push("a", 1))
    t.start()
    assert q.pop(timeout=2.0) == ("a", 1)
    q.push("a", 2)
    q.push("a", 3)
    assert q.drop_tenant("a") == 2 and q.qsize() == 0
    q.push("a", 4)  # still configured after the drop
    assert q.pop() == ("a", 4)


# ---------------------------------------------------------------------------
# admission / shedding
# ---------------------------------------------------------------------------

def test_admission_shed_then_retry_lifecycle():
    """The satellite lifecycle: admit while the prediction fits, shed
    with a retry hint when the backlog blows the deadline, admit again
    once completions drain the backlog."""
    # (a tenant of this test's own: the per-tenant counters live in the
    # process's registry, and another file's front door serves "t")
    ctl = AdmissionController(service_s=lambda: 0.1)
    ctl.configure(TenantConfig("lifecycle_t", deadline_ms=250.0))
    d1 = ctl.admit("lifecycle_t", "u1")
    d2 = ctl.admit("lifecycle_t", "u2")
    assert d1.admitted and d2.admitted
    d3 = ctl.admit("lifecycle_t", "u3")  # predicted (2+1)*0.1 = 0.3 > 0.25
    assert not d3.admitted and d3.reason == "deadline"
    assert d3.retry_after_s > 0 and d3.predicted_s > 0.25
    ctl.complete("lifecycle_t", queued_at=time.monotonic())
    d4 = ctl.admit("lifecycle_t", "u3-retry")  # backlog drained below the SLO
    assert d4.admitted
    stats = ctl.stats()
    assert stats["tenants"]["lifecycle_t"]["admitted"] == 3
    assert stats["tenants"]["lifecycle_t"]["shed"] == 1
    assert stats["tenants"]["lifecycle_t"]["completed"] == 1


def test_admission_backlog_cap_sheds_without_deadline():
    ctl = AdmissionController(service_s=lambda: 0.0)
    ctl.configure(TenantConfig("t", max_queued=2))
    assert ctl.admit("t", 1).admitted and ctl.admit("t", 2).admitted
    d = ctl.admit("t", 3)
    assert not d.admitted and d.reason == "backlog"


def test_admission_ewma_and_per_tenant_isolation():
    ctl = AdmissionController()
    ctl.configure(TenantConfig("slo", deadline_ms=50.0))
    ctl.configure(TenantConfig("besteffort"))  # no deadline: never SLO-shed
    ctl.observe_service(0.2)
    assert ctl.service_estimate_s() == pytest.approx(0.2)
    assert not ctl.admit("slo", 1).admitted      # 0.2s >> 50ms
    assert ctl.admit("besteffort", 1).admitted   # deadline-free rides on


# ---------------------------------------------------------------------------
# latency-budget queries (plan/)
# ---------------------------------------------------------------------------

def test_latency_budget_width_query():
    g = resnet_tiny()
    stages = partition(g, num_stages=3)
    cuts = [s.output_name for s in stages[:-1]]
    cm = StageCostModel(g, batch=1)
    ms1 = max(stage_ms_at_batch(g, cuts, cm, 1))
    ms8 = max(stage_ms_at_batch(g, cuts, cm, 8))
    assert ms8 > ms1 > 0, "stage time must grow with batch"
    assert max_batch_within_budget(g, cuts, cm, ms1 * 0.5) == 1, \
        "a budget below the single-sample cost degrades to width 1"
    w = max_batch_within_budget(g, cuts, cm, ms8, cap=64)
    assert 8 <= w <= 64
    assert max(stage_ms_at_batch(g, cuts, cm, w)) <= ms8 + 1e-9
    big = max_batch_within_budget(g, cuts, cm, 1e9, cap=16)
    assert big == 16, "an unbounded budget saturates the cap"


def test_latency_budget_scales_measured_costs():
    g = resnet_tiny()
    stages = partition(g, num_stages=2)
    cuts = [s.output_name for s in stages[:-1]]
    node_costs = {n: 1e-4 for n in g.topo_order}
    cm = StageCostModel(g, batch=1, node_costs=node_costs)
    ms2 = stage_ms_at_batch(g, cuts, cm, 2)
    ms1 = stage_ms_at_batch(g, cuts, cm, 1)
    # measured costs scale linearly with the candidate batch
    assert max(ms2) == pytest.approx(2 * max(ms1), rel=0.2)


# ---------------------------------------------------------------------------
# continuous-batching decode engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gpt_setup():
    g = gpt_tiny()
    return g, g.init(jax.random.key(0))


def _prompts(n, rng):
    return [rng.integers(0, 97, (int(p),)).astype(np.int32)
            for p in rng.integers(2, 6, n)]


def _prefill_positions(monkeypatch, positions):
    """Engines built from here on prefill ``positions`` of a prompt in
    one call (0: none, every prompt token is fed to a step)."""
    monkeypatch.setattr(engine_mod, "PREFILL_POSITIONS", positions)


@pytest.mark.parametrize("positions", [128, 2, 0],
                         ids=["prefill", "prefill-and-tail", "forced"])
def test_engine_byte_identity_solo_vs_continuous(gpt_setup, monkeypatch,
                                                 positions):
    """The correctness bar: per-request outputs byte-identical to the
    request run alone, with requests JOINING AT DIFFERENT STEPS (true
    continuous batching, not lockstep), greedy and sampled rows mixed.
    A joining request's prefill call lands between the other slots'
    steps and touches its own rows only; so does a prompt whose tail is
    teacher-forced behind a short prefill."""
    _prefill_positions(monkeypatch, positions)
    g, params = gpt_setup
    rng = np.random.default_rng(3)
    prompts = _prompts(3, rng)

    def make_reqs():
        return [DecodeRequest(prompt=p, max_new_tokens=5, request_id=i,
                              seed=100 + i,
                              temperature=0.8 if i == 1 else 0.0)
                for i, p in enumerate(prompts)]

    solo = {}
    for req in make_reqs():
        eng = ContinuousBatchEngine(g, params, num_stages=2, width=3)
        solo[req.request_id] = eng.run_all([req])[req.request_id]

    eng = ContinuousBatchEngine(g, params, num_stages=2, width=3)

    def stagger(e, queue):
        while queue and e.free_slots() \
                and e.steps >= 3 * queue[0].request_id:
            e.join(queue.pop(0))

    fills0 = REGISTRY.histogram("serve.decode.prefill_s").count
    batched = eng.run_all(make_reqs(), joiner=stagger)
    for rid, ids in solo.items():
        np.testing.assert_array_equal(batched[rid], ids)
    # every prompt here has two tokens or more: one call a request
    assert REGISTRY.histogram("serve.decode.prefill_s").count \
        - fills0 == (3 if positions else 0)


def test_engine_cancel_reclaims_slot_others_unaffected(gpt_setup):
    """A client disconnecting mid-decode: its slot is reclaimed (a new
    request joins into it) and the surviving request's output is
    byte-identical to an undisturbed run."""
    g, params = gpt_setup
    rng = np.random.default_rng(4)
    p_victim, p_survivor, p_late = _prompts(3, rng)
    solo_eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    survivor_solo = solo_eng.run_all(
        [DecodeRequest(prompt=p_survivor, max_new_tokens=6,
                       request_id=1)])[1]

    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    victim = DecodeRequest(prompt=p_victim, max_new_tokens=10,
                           request_id=0)
    survivor = DecodeRequest(prompt=p_survivor, max_new_tokens=6,
                             request_id=1)
    seen = []
    victim.on_done = seen.append
    assert eng.join(victim) and eng.join(survivor)
    assert eng.free_slots() == 0
    for _ in range(3):
        eng.step()
    assert eng.cancel(victim)
    assert seen == [None], "cancellation must signal on_done(None)"
    assert eng.free_slots() == 1, "the KV slot must be reclaimed"
    late = DecodeRequest(prompt=p_late, max_new_tokens=2, request_id=2)
    assert eng.join(late), "a new request must fit the reclaimed slot"
    out = {}
    while eng.active():
        for req, ids in eng.step():
            out[req.request_id] = ids
    np.testing.assert_array_equal(out[1], survivor_solo)
    assert 2 in out and eng.free_slots() == 2


def _oracle_run(g, params, tokens, max_len, new=0):
    """The oracle: ``CausalTransformerBlock.decode`` (the composition
    over one cache item) looped over ``tokens`` fed one a position,
    then over its own greedy ids until ``new`` are generated, against
    one sequence's buffers a block.  Returns all the tokens, the
    greedy next id after each fed position, and the final caches."""
    from defer_tpu.ops.kv_cache import KVCacheFormat
    blocks = [nm for nm in g.topo_order if nm.startswith("block_")]
    op0 = g.nodes[blocks[0]].op
    d = params["embeddings"]["wte"].shape[1]
    fmt = KVCacheFormat(op0.kv_heads, d // op0.num_heads, max_len,
                        jnp.float32)
    caches = {nm: fmt.layer(fmt.zeros(1, 1), 0) for nm in blocks}

    @jax.jit
    def one(caches, tok, pos):
        x = (params["embeddings"]["wte"][tok]
             + params["embeddings"]["wpe"][pos])[None]
        out = {}
        for nm in blocks:
            x, out[nm] = g.nodes[nm].op.decode(params[nm], x, caches[nm],
                                               pos, fmt)
        h = g.nodes["final_ln"].op.apply(params["final_ln"], x)
        logits = g.nodes["lm_head"].op.apply(params["lm_head"], h)
        return jnp.argmax(logits[0]), out

    toks, nxt = [int(t) for t in tokens], []
    want = len(toks) + new
    while len(nxt) < len(toks):
        pos = len(nxt)
        i, caches = one(caches, jnp.int32(toks[pos]), jnp.int32(pos))
        nxt.append(int(i))
        if pos + 1 == len(toks) < want:
            toks.append(int(i))
    return np.asarray(toks, np.int64), nxt, caches


def test_engine_slots_at_different_positions_match_solo_and_oracle(
        gpt_setup):
    """Slots sit at DIFFERENT positions in every step (unequal prompts,
    joined at different steps): each slot's row lands at its own
    position of each layer's buffer, and every answer equals the
    request run alone and the one-item oracle."""
    g, params = gpt_setup
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
               for n in (3, 7, 5)]
    join_at = {0: 0, 1: 2, 2: 5}

    def make_reqs():
        return [DecodeRequest(prompt=p, max_new_tokens=6, request_id=i)
                for i, p in enumerate(prompts)]

    eng = ContinuousBatchEngine(g, params, num_stages=2, width=3)
    seen_pos = []

    def stagger(e, queue):
        while queue and e.steps >= join_at[queue[0].request_id]:
            e.join(queue.pop(0))
        seen_pos.append(tuple(s.pos for s in e._slots if s is not None))

    batched = eng.run_all(make_reqs(), joiner=stagger)
    assert any(len(set(ps)) == 3 for ps in seen_pos), \
        "the test must hold three slots at three positions in one step"
    for req in make_reqs():
        solo = ContinuousBatchEngine(g, params, num_stages=2, width=3)
        want = solo.run_all([req])[req.request_id]
        np.testing.assert_array_equal(batched[req.request_id], want)
        np.testing.assert_array_equal(
            want, _oracle_run(g, params, req.prompt, eng.max_len, new=6)[0])


@pytest.mark.parametrize("positions", [128, 4],
                         ids=["padded-over", "padded-and-stale"])
@pytest.mark.parametrize("leaves_by", ["cancel", "finished"])
def test_engine_recycled_slot_ignores_previous_tenants_rows(
        gpt_setup, monkeypatch, leaves_by, positions):
    """A slot is never zeroed between tenants, and the next tenant's
    prefill writes rows for its padding: behind the next tenant's
    positions the buffers hold rows that are not its own — the padding's
    up to the prefill's length, the longer previous tenant's from there
    on — and its answer equals its solo run and the one-item oracle,
    which has neither."""
    _prefill_positions(monkeypatch, positions)
    g, params = gpt_setup
    rng = np.random.default_rng(12)
    p_long = rng.integers(0, 97, (8,)).astype(np.int32)
    p_next = rng.integers(0, 97, (3,)).astype(np.int32)
    solo = ContinuousBatchEngine(g, params, num_stages=1, width=1).run_all(
        [DecodeRequest(prompt=p_next, max_new_tokens=3, request_id=7)])[7]
    np.testing.assert_array_equal(
        solo, _oracle_run(g, params, p_next, 16, new=3)[0])

    eng = ContinuousBatchEngine(g, params, num_stages=1, width=1)
    first = DecodeRequest(prompt=p_long, max_new_tokens=7, request_id=0)
    assert eng.join(first)
    # the first tenant feeds positions 0..13, all but the prefilled to
    # a step each
    to_go = 14 - eng._slots[0].prefill
    if leaves_by == "cancel":
        for _ in range(to_go - 2):
            eng.step()
        assert eng.cancel(first)
    else:
        while eng.active():
            eng.step()
        assert eng.steps == to_go
    assert eng.free_slots() == 1
    got = eng.run_all(
        [DecodeRequest(prompt=p_next, max_new_tokens=3, request_id=7)])[7]
    np.testing.assert_array_equal(got, solo)
    # the next tenant fed positions 0..4; rows 5..11 are still there,
    # the first tenant's or the padding's
    stale = np.asarray(eng._caches["k"][0])[0, :, 5:12]
    assert np.abs(stale).min(axis=(0, 2)).all()


@pytest.mark.parametrize("layers", [8, 3], ids=["one-group", "groups-3+1"])
@pytest.mark.parametrize("plen", [1, 2, 4, 5, 11],
                         ids=["1", "2", "L", "L+1", "L+7"])
def test_engine_prefilled_rows_are_the_teacher_forced_rows(
        gpt_setup, monkeypatch, plen, layers):
    """One prefill of L = 4 positions against the token-a-step path:
    the slot's rows agree within float32 rounding (a whole-prompt
    product against ``plen`` one-row products), the generated tokens are
    the same, and a prompt longer than L + 1 is served by prefill plus a
    forced tail.  Another slot rides along at its own positions.  The
    four blocks go through one program, or through one of three blocks
    and one of the last."""
    monkeypatch.setattr(engine_mod, "PREFILL_LAYERS", layers)
    g, params = gpt_setup
    rng = np.random.default_rng(21)
    prompt = rng.integers(1, 97, (plen,)).astype(np.int32)
    other = rng.integers(1, 97, (3,)).astype(np.int32)

    def run(eng):
        out = eng.run_all([
            DecodeRequest(prompt=other, max_new_tokens=2, request_id=0),
            DecodeRequest(prompt=prompt, max_new_tokens=4, request_id=1)])
        return out[1], eng

    _prefill_positions(monkeypatch, 0)      # every token to a step
    want, forced = run(ContinuousBatchEngine(g, params, num_stages=2,
                                             width=2))
    assert forced.prefill_len == 0
    _prefill_positions(monkeypatch, 4)
    fills0 = REGISTRY.histogram("serve.decode.prefill_s").count
    got, eng = run(ContinuousBatchEngine(g, params, num_stages=2, width=2))
    assert eng.prefill_len == 4
    np.testing.assert_array_equal(got, want)
    assert eng.steps == 4 + plen - 1 - min(plen - 1, 4)
    assert forced.steps == 4 + plen - 1
    # the other request's call, and this one's unless it has one token
    assert REGISTRY.histogram("serve.decode.prefill_s").count \
        - fills0 == 1 + (plen > 1)
    live = plen + 4 - 1         # positions 0..live-1 were fed
    for side in ("k", "v"):
        for a, b in zip(eng._caches[side], forced._caches[side]):
            a, b = np.asarray(a)[1, :, :live], np.asarray(b)[1, :, :live]
            np.testing.assert_allclose(a, b, atol=1e-5)
            assert np.abs(a).max() > 0


def test_engine_cancel_between_join_and_first_step_frees_the_slot(
        gpt_setup):
    """A request cancelled before the step that follows its join has
    run nothing: no prefill call, its slot free, ``on_done(None)``; the
    slot's next tenant is served as if it had never been there."""
    g, params = gpt_setup
    rng = np.random.default_rng(22)
    p_gone, p_next = (rng.integers(0, 97, (n,)).astype(np.int32)
                      for n in (6, 4))
    solo = ContinuousBatchEngine(g, params, num_stages=2, width=1).run_all(
        [DecodeRequest(prompt=p_next, max_new_tokens=3, request_id=1)])[1]
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=1)
    seen = []
    gone = DecodeRequest(prompt=p_gone, max_new_tokens=3, on_done=seen.append)
    fills = REGISTRY.histogram("serve.decode.prefill_s")
    fills0 = fills.count
    assert eng.join(gone) and eng._slots[0].prefill == 5
    assert eng.cancel(gone)
    assert seen == [None] and eng.free_slots() == 1
    assert eng.step() == [] and eng.steps == 0
    assert fills.count == fills0
    assert not np.asarray(eng._caches["k"][0]).any()
    got = eng.run_all(
        [DecodeRequest(prompt=p_next, max_new_tokens=3, request_id=1)])[1]
    np.testing.assert_array_equal(got, solo)
    assert fills.count == fills0 + 1


@pytest.mark.parametrize("positions", [128, 4, 0],
                         ids=["prefill", "prefill-and-tail", "forced"])
def test_engine_counts_prompt_tokens_prefilled_and_forced(
        gpt_setup, monkeypatch, positions):
    """``serve.decode.prompt_tokens_prefilled`` + ``_forced`` = every
    prompt token served; with a prefill as long as the prompts, one
    forced a request: its last prompt token, which the first step
    takes."""
    _prefill_positions(monkeypatch, positions)
    g, params = gpt_setup
    rng = np.random.default_rng(23)
    plens = [1, 2, 5, 9, 6]
    reqs = [DecodeRequest(prompt=rng.integers(0, 97, (n,)),
                          max_new_tokens=3, request_id=i)
            for i, n in enumerate(plens)]
    counters = [REGISTRY.counter(f"serve.decode.{name}")
                for name in ("prompt_tokens_prefilled",
                             "prompt_tokens_forced", "tokens")]
    before = [c.value for c in counters]
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    assert len(eng.run_all(reqs)) == len(plens)
    prefilled, forced, tokens = (c.value - b
                                 for c, b in zip(counters, before))
    assert prefilled + forced == sum(plens)
    assert prefilled == sum(min(n - 1, positions) for n in plens)
    if positions == 128:
        assert forced == len(plens)
    assert tokens == 3 * len(plens)


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk_eqns(sub)


def test_engine_step_holds_a_buffer_a_layer_and_writes_rows_in_place(
        gpt_setup):
    """Structure of the step program: ``n_layer`` cache buffers a side,
    every one donated and aliased to its output; no scatter (a vmapped
    row write is one: docs/DECODE_CLIFF.md) and nothing of the stacked
    ``[n_layer, width, ...]`` shape."""
    g, params = gpt_setup
    w, n_layer = 3, 4
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=w)
    buffers = eng.kv_format.buffers(w)
    assert set(eng._caches) == set(buffers) == {"k", "v"}
    for side, buf in buffers.items():
        assert [b.shape for b in eng._caches[side]] == [buf.shape] * n_layer
    args = (eng.params, eng._caches, eng._prev_ids, *eng._blank_rows())
    for sample in (False, True):
        traced = eng._step_fn(sample).trace(*args)
        prims = []
        for eqn in _walk_eqns(traced.jaxpr.jaxpr):
            prims.append(eqn.primitive.name)
            for out in eqn.outvars:
                shape = tuple(getattr(out.aval, "shape", ()))
                assert shape[:2] != (n_layer, w) or len(shape) != 5, \
                    f"{eqn.primitive.name} makes a stacked cache {shape}"
        assert not any("scatter" in p for p in prims), set(prims)
        # one aliased row-writer call a buffer and one attention a
        # layer (ops/kv_cache.py)
        assert prims.count("pallas_call") == 3 * n_layer
        # the CPU does not donate, so read the lowering: every cache
        # buffer argument names the output it aliases
        text = traced.lower().as_text()
        assert text.count("tf.aliasing_output") == 2 * n_layer, \
            text.count("tf.aliasing_output")


def test_engine_writes_and_reads_the_last_position(gpt_setup):
    """``pos == max_len - 1``: the row lands in the buffer's last
    position and the attention reads it."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    last = eng.max_len - 1
    tokens = np.random.default_rng(13).integers(0, 97, (eng.max_len,))
    _, want, oracle_caches = _oracle_run(g, params, tokens, eng.max_len)
    step = eng._step_fn(False)
    caches = eng._caches
    _, from_host, _, seeds, temps, live = eng._blank_rows([0, 1])
    for pos, tok in enumerate(tokens):
        # slot 1 rides along at another position, with another token
        ids, caches = step(eng.params, caches, eng._prev_ids,
                           np.asarray([tok, 5], np.int32), from_host,
                           np.asarray([pos, last - pos], np.int32),
                           seeds, temps, live)
        assert int(ids[0]) == want[pos], pos
    for side in ("k", "v"):
        got = np.asarray(caches[side][0])[0]
        # one row against two: the products round differently
        np.testing.assert_allclose(
            got, np.asarray(oracle_caches["block_0"][side])[0], atol=1e-5)
        assert np.abs(got[:, last]).max() > 0


def test_engine_validates_requests(gpt_setup):
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=1)
    with pytest.raises(ValueError, match="max_len"):
        eng.join(DecodeRequest(prompt=np.arange(10), max_new_tokens=99))
    with pytest.raises(ValueError, match="at least one token"):
        DecodeRequest(prompt=np.zeros((0,)), max_new_tokens=1)
    assert eng.join(DecodeRequest(prompt=np.arange(3), max_new_tokens=1))
    assert not eng.join(
        DecodeRequest(prompt=np.arange(3), max_new_tokens=1)), \
        "a full batch refuses joins until a slot frees"


# -- one step launched ahead of the one whose tokens are read --------------

_STEP_PHASES = ("gather", "dispatch", "upload", "launch", "device", "sync",
                "delivery")


def _decode_counts():
    """(steps read, launched ahead, tokens, then one count a phase)."""
    return [REGISTRY.histogram("serve.decode.step_s").count,
            REGISTRY.counter("serve.decode.ahead.launched").value,
            REGISTRY.counter("serve.decode.tokens").value,
            *(REGISTRY.histogram(f"serve.decode.{p}_s").count
              for p in _STEP_PHASES)]


def _since(before):
    return [a - b for a, b in zip(_decode_counts(), before)]


@pytest.mark.parametrize("temperature", [0.0, 0.8],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("plen", [1, 2, 4, 5, 11],
                         ids=["1", "2", "L", "L+1", "L+7"])
def test_engine_launched_ahead_answers_equal_the_request_run_alone(
        gpt_setup, monkeypatch, plen, temperature):
    """With a step always in flight, a request that joins beside two
    others at a later step answers as it does alone: past its prompt a
    slot's id comes from the device array the step before returned, and
    a prompt token (its last; a tail longer than the prefill of L = 4
    positions) from the host, behind ``from_host``."""
    _prefill_positions(monkeypatch, 4)
    g, params = gpt_setup
    rng = np.random.default_rng(31 + plen)
    prompts = [rng.integers(0, 97, (n,)).astype(np.int32)
               for n in (3, plen, 6)]

    def make_reqs():
        return [DecodeRequest(prompt=p, max_new_tokens=4, request_id=i,
                              seed=7 + i,
                              temperature=temperature if i == 1 else
                              (0.0 if i == 0 else 0.6))
                for i, p in enumerate(prompts)]

    solo = {}
    for req in make_reqs():
        eng = ContinuousBatchEngine(g, params, num_stages=2, width=3, top_k=5)
        solo[req.request_id] = eng.run_all([req])[req.request_id]
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=3, top_k=5)

    def stagger(e, queue):
        while queue and e.steps >= 2 * queue[0].request_id:
            e.join(queue.pop(0))

    before = _decode_counts()
    batched = eng.run_all(make_reqs(), joiner=stagger)
    for rid, ids in solo.items():
        np.testing.assert_array_equal(batched[rid], ids)
    steps, ahead, tokens, *phases = _since(before)
    # only the first launch found nothing in flight
    assert steps == eng.steps and ahead == steps - 1
    assert tokens == 3 * 4 and phases == [steps] * len(_STEP_PHASES)
    assert eng._flight is None


@pytest.mark.parametrize("joined,long", [
    (1, [0]), (16, [2, 7, 15]), (16, list(range(16)))],
    ids=["1-of-16", "3-of-16", "16-of-16"])
def test_engine_answers_at_any_number_of_live_slots_equal_the_request_alone(
        gpt_setup, joined, long):
    """The step's cache kernels visit the live slots only: with one slot
    of 16 live, with three apart (13 neighbours finished and idle, their
    rows stale) and with all 16, every request — greedy and sampled —
    answers bit for bit as it does alone, and the engine did launch a
    step for exactly the slots in question."""
    g, params = gpt_setup
    rng = np.random.default_rng(53)
    prompts = _prompts(joined, rng)

    def make_reqs():
        return [DecodeRequest(prompt=p, request_id=i, seed=11 + i,
                              max_new_tokens=7 if i in long else 2,
                              temperature=0.7 * (i % 2))
                for i, p in enumerate(prompts)]

    eng = ContinuousBatchEngine(g, params, num_stages=2, width=16, top_k=5)
    solo = {req.request_id: eng.run_all([req])[req.request_id]
            for req in make_reqs()}
    visited = []
    launch = eng._launch

    def watched(rows):
        visited.append([i for i, _ in rows])
        return launch(rows)

    eng._launch = watched
    batched = eng.run_all(make_reqs())
    assert list(range(joined)) in visited and long in visited
    for rid, ids in solo.items():
        np.testing.assert_array_equal(batched[rid], ids)


def test_engine_counts_the_live_slots_it_launches(gpt_setup):
    """``serve.decode.rows.launched`` rises by ``len(rows)`` a launch —
    the slots the step's cache kernels visit — and the list the step is
    handed names those slots, padded by the last, with their count."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=4)
    reqs = [DecodeRequest(prompt=np.arange(2 + i), max_new_tokens=n,
                          request_id=i) for i, n in enumerate((2, 5, 3))]
    for req in reqs:
        assert eng.join(req)
    rows = REGISTRY.counter("serve.decode.rows.launched")
    steps = REGISTRY.histogram("serve.decode.step_s")
    rows0, steps0 = rows.value, steps.count
    lists = []
    step_fn = eng._step_fn

    def watched(sample):
        def call(*args):
            lists.append(np.asarray(args[-1]).tolist())
            return step_fn(sample)(*args)
        return call

    eng._step_fn = watched
    while eng.active():
        eng.step()
    # 2, 5 and 3 steps: [0 1 2] [0 1 2] [1 2] [1] [1]
    assert lists == [[0, 1, 2, 2, 3], [0, 1, 2, 2, 3], [1, 2, 2, 2, 2],
                     [1, 1, 1, 1, 1], [1, 1, 1, 1, 1]]
    assert rows.value - rows0 == 2 + 5 + 3
    assert steps.count - steps0 == 5


def test_engine_launches_step_n_plus_1_before_it_reads_step_n(
        gpt_setup, traced):
    """Order of the spans of a steady run: ``launch(0)``, then
    ``launch(n + 1)`` in front of ``sync(n)`` (``ahead`` 1: the device
    runs under the wait), and the last ``sync`` with nothing behind it.
    ``serve.decode.ahead.launched`` counts the launches that found a
    step unread; ``step()`` has answers only from its second call on."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    req = DecodeRequest(prompt=np.arange(3), max_new_tokens=5)
    assert eng.join(req)
    before = _decode_counts()
    assert eng.step() == [] and eng.steps == 0      # launched, not read
    assert eng._flight is not None and _since(before)[:2] == [0, 0]
    calls = 1
    while eng.active():
        done = eng.step()
        calls += 1
    assert [r for r, _ in done] == [req]
    # 5 tokens are 5 steps (the prompt went through the prefill), read
    # by calls 2..6; the last call launched nothing
    assert (eng.steps, calls) == (5, 6)
    assert _since(before)[:2] == [5, 4]
    order = [(s["name"], s["args"].get("ahead"))
             for s in sorted(traced.spans, key=lambda s: s["ts_us"])
             if s["name"] in ("engine.launch", "engine.sync")]
    assert order == [("engine.launch", None)] \
        + [("engine.launch", None), ("engine.sync", 1)] * 4 \
        + [("engine.sync", 0)]
    roots = [s for s in traced.spans if s["name"] == "engine.step"]
    assert sorted(s["args"]["step"] for s in roots) == list(range(5))


def test_engine_slot_at_its_last_token_is_not_launched_ahead(gpt_setup):
    """Which slots a step holds is known before any token of the step
    in front of it: a slot whose last step is in flight is left out of
    the next launch (its row is an idle slot's), nothing is discarded,
    and ``serve.decode.tokens`` is the sum of the answers."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    short = DecodeRequest(prompt=np.arange(4), max_new_tokens=2,
                          request_id=0)
    long = DecodeRequest(prompt=np.arange(5), max_new_tokens=5,
                         request_id=1)
    assert eng.join(short) and eng.join(long)
    before = _decode_counts()
    held = []
    out = {}
    while eng.active():
        for r, ids in eng.step():
            out[r.request_id] = ids
        if eng._flight is not None:
            held.append(sorted(s.req.request_id
                               for _i, s, _p in eng._flight.rows))
    assert held == [[0, 1], [0, 1], [1], [1], [1]]
    assert [out[i].size for i in (0, 1)] == [4 + 2, 5 + 5]
    steps, ahead, tokens, *_ = _since(before)
    assert (steps, ahead, tokens) == (5, 4, 2 + 5)


def test_engine_on_done_fires_in_the_call_that_reads_the_last_token(
        gpt_setup):
    """Not a call later: the delivery of a request's last token frees
    its slot and calls ``on_done`` with the ids ``step()`` returns."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    seen = []
    req = DecodeRequest(prompt=np.arange(3), max_new_tokens=3,
                        on_done=seen.append)
    other = DecodeRequest(prompt=np.arange(2), max_new_tokens=6)
    assert eng.join(req) and eng.join(other)
    returned = []
    for call in range(1, 5):
        assert not seen
        returned = eng.step()
    # three tokens: launched by calls 1-3, the third read by call 4
    assert call == 4 and len(seen) == 1
    assert [r for r, _ in returned] == [req] and returned[0][1] is seen[0]
    assert eng.free_slots() == 1 and eng._flight is not None
    assert [s.req for _i, s, _p in eng._flight.rows] == [other]


@pytest.mark.parametrize("temperature", [0.0, 0.9],
                         ids=["greedy", "sampled"])
def test_engine_cancel_and_rejoin_while_a_step_is_in_flight(
        gpt_setup, temperature):
    """A request cancelled between two calls leaves a row in the step in
    flight; a newcomer joins into the freed slot while that step is
    still unread.  The stale token is dropped at delivery (the flight
    holds the slot object: the index is the newcomer's by then), the
    cancelled request hears ``None`` once, the newcomer's answer equals
    its solo run and the bystander's is undisturbed — the last tenant's
    rows, which no step has touched since it left the list of live
    slots, are each rewritten before the newcomer reads them."""
    g, params = gpt_setup
    rng = np.random.default_rng(41)
    p_gone, p_stay, p_new = (rng.integers(0, 97, (n,)).astype(np.int32)
                             for n in (5, 3, 4))

    def fresh(rid):
        prompt = {1: p_stay, 2: p_new}[rid]
        return DecodeRequest(prompt=prompt, max_new_tokens=6,
                             request_id=rid, seed=rid,
                             temperature=temperature)

    solo = {rid: ContinuousBatchEngine(
        g, params, num_stages=2, width=2).run_all([fresh(rid)])[rid]
        for rid in (1, 2)}
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    heard = []
    gone = DecodeRequest(prompt=p_gone, max_new_tokens=9, request_id=0,
                         on_done=heard.append)
    assert eng.join(gone) and eng.join(fresh(1))
    for _ in range(3):
        eng.step()
    flight = eng._flight
    assert [s.req.request_id for _i, s, _p in flight.rows] == [0, 1]
    tokens0 = REGISTRY.counter("serve.decode.tokens").value
    assert eng.cancel(gone) and heard == [None]
    assert eng._flight is flight, "a live slot is left: nothing is read"
    assert eng.join(fresh(2)) and eng._slots[0].req.request_id == 2
    out = {}
    for r, ids in eng.step():               # reads the stale step
        out[r.request_id] = ids
    # of its two rows only the bystander's token counted
    assert REGISTRY.counter("serve.decode.tokens").value == tokens0 + 1
    assert eng._slots[0].out == [] and eng._slots[0].pos == p_new.size
    out.update(eng.run_all([]))
    assert heard == [None] and not eng.cancel(gone)
    for rid in (1, 2):
        np.testing.assert_array_equal(out[rid], solo[rid])


def test_engine_join_while_a_step_is_queued_takes_part_from_the_next(
        gpt_setup):
    """A request that joins between two calls is in no row of the step
    in flight: its prefill and its first step go behind it."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    first = DecodeRequest(prompt=np.arange(3), max_new_tokens=8,
                          request_id=0)
    late = DecodeRequest(prompt=np.arange(1, 6), max_new_tokens=2,
                         request_id=1)
    solo = ContinuousBatchEngine(g, params, num_stages=2, width=2).run_all(
        [DecodeRequest(prompt=late.prompt, max_new_tokens=2,
                       request_id=1)])[1]
    assert eng.join(first)
    eng.step()
    eng.step()
    in_flight = eng._flight
    fills = REGISTRY.histogram("serve.decode.prefill_s")
    fills0 = fills.count
    assert eng.join(late) and eng._slots[1].prefill == 4
    assert [s.req for _i, s, _p in in_flight.rows] == [first]
    eng.step()              # prefills, launches both, reads ``in_flight``
    assert fills.count == fills0 + 1 and eng._slots[1].out == []
    assert [(s.req.request_id, p) for _i, s, p in eng._flight.rows] \
        == [(0, 4), (1, 4)]
    np.testing.assert_array_equal(eng.run_all([])[1], solo)


def test_engine_drains_the_step_in_flight(gpt_setup):
    """``run_all`` returns every answer with nothing left in flight; a
    cancellation that empties the engine reads the step in flight there
    and then (no token counted, every phase closed), so a caller that
    parks leaves nothing unread; ``drain`` with nothing in flight is a
    no-op."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    reqs = [DecodeRequest(prompt=np.arange(2 + i), max_new_tokens=3 + i,
                          request_id=i) for i in range(4)]
    out = eng.run_all(reqs)
    assert sorted(out) == [0, 1, 2, 3] and eng._flight is None
    assert [out[i].size for i in range(4)] == [2 * i + 5 for i in range(4)]
    assert eng.drain() == [] and eng.step() == []
    heard = []
    lone = DecodeRequest(prompt=np.arange(4), max_new_tokens=9,
                         on_done=heard.append)
    assert eng.join(lone)
    eng.step()
    eng.step()
    before = _decode_counts()
    assert eng._flight is not None and eng.cancel(lone)
    assert heard == [None] and eng._flight is None and eng.active() == 0
    steps, ahead, tokens, *phases = _since(before)
    assert (steps, ahead, tokens) == (1, 0, 0)
    # the step read had been gathered and launched a call earlier
    assert phases == [0, 0, 0, 0, 1, 1, 1]
    assert eng.step() == []


def test_engine_loop_stop_reads_the_step_in_flight(gpt_setup):
    """``EngineLoop.stop`` with a request mid-answer: the loop ends with
    no error and nothing in flight, and every launched step was read
    (``step_s`` and the phases count alike)."""
    g, params = gpt_setup
    engine = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    door = ServeFrontDoor(engine=engine,
                          decode_defaults={"max_new_tokens": 12}).start()
    host, port = door.address
    ServeClient(host, port, "t", max_new_tokens=2).stream(
        [np.arange(3, dtype=np.int32)])         # compiled
    # the client has its answer from *inside* the last step's delivery
    # span (``on_done``), which the loop's thread closes after: count
    # from a loop gone quiet, or that span lands in the window below
    before = _decode_counts()
    quiet = time.monotonic() + 5
    while time.monotonic() < quiet:
        time.sleep(0.02)
        was, before = before, _decode_counts()
        if was == before:
            break
    read = engine.steps         # the first request's: 3 more are this one's
    def ask():      # its answer never comes: the door is stopped first
        with pytest.raises(ConnectionError):
            ServeClient(host, port, "t", max_new_tokens=12).stream(
                [np.arange(4, dtype=np.int32)])

    client = threading.Thread(target=ask, daemon=True)
    client.start()
    deadline = time.monotonic() + 20
    while engine.steps < read + 3 and time.monotonic() < deadline:
        time.sleep(0.001)
    loop = door._engine_loop
    loop.stop()
    loop.join(10)
    assert not loop.is_alive() and loop.error is None
    assert engine._flight is None
    steps, _ahead, _tokens, *phases = _since(before)
    assert steps >= 3 and phases == [steps] * len(_STEP_PHASES)
    door.stop()
    client.join(10)


def test_engine_keeps_one_step_program_a_sample_value(gpt_setup):
    """Reading the ids from the device took no second program: one
    ``jit_step`` for greedy batches, one for batches that sample, each
    compiled once however the slots' ids are owned."""
    g, params = gpt_setup
    eng = ContinuousBatchEngine(g, params, num_stages=2, width=2)
    reqs = [DecodeRequest(prompt=np.arange(1 + 3 * i), max_new_tokens=4,
                          request_id=i, temperature=0.5 * (i >= 2))
            for i in range(4)]
    assert len(eng.run_all(reqs)) == 4
    assert sorted(eng._step_fns) == [False, True]
    assert [fn._cache_size() for fn in eng._step_fns.values()] == [1, 1]


# ---------------------------------------------------------------------------
# request-scoped chain streaming (req_meta + seq namespace)
# ---------------------------------------------------------------------------

def _boot_chain(stages, params, batch, *, codecs=None):
    nodes = [StageNode(None, "127.0.0.1:0", None) for _ in stages]
    addrs = [f"127.0.0.1:{n.address[1]}" for n in nodes]
    threads = [threading.Thread(target=n.serve, daemon=True)
               for n in nodes]
    for t in threads:
        t.start()
    disp = ChainDispatcher(addrs[0], codec="raw")
    disp.deploy(stages, params, addrs, batch=batch, codecs=codecs)
    return disp, threads


@pytest.fixture(scope="module")
def resnet_setup():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


def test_req_meta_cascades_ahead_of_its_frame(resnet_setup):
    """The node-side contract: a req_meta K_CTRL cascades through every
    stage and arrives on the result hop BEFORE the frame it describes
    (it may overtake earlier frames — the demux joins by seq), with the
    v2 seq stamp relayed end to end and both kinds in send order."""
    g, params = resnet_setup
    stages = partition(g, num_stages=2)
    disp, threads = _boot_chain(stages, params, 2)
    try:
        rng = np.random.default_rng(0)
        xs = [rng.standard_normal((2, 32, 32, 3)).astype(np.float32)
              for _ in range(3)]
        for i, x in enumerate(xs):
            disp.send_request_frame(x, seq=1000 + i,
                                    meta={"slots": [["t", i, i, 0]]})
        got = [disp.recv_result(timeout_s=60.0) for _ in range(6)]
        metas = [v for k, v in got if k == "meta"]
        tensors = [v for k, v in got if k == "tensor"]
        assert len(metas) == 3 and len(tensors) == 3
        assert [m["seq"] for m in metas] == [1000, 1001, 1002]
        assert [m["slots"] for m in metas] == [[["t", i, i, 0]]
                                               for i in range(3)]
        assert [s for s, _ in tensors] == [1000, 1001, 1002]
        for i in range(3):
            at_meta = next(j for j, (k, v) in enumerate(got)
                           if k == "meta" and v["seq"] == 1000 + i)
            at_tensor = next(j for j, (k, v) in enumerate(got)
                             if k == "tensor" and v[0] == 1000 + i)
            assert at_meta < at_tensor, \
                f"meta for frame {i} arrived after its tensor"
    finally:
        disp.close()
        for t in threads:
            t.join(timeout=30)


def test_request_frames_reject_replicated_chains(resnet_setup):
    disp = ChainDispatcher.__new__(ChainDispatcher)
    disp.result_fan_in = 2
    disp._send_sock = object()  # pretend connected
    disp._tx_chan = object()
    with pytest.raises(ValueError, match="non-replicated"):
        disp.send_request_frame(np.zeros((1, 2)), seq=0)


# ---------------------------------------------------------------------------
# the front door end to end (tensor mode over an in-process chain)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tensor_door(resnet_setup):
    g, params = resnet_setup
    stages = partition(g, num_stages=2)
    disp, threads = _boot_chain(stages, params, 4)
    door = ServeFrontDoor(
        backend=ChainBackend(disp, 4, (32, 32, 3))).start()
    yield g, params, door
    door.stop()
    for t in threads:
        t.join(timeout=30)


def test_frontdoor_multitenant_byte_identity(tensor_door):
    """Three concurrent tenant streams over ONE deployed chain: every
    per-request output byte-identical to the request run alone through
    the same serving path (the acceptance bar)."""
    g, params, door = tensor_door
    host, port = door.address
    rng = np.random.default_rng(11)
    data = {t: [rng.standard_normal((32, 32, 3)).astype(np.float32)
                for _ in range(3)] for t in ("alpha", "beta", "gamma")}
    solo = {t: ServeClient(host, port, t + "_solo").stream(data[t])
            for t in data}
    outs = {}

    def run_tenant(t):
        outs[t] = ServeClient(host, port, t).stream(data[t])

    ths = [threading.Thread(target=run_tenant, args=(t,)) for t in data]
    for th in ths:
        th.start()
    for th in ths:
        th.join(timeout=120)
    door.healthcheck()
    for t in data:
        for i in range(len(data[t])):
            assert outs[t][i][0] == "ok" and solo[t][i][0] == "ok"
            np.testing.assert_array_equal(outs[t][i][1], solo[t][i][1])
    doc = fetch_stats(host, port)
    assert doc["mode"] == "tensor" and doc["width"] == 4
    assert doc["tenants"]["alpha"]["completed"] == 3


def test_frontdoor_shed_reply_and_retry(tensor_door):
    """Overload a deadline-bound tenant: the client receives shed
    control frames (with prediction + retry hint) instead of late
    results, and a retry after the backlog drains is served."""
    g, params, door = tensor_door
    host, port = door.address
    # pin the service estimate high so the SLO math sheds immediately
    # and deterministically (the live EWMA would need real overload)
    door.admission._service_s = lambda: 0.5
    try:
        c = ServeClient(host, port, "slo_tenant", deadline_ms=600.0)
        x = np.zeros((32, 32, 3), np.float32)
        for _ in range(4):
            c.submit(x)
        # give the first admissions a moment to resolve, then retry
        time.sleep(1.0)
        retry_seq = c.submit(x)
        results = c.finish()
        outcomes = [results[q][0] for q in sorted(results)]
        assert "shed" in outcomes, outcomes
        shed = next(v for v in results.values() if v[0] == "shed")
        assert shed[1]["reason"] == "deadline"
        assert shed[1]["retry_after_ms"] > 0
        assert shed[1]["predicted_ms"] > 600.0
        assert results[retry_seq][0] == "ok", \
            "a retry after the backlog drained must be admitted"
    finally:
        door.admission._service_s = None


# ---------------------------------------------------------------------------
# the front door end to end (decode mode) + disconnect mid-decode
# ---------------------------------------------------------------------------

@pytest.fixture()
def decode_door():
    # a longer positional table than gpt_tiny's 16 so the "victim" can
    # run a generation long enough to be caught mid-decode
    g = gpt_tiny(seq_len=48)
    params = g.init(jax.random.key(0))
    engine = ContinuousBatchEngine(g, params, num_stages=2, width=3)
    door = ServeFrontDoor(engine=engine,
                          decode_defaults={"max_new_tokens": 4}).start()
    yield g, params, door
    door.stop()


def test_frontdoor_decode_roundtrip_and_disconnect(decode_door):
    """Decode mode: concurrent tenants' generations are byte-identical
    to solo runs; a client disconnecting mid-decode frees its KV slot
    and leaves the other tenant's output untouched."""
    g, params, door = decode_door
    host, port = door.address
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 97, (4,)).astype(np.int32)
               for _ in range(2)]
    solo = ServeClient(host, port, "ref",
                       max_new_tokens=4).stream([prompts[0]])
    assert solo[0][0] == "ok"

    # victim starts a long generation then disconnects without END
    victim = ServeClient(host, port, "victim", max_new_tokens=40)
    victim.submit(prompts[1])
    deadline = time.monotonic() + 30
    while door.engine.active() == 0:
        assert time.monotonic() < deadline, "victim never joined"
        time.sleep(0.01)
    victim.abort()

    steady = ServeClient(host, port, "steady", max_new_tokens=4)
    out = steady.stream([prompts[0]])
    assert out[0][0] == "ok"
    np.testing.assert_array_equal(out[0][1], solo[0][1])

    deadline = time.monotonic() + 30
    while door.engine.free_slots() != door.engine.width:
        assert time.monotonic() < deadline, \
            "the disconnected client's KV slot was never reclaimed"
        time.sleep(0.05)
    # decode attribution: the engine's residency lands in the decode
    # path's own buckets (queue wait in admission), not all-in-admission,
    # and in none of the tensor path's
    buckets = fetch_stats(host, port)["attribution"]["steady"]
    assert buckets["e2e"]["count"] == 1
    assert buckets["first_token"]["p50"] > 0 and buckets["tokens"]["p50"] > 0
    assert "gather" not in buckets and "chain" not in buckets
    door.healthcheck()


# ---------------------------------------------------------------------------
# the CLI surface (in-process: serve + serve-client + monitor --serve)
# ---------------------------------------------------------------------------

def test_cli_serve_serve_client_and_monitor(capsys):
    from defer_tpu import cli
    from defer_tpu.runtime.node import _free_ports

    port = _free_ports(1)[0]
    addr = f"127.0.0.1:{port}"
    t = threading.Thread(
        target=cli.main,
        args=(["serve", "--model", "resnet_tiny", "--stages", "2",
               "--width", "2", "--listen", addr, "--seconds", "6",
               "--tenant", "gold=2.0:1:5000"],),
        daemon=True)
    t.start()
    # the load-generating client CLI against the booting door
    cli.main(["serve-client", "--connect", addr, "--tenant", "gold",
              "--rate", "30", "--seconds", "1", "--seed", "3",
              "--burst", "0.2:0.6:2.0"])
    # the monitor's per-tenant serve columns
    cli.main(["monitor", "--serve", addr, "--iterations", "1",
              "--interval-ms", "50", "--json"])
    # the serve thread must finish INSIDE this test: a stray print
    # after --seconds elapse would land in some other test's capture
    t.join(timeout=60)
    assert not t.is_alive()
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    gen = next(json.loads(ln) for ln in lines
               if "latency_p99_ms" in ln)
    assert gen["tenant"] == "gold" and gen["completed"] >= 1
    assert gen["shed"] == 0, "a 5s SLO at 30 Hz must not shed"
    mon = next(json.loads(ln) for ln in lines if '"serve"' in ln)
    assert mon["serve"]["mode"] == "tensor"
    assert mon["serve"]["tenants"]["gold"]["weight"] == 2.0
    assert mon["serve"]["tenants"]["gold"]["completed"] \
        == gen["completed"]
