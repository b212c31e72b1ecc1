"""Stage replication: the ordered fan-out/fan-in transport layer
(``transport/replicate.py``), the replicated chain runtime, and the
hardened ``run_chain`` failure paths.

The reorder-buffer unit tests pin the merge's contract — strict sequence
order, gap stalls, bounded-buffer backpressure, duplicate/stale
rejection, R-upstream END bookkeeping — because the runtime's
correctness claim ("replicated chain output is byte-identical to the
serial chain") reduces to exactly those properties.
"""

import queue
import socket
import threading
import time

import numpy as np
import pytest

import jax

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.transport.framed import (K_CTRL, K_END, K_TENSOR,
                                        K_TENSOR_SEQ, recv_frame,
                                        send_frame)
from defer_tpu.transport.replicate import FanInMerge, FanOutSender

#: stage-node subprocesses run on the CPU platform (a chip belongs to
#: one process; a local chain demonstrates topology, not the device)
CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}


@pytest.fixture(scope="module")
def tiny():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# sequence-stamped frames (protocol v2)
# ---------------------------------------------------------------------------

def test_seq_frame_round_trip():
    a, b = socket.socketpair()
    try:
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        send_frame(a, arr, seq=7, codec="bf8")
        kind, value = recv_frame(b)
        assert kind == K_TENSOR_SEQ
        seq, got = value
        assert seq == 7 and got.shape == (3, 4)
        # plain frames are untouched by the v2 addition
        send_frame(a, arr)
        kind, got = recv_frame(b)
        assert kind == K_TENSOR and got.shape == (3, 4)
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# reorder buffer
# ---------------------------------------------------------------------------

def test_merge_gap_stalls_consumer_until_filled():
    """Later frames buffered, the next-needed one missing: the consumer
    must PARK (never reorder silently), then release everything in
    order once the gap fills."""
    m = FanInMerge(2, capacity=8)
    m.put(1, "b")
    m.put(2, "c")
    with pytest.raises(queue.Empty):
        m.get_nowait()
    with pytest.raises(TimeoutError):
        m.get(timeout=0.2)
    m.put(0, "a")
    assert [m.get(1.0)[1] for _ in range(3)] == ["a", "b", "c"]


def test_merge_backpressure_parks_producer_but_admits_needed_seq():
    """A full buffer of future frames blocks further out-of-order puts
    (backpressure toward the fast replica) — but the sequence the
    consumer is parked on is ALWAYS admitted, so a full buffer can
    never deadlock the stream."""
    m = FanInMerge(2, capacity=2)
    m.put(1, "b")
    m.put(2, "c")  # buffer full: {1, 2}
    blocked = threading.Event()
    unblocked = threading.Event()

    def slow_path():
        blocked.set()
        m.put(3, "d", timeout=30.0)  # must park: buffer full, not needed
        unblocked.set()

    t = threading.Thread(target=slow_path, daemon=True)
    t.start()
    blocked.wait(5.0)
    time.sleep(0.2)
    assert not unblocked.is_set()      # producer parked on the full buffer
    m.put(0, "a")                      # the needed seq is admitted anyway
    assert m.get(1.0)[1] == "a"        # back AT capacity: still parked
    time.sleep(0.2)
    assert not unblocked.is_set()
    assert m.get(1.0)[1] == "b"        # below capacity: producer wakes
    t.join(timeout=10)
    assert unblocked.is_set()
    assert [m.get(1.0)[1] for _ in range(2)] == ["c", "d"]


def test_merge_rejects_duplicate_and_stale_seq():
    m = FanInMerge(2, capacity=4)
    m.put(0, "a")
    with pytest.raises(ValueError, match="duplicate/stale"):
        m.put(0, "dup")            # duplicate while buffered
    assert m.get(1.0) == (K_TENSOR, "a")
    with pytest.raises(ValueError, match="duplicate/stale"):
        m.put(0, "late")           # stale: already released
    m.put(1, "b")
    assert m.get(1.0)[1] == "b"


def test_merge_end_requires_all_upstreams():
    """K_END with R upstreams: one END is not the stream's end; R are.
    Interleaving END with still-buffered frames must drain in order
    first."""
    m = FanInMerge(3, capacity=8)
    m.put(0, "a")
    m.end()                         # upstream 0 done
    m.end()                         # upstream 1 done
    assert m.get(1.0)[1] == "a"
    with pytest.raises(TimeoutError):
        m.get(timeout=0.2)          # 2 of 3 ENDs: not over yet
    m.put(1, "b")                   # upstream 2 still streaming
    m.end()
    assert m.get(1.0)[1] == "b"
    assert m.get(1.0) == (K_END, None)


def test_merge_end_with_gap_raises():
    """All upstreams ended but a sequence slot never arrived (a replica
    died between fan-out and fan-in): loud, never a silent skip."""
    m = FanInMerge(2, capacity=8)
    m.put(1, "b")
    m.end()
    m.end()
    with pytest.raises(ConnectionError, match="gap"):
        m.get(timeout=1.0)


def test_merge_ctrl_rides_ahead_and_reader_failure_propagates():
    m = FanInMerge(2, capacity=4)
    m.put(0, "a")
    m.put_ctrl({"cmd": "trace"})
    kind, msg = m.get(1.0)
    assert kind == K_CTRL and msg["cmd"] == "trace"
    assert m.get(1.0)[1] == "a"
    m.fail(ConnectionError("replica died"))
    with pytest.raises(ConnectionError, match="replica died"):
        m.get(timeout=1.0)
    with pytest.raises(ConnectionError, match="replica died"):
        m.put(1, "b")


def test_merge_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        FanInMerge(4, capacity=2)
    with pytest.raises(ValueError, match="expected"):
        FanInMerge(0)


# ---------------------------------------------------------------------------
# fan-out sender
# ---------------------------------------------------------------------------

def test_fanout_round_robin_stamps_sequence():
    """Tensor i goes to channel i % R carrying seq i; ctrl and END
    broadcast to every channel."""
    pairs = [socket.socketpair() for _ in range(2)]
    try:
        fo = FanOutSender([a for a, _ in pairs], depth=4)
        for i in range(6):
            fo.send(np.full((2,), i, np.int32))
        fo.close(timeout=10.0)
        for r, (_, b) in enumerate(pairs):
            seqs = []
            while True:
                kind, value = recv_frame(b)
                if kind == K_END:
                    break
                assert kind == K_TENSOR_SEQ
                seq, arr = value
                assert int(arr[0]) == seq  # payload i carries seq i
                seqs.append(seq)
            assert seqs == [r, r + 2, r + 4]
    finally:
        for a, b in pairs:
            a.close()
            b.close()


def test_fanout_merge_round_trip_out_of_order_arrival():
    """End to end through real sockets: fan out 2 ways, merge back — in
    order, even when one path's reader runs far behind."""
    pairs = [socket.socketpair() for _ in range(2)]
    try:
        fo = FanOutSender([a for a, _ in pairs], depth=8)
        merge = FanInMerge(2, capacity=8)

        def reader(r, delay):
            sock = pairs[r][1]
            try:
                while True:
                    kind, value = recv_frame(sock)
                    if kind == K_END:
                        merge.end()
                        return
                    if kind == K_CTRL:
                        continue
                    time.sleep(delay)  # one slow replica path
                    merge.put(*value)
            except BaseException as e:  # noqa: BLE001
                merge.fail(e)

        threads = [threading.Thread(target=reader, args=(r, 0.02 * r),
                                    daemon=True) for r in range(2)]
        for t in threads:
            t.start()
        n = 12
        for i in range(n):
            fo.send(np.full((2,), i, np.int32))
        fo.close(timeout=10.0)
        got = []
        while True:
            kind, value = merge.get(timeout=30.0)
            if kind == K_END:
                break
            got.append(int(value[0]))
        assert got == list(range(n))
        for t in threads:
            t.join(timeout=10)
    finally:
        for a, b in pairs:
            a.close()
            b.close()


# ---------------------------------------------------------------------------
# replicated chain runtime (in-process thread nodes)
# ---------------------------------------------------------------------------

def _run_chain_inproc(stages, params, xs, *, replicas=(1, 1, 1),
                      codecs=None):
    from defer_tpu.runtime.node import ChainDispatcher, StageNode

    groups = []
    for k, r in enumerate(replicas):
        fan_in = replicas[k - 1] if k > 0 else 1
        groups.append([
            StageNode(None, "127.0.0.1:0", None,
                      replica=j if r > 1 else None, fan_in=fan_in)
            for j in range(r)])
    addr_groups = [[f"127.0.0.1:{n.address[1]}" for n in grp]
                   for grp in groups]
    flat = [n for grp in groups for n in grp]
    threads = [threading.Thread(target=n.serve, daemon=True) for n in flat]
    for t in threads:
        t.start()
    disp = ChainDispatcher(",".join(addr_groups[0]), codec="raw",
                           result_fan_in=replicas[-1])
    try:
        disp.deploy(stages, params, addr_groups, batch=xs[0].shape[0],
                    codecs=codecs)
        outs = disp.stream(xs)
        stats = disp.stats([a for grp in addr_groups for a in grp])
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=30)
    return outs, stats


def test_replicated_chain_byte_identical_and_split(tiny):
    """Replicating the middle stage is a scheduling change only: same
    outputs, same order, and the round-robin split is visible in the
    per-replica stats."""
    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(21)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(6)]
    base, _ = _run_chain_inproc(stages, params, xs)
    rep, stats = _run_chain_inproc(stages, params, xs,
                                   replicas=(1, 2, 1))
    assert len(base) == len(rep) == 6
    for a, b in zip(base, rep):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    per_rep = {s["replica"]: s["processed"] for s in stats
               if s.get("stage") == 1}
    assert per_rep == {0: 3, 1: 3}
    fan_in = [s["fan_in"] for s in stats]
    assert fan_in == [1, 1, 1, 2]


def test_replicated_last_stage_and_short_stream(tiny):
    """Last-stage replication (dispatcher-side fan-in merge), including
    the fewer-inputs-than-replicas edge where one replica only ever
    sees the cascaded END."""
    g, params = tiny
    stages = partition(g, num_stages=2)
    rng = np.random.default_rng(22)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(3)]
    base, _ = _run_chain_inproc(stages, params, xs, replicas=(1, 1))
    rep, _ = _run_chain_inproc(stages, params, xs, replicas=(1, 2))
    for a, b in zip(base, rep):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    one, _ = _run_chain_inproc(stages, params, xs[:1], replicas=(1, 2))
    np.testing.assert_array_equal(np.asarray(one[0]), np.asarray(base[0]))


def test_adjacent_replication_rejected(tiny):
    from defer_tpu.runtime.node import _normalize_replicas
    with pytest.raises(ValueError, match="adjacent"):
        _normalize_replicas({0: 2, 1: 2}, 3)
    with pytest.raises(ValueError, match="out of range"):
        _normalize_replicas({7: 2}, 3)
    assert _normalize_replicas({1: 3}, 3) == [1, 3, 1]


# ---------------------------------------------------------------------------
# run_chain failure hardening (satellites: bind-race retry, kill-mid-stream)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_run_chain_kills_children_when_one_dies_mid_stream(tiny):
    """Kill one node mid-stream: run_chain must raise (with the dead
    node attributed) and terminate every remaining child before the
    error propagates — no leaked replica processes."""
    from defer_tpu.runtime.node import run_chain

    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(23)
    spawned: list = []

    def on_spawn(procs):
        spawned.extend(procs)

    def inputs():
        # feed a couple of frames, murder the middle node, keep feeding
        for i in range(40):
            if i == 2:
                spawned[1].kill()
            yield rng.standard_normal((1, 32, 32, 3)).astype(np.float32)

    with pytest.raises(RuntimeError, match="stage1"):
        run_chain(stages, params, inputs(), env=CPU_ENV,
                  on_spawn=on_spawn, spawn_retries=1)
    assert len(spawned) == 3
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if all(pr.poll() is not None for pr in spawned):
            break
        time.sleep(0.2)
    assert all(pr.poll() is not None for pr in spawned), (
        "run_chain leaked live children: "
        f"{[pr.poll() for pr in spawned]}")


@pytest.mark.slow
def test_run_chain_retries_bind_race(tiny, monkeypatch):
    """Steal one probed port before the children spawn: attempt 1 dies
    with address-in-use, the retry on fresh ports succeeds."""
    from defer_tpu.runtime import node as node_mod

    g, params = tiny
    stages = partition(g, num_stages=2)
    rng = np.random.default_rng(24)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(2)]
    real_free_ports = node_mod._free_ports
    thief: list = []

    def stealing_free_ports(n):
        ports = real_free_ports(n)
        if not thief:  # first attempt only: occupy a node's port
            thief.append(socket.create_server(("127.0.0.1", ports[0])))
        return ports

    monkeypatch.setattr(node_mod, "_free_ports", stealing_free_ports)
    try:
        outs = node_mod.run_chain(stages, params, xs, env=CPU_ENV,
                                  spawn_retries=3)
        assert len(outs) == 2
    finally:
        for s in thief:
            s.close()


@pytest.mark.slow
def test_three_process_replicated_chain_matches_single_program(tiny):
    """Full multi-process topology: stage 1 as two OS-process replicas,
    against the single-program oracle, with per-replica stats."""
    from defer_tpu.runtime.node import run_chain

    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(25)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(5)]
    stats: list = []
    outs = run_chain(stages, params, xs, env=CPU_ENV,
                     replicas={1: 2}, stats_out=stats)
    assert len(outs) == 5
    fwd = jax.jit(g.apply)
    for x, y in zip(xs, outs):
        np.testing.assert_allclose(
            y, np.asarray(fwd(params, x)), rtol=2e-4, atol=2e-4)
    per_rep = {s["replica"]: s["processed"] for s in stats
               if s.get("stage") == 1}
    assert sorted(per_rep) == [0, 1] and sum(per_rep.values()) == 5
