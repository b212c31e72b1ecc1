"""Async transport channel layer (transport/channel.py): backpressure,
error propagation, in-order delivery under load, and the overlapped node
loop producing byte-identical results vs the serial baseline."""

import socket
import threading
import time

import numpy as np
import pytest

import jax

from defer_tpu import partition
from defer_tpu.models import resnet_tiny
from defer_tpu.obs import REGISTRY
from defer_tpu.transport.channel import (AsyncReceiver, AsyncSender,
                                         ChannelError)
from defer_tpu.transport.framed import (K_END, K_TENSOR, recv_frame,
                                        send_end, send_frame)


@pytest.fixture(scope="module")
def tiny():
    g = resnet_tiny()
    return g, g.init(jax.random.key(0))


def test_receiver_bounded_queue_applies_backpressure():
    """A full rx queue parks the rx thread (it stops reading), but every
    frame still arrives, in order, once the consumer drains."""
    a, b = socket.socketpair()
    try:
        rx = AsyncReceiver(b, depth=2)
        for i in range(5):
            send_frame(a, np.full((4,), i, np.int32))
        send_end(a)
        time.sleep(0.3)
        # depth=2 in the queue + at most one frame in the thread's hand:
        # the receiver must NOT have slurped all 6 frames
        assert rx.qsize() <= 2
        got = []
        while True:
            kind, v = rx.get(timeout=5.0)
            if kind == K_END:
                break
            got.append(int(v[0]))
        assert got == list(range(5))
    finally:
        a.close()
        b.close()


def test_sender_bounded_queue_blocks_producer():
    """With the wire stalled (peer not reading, kernel buffer shrunk), a
    producer pushing past depth must block — bounded in-flight depth is
    the backpressure contract."""
    a, b = socket.socketpair()
    try:
        a.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16384)
        tx = AsyncSender(a, depth=2)
        big = np.zeros(1 << 18, np.float32)  # 1 MiB frames
        fed = []
        done = threading.Event()

        def feed():
            for i in range(6):
                tx.send(big)
                fed.append(i)
            done.set()

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        time.sleep(0.4)
        assert not done.is_set()      # producer parked on the full queue
        assert len(fed) <= 4          # depth 2 + wire slack, not all 6
        for _ in range(6):            # drain the wire; producer unblocks
            kind, _ = recv_frame(b)
            assert kind == K_TENSOR
        t.join(timeout=10)
        assert done.is_set()
    finally:
        a.close()
        b.close()


def test_receiver_error_propagates_to_consumer():
    a, b = socket.socketpair()
    try:
        rx = AsyncReceiver(b, depth=4)
        a.sendall(b"\x01\x03")  # truncated header
        a.close()
        with pytest.raises(ConnectionError):
            rx.get(timeout=5.0)
    finally:
        b.close()


def test_sender_error_propagates_and_unblocks_producer():
    a, b = socket.socketpair()
    b.close()  # dead peer: sends fail with EPIPE
    try:
        tx = AsyncSender(a, depth=2)
        with pytest.raises((ChannelError, OSError)):
            for _ in range(200):
                tx.send(np.zeros(1024, np.float32))
                time.sleep(0.005)
        # flush after death raises too (never hangs)
        with pytest.raises((ChannelError, OSError)):
            tx.flush(timeout=5.0)
    finally:
        a.close()


def test_in_order_delivery_under_load():
    """Sender and receiver threads racing over one socket: frames come out
    exactly in send order (the channel adds no reordering)."""
    a, b = socket.socketpair()
    try:
        tx = AsyncSender(a, depth=4, codec="lzb")
        rx = AsyncReceiver(b, depth=4)
        n = 300

        def feed():
            for i in range(n):
                tx.send(np.full((16,), i, np.int32))
            tx.send_end()

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        seqs = []
        while True:
            kind, v = rx.get(timeout=30.0)
            if kind == K_END:
                break
            seqs.append(int(v[0]))
        t.join(timeout=10)
        assert seqs == list(range(n))
    finally:
        a.close()
        b.close()


def test_sender_flush_completes_pending_writes():
    a, b = socket.socketpair()
    try:
        tx = AsyncSender(a, depth=8)
        for i in range(5):
            tx.send(np.full((8,), i, np.float32))
        got = []

        def drain():
            for _ in range(5):
                got.append(recv_frame(b)[1])

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        tx.flush(timeout=10.0)
        t.join(timeout=10)
        assert tx.qsize() == 0 and len(got) == 5
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# overlapped node loop vs the serial baseline (in-process, 2 stages)
# ---------------------------------------------------------------------------

def _run_inproc_chain(stages, params, xs, *, overlap: bool, codec: str):
    """Two StageNode threads wired into a chain, driven by a dispatcher —
    the in-band deploy topology with the overlap mode under test."""
    from defer_tpu.runtime.node import ChainDispatcher, StageNode

    nodes = [StageNode(None, "127.0.0.1:0", None, overlap=overlap,
                       inflight=2)
             for _ in range(2)]
    addrs = [f"127.0.0.1:{n.address[1]}" for n in nodes]
    threads = [threading.Thread(target=n.serve, daemon=True) for n in nodes]
    for t in threads:
        t.start()
    disp = ChainDispatcher(addrs[0], codec=codec)
    try:
        disp.deploy(stages, params, addrs, batch=xs[0].shape[0])
        outs = disp.stream(xs)
    finally:
        disp.close()
    for t in threads:
        t.join(timeout=30)
    return outs


def test_overlapped_chain_byte_identical_to_serial(tiny):
    """The overlap is a scheduling change only: with the deterministic bf8
    codec, the overlapped chain must produce byte-identical outputs to the
    serial baseline, and the channel gauges must be registered."""
    g, params = tiny
    stages = partition(g, num_stages=2)
    rng = np.random.default_rng(11)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(6)]
    fast = _run_inproc_chain(stages, params, xs, overlap=True, codec="bf8")
    slow = _run_inproc_chain(stages, params, xs, overlap=False, codec="bf8")
    assert len(fast) == len(slow) == 6
    for y1, y2 in zip(fast, slow):
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
    snap = REGISTRY.snapshot()
    for name in ("node.rx_queue_depth", "node.tx_queue_depth",
                 "node.inflight", "chain.tx_queue_depth",
                 "chain.rx_queue_depth"):
        assert name in snap, f"gauge {name} missing from the registry"


@pytest.mark.slow
def test_three_process_chain_overlap_byte_identical(tiny):
    """Satellite: a real 3-process chain (one OS process per stage) run
    overlapped and serial over the same inputs — byte-identical outputs."""
    from defer_tpu.runtime.node import run_chain

    cpu_env = {"JAX_PLATFORMS": "cpu",
               "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    g, params = tiny
    stages = partition(g, num_stages=3)
    rng = np.random.default_rng(12)
    xs = [rng.standard_normal((1, 32, 32, 3)).astype(np.float32)
          for _ in range(5)]
    fast = run_chain(stages, params, xs, env=cpu_env, codec="bf8",
                     overlap=True)
    slow = run_chain(stages, params, xs, env=cpu_env, codec="bf8",
                     overlap=False)
    assert len(fast) == len(slow) == 5
    for y1, y2 in zip(fast, slow):
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))
