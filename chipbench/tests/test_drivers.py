"""Each driver end to end at a tiny preset on the CPU, through the
harness: the last line's keys are the contract's, and the yardsticks
inside the drivers hold."""

import json
import subprocess
import sys
import time

import numpy as np
import pytest

import tiny
from chipbench.harness import run_cell
from chipbench.manifest import Manifest

KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny_root")))


@pytest.mark.parametrize("cell, first", [
    ("batch_tiny", "tokens_per_s"), ("chat_tiny", "answer_ms_per_token_p50")])
def test_an_untraced_run_prints_the_contracts_keys(root, cell, first):
    doc = run_cell(workload=cell, seed=2 ** 31 + 12345, seconds=1.0,
                   trace=False, t_start=time.perf_counter(), root=root,
                   require_tpu=False)
    json.dumps(doc)
    assert set(doc) == KEYS and set(doc["device"]) == DEVICE_KEYS
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["attempted"] > 0
    want = set(Manifest(root).cell(cell).end_to_end)
    assert set(doc["metrics"]) == want and first in want
    for m in doc["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0


def test_a_traced_run_reports_per_layer_metrics_and_a_breakdown(root):
    doc = run_cell(workload="chat_tiny", seed=7, seconds=1.0, trace=True,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert set(doc) == KEYS | {"breakdown"}
    assert set(doc["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    assert 0 < doc["device"]["busy_s"] <= doc["device"]["window_s"]
    per_layer = set(Manifest(root).cell("chat_tiny").per_layer)
    assert {"engine_step_ms", "engine_host_ms", "admission_wait_ms",
            "loadgen_late_ms"} <= set(doc["metrics"]) <= per_layer
    assert set(doc["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in doc["breakdown"].values())


def test_an_untraced_run_with_too_few_readings_is_not_correct(root):
    doc = run_cell(workload="chat_tiny", seed=7, seconds=0.2, trace=False,
                   t_start=time.perf_counter(), root=root, require_tpu=False)
    assert doc["correct"] is False and "setup_s" in doc["metrics"]
    assert "answer_ms_per_token_p50" not in doc["metrics"]


def test_tokens_per_s_is_all_tokens_over_all_the_window(root, monkeypatch):
    """One stalled chunk of twelve moves the rate: it is taken over the
    whole window, not from the median reading."""
    import contextlib
    import types

    drv = Manifest(root).driver(Manifest(root).cell("batch_tiny"))
    clock = types.SimpleNamespace(now=100.0)
    monkeypatch.setattr(drv, "time", types.SimpleNamespace(
        perf_counter=lambda: clock.now))

    class Decoder:
        def generate(self, prompts, new_tokens, *, on_tokens, **_kw):
            for i in range(13):          # the prefill's call, then 12 chunks
                clock.now += 5.0 if i == 6 else 1.0     # one stall
                on_tokens(2 * i, 2 * i + 2, np.zeros((2, 2), np.int32), None)

    state = {"dec": Decoder(), "prompts": np.zeros((2, 8), np.int32),
             "traffic": tiny.TRAFFIC["batch_tiny"],
             "config": tiny.CONFIGS["gpt-tiny"]}
    ctx = types.SimpleNamespace(trace=False,
                                span=lambda name: contextlib.nullcontext())
    m = drv.measure(state, 16.5, ctx)    # over inside the thirteenth call
    assert m["readings"] == 5 * [1.0] + [5.0] + 6 * [1.0]
    # 13 calls of 2 x 2 tokens in 17 s; the median reading would say 4.0
    assert m["end_to_end"]["tokens_per_s"] == pytest.approx(52 / 17.0)


def test_the_command_line_fails_without_a_tpu():
    r = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload",
         "gpt2xl_batch_decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tiny.REPO, capture_output=True, text=True,
        timeout=300)
    assert r.returncode != 0
    assert not any(line.startswith("{") for line in r.stdout.splitlines())
    assert "no TPU" in r.stderr


def test_the_open_loop_client_times_from_the_due_time(root):
    """A stalled server delays the *send* of later requests; their time
    still runs from when they were due."""
    import socket
    import threading

    from defer_tpu.transport.framed import (K_END, recv_frame, send_ctrl,
                                            send_end, send_frame)
    drv = Manifest(root).driver(Manifest(root).cell("chat_tiny"))
    srv = socket.create_server(("127.0.0.1", 0))
    stall = 0.30

    def serve():
        for i in range(3):
            conn, _ = srv.accept()
            recv_frame(conn)                       # hello
            if i == 0:
                time.sleep(stall)                  # the server stalls once
            send_ctrl(conn, {"cmd": "welcome"})
            _kind, prompt = recv_frame(conn)
            assert recv_frame(conn)[0] == K_END
            send_frame(conn, np.asarray(prompt, np.int64), seq=0)
            send_end(conn)
            conn.close()

    th = threading.Thread(target=serve, daemon=True)
    th.start()
    reqs = [drv.Request(0.0, np.arange(3, dtype=np.int32), 4),
            drv.Request(0.05, np.arange(3, dtype=np.int32), 4),
            drv.Request(0.10, np.arange(3, dtype=np.int32), 4)]
    drv.play(srv.getsockname(), reqs, tenant="t", drain_timeout_s=10.0)
    th.join(timeout=10)
    srv.close()
    assert all(r.answer is not None for r in reqs)
    # request 1 was due at 0.05 s but could only be sent after the stall:
    # it is late by about stall - 0.05, and its time counts that wait
    assert reqs[1].sent_s - reqs[1].due_s > stall - 0.1
    assert reqs[1].done_s - reqs[1].due_s >= reqs[1].sent_s - reqs[1].due_s
    assert reqs[1].done_s - reqs[1].due_s > stall - 0.1


def test_every_seed_replays_the_same_schedule_with_other_contents(root):
    drv = Manifest(root).driver(Manifest(root).cell("chat_tiny"))
    traffic = dict(tiny.TRAFFIC["chat_tiny"], rate_hz=40.0)
    a = drv.make_requests(traffic, 211, 1, 2.0)
    b = drv.make_requests(traffic, 211, 2 ** 31 + 5, 2.0)
    dues = [r.due_s for r in a]
    assert len(a) == 80 and dues == sorted(dues) and dues[-1] < 2.0
    assert dues == [r.due_s for r in b]
    assert [(r.prompt.size, r.answer_len) for r in a] == \
        [(r.prompt.size, r.answer_len) for r in b]
    # 80 requests are 20 whole passes over the table of 4
    assert sorted((r.prompt.size, r.answer_len) for r in a) == sorted(
        20 * [tuple(p) for p in traffic["lengths"]])
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    again = drv.make_requests(traffic, 211, 1, 2.0)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))
