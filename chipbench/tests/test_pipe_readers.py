"""The four-chip cell's readers on a trace made by hand: four device
planes, a decode program of four steps a run, hop operations."""

import types

import pytest

from chipbench.manifest import Manifest
from chipbench.roofline import gpt_decode_step_needs
from chipbench.trace import DeviceTrace, TraceReduction

PIPE = ("pipe_decode_step_roofline", "decode_worst_chip_idle_share",
        "decode_hop_wait_share")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
ARGS = {"num_layers": 48, "hidden": 1600, "vocab": 50257}
COUNTERS = {"model_args": ARGS, "rows": 8, "live_positions": 640.0,
            "weight_bytes": 2, "kv_bytes": 2, "steps_per_reading": 4}


def _chip(i, busy_to, hop, run_s):
    """A chip whose window [0, 1] holds two decode runs of ``run_s``,
    a fusion from 0 to ``busy_to`` and a hop wait of ``hop`` seconds."""
    d = DeviceTrace(f"/device:TPU:{i}")
    d.ops = [("%fusion.3 = bf16[8] fusion(...)", 0.0, busy_to - hop),
             ("%collective-permute-start.2 = ...", busy_to - hop,
              busy_to - hop + 0.01),
             ("%collective-permute-done.2 = ...", busy_to - hop + 0.01,
              busy_to),
             ("%collective-permute-done.9 = ...", 1.5, 2.5)]   # outside
    d.modules = [("jit_device_decode(123)", 0.0, run_s),
                 ("jit_device_decode(123)", 0.5, 0.5 + run_s),
                 ("jit_device_prefill(7)", 0.2, 0.3)]
    return d


@pytest.fixture
def run():
    chips = [_chip(0, 0.95, 0.25, 0.16), _chip(1, 0.90, 0.30, 0.20),
             _chip(2, 0.95, 0.20, 0.16), _chip(3, 0.95, 0.20, 0.16)]
    red = TraceReduction(chips, [("window", 0.0, 1.0)])
    return types.SimpleNamespace(trace=red, counters=COUNTERS, peaks=PEAKS)


def test_the_cell_has_the_readers_and_they_name_their_entries():
    m = Manifest()
    cell = m.cell("gpt2xl_pipe4_decode")
    assert set(PIPE) <= set(cell.per_layer)
    assert "decode_step_roofline" not in cell.per_layer
    for one in ("gpt2xl_batch_decode", "olmoe_batch_decode"):
        assert not set(PIPE) & set(m.cell(one).per_layer)


def test_the_worst_chip_and_the_hop_are_read_off_the_right_plane(run):
    m = Manifest()
    # chip 1 is busy 0.90 of the window's 1.0; the mean reader says 6.25
    assert m.reader("decode_worst_chip_idle_share").read(run) \
        == pytest.approx(10.0)
    assert m.reader("decode_device_idle_share").read(run) \
        == pytest.approx(6.25)
    # start and done inside the window, on the chip that waits longest
    assert m.reader("decode_hop_wait_share").read(run) == pytest.approx(30.0)


def test_the_roofline_is_a_stages_needs_over_the_slowest_chips_step(run):
    flops, nbytes = gpt_decode_step_needs(
        n_layer=48, n_embd=1600, vocab=50257, rows=8, live_positions=640.0,
        weight_bytes=2, kv_bytes=2)
    assert flops / 4 / 197e12 < nbytes / 4 / 819e9        # memory-bound
    want = 100.0 * (nbytes / 4 / 819e9) / (0.20 / 4)      # chip 1, 4 steps
    got = Manifest().reader("pipe_decode_step_roofline").read(run)
    assert got == pytest.approx(want) and 0 < got < 100


def test_the_readers_return_nothing_without_a_trace(run):
    m = Manifest()
    none = types.SimpleNamespace(trace=None, counters={}, peaks=PEAKS)
    for name in PIPE:
        assert m.reader(name).read(none) is None
    run.trace.devices[2].modules = []       # a plane with no program run
    assert m.reader("pipe_decode_step_roofline").read(run) is None
