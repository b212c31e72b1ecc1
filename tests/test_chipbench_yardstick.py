"""The benchmark's own tests, in tier-1: the manifest, the readers and
the reductions that decide a PR are held by the run that decides a PR.

These are the test functions (and the fixtures they ask for) of
``chipbench/tests`` that run in one process on the CPU without the
tiny cells' drivers: each keeps its own module's globals, so this file
only has to name them.  ``chipbench/tests/test_*_driver.py`` and
``test_drivers.py`` stay out (PERF.md section 7: five of them pin cell
counts and a gauge that later PRs moved, and only a ``benchmark`` PR may
edit them).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "chipbench", "tests"))

from test_idle import *  # noqa: E402,F401,F403
from test_manifest import *  # noqa: E402,F401,F403
from test_pipe_readers import *  # noqa: E402,F401,F403
from test_readings import *  # noqa: E402,F401,F403
from test_request_readers import *  # noqa: E402,F401,F403
from test_setup_readers import *  # noqa: E402,F401,F403
from test_spans import *  # noqa: E402,F401,F403
from test_steady import *  # noqa: E402,F401,F403
from test_trace import *  # noqa: E402,F401,F403

# left out: it holds a cell added by a test to exactly the one per-layer
# metric that lists it, and since PR 53 the ten ``setup_*_s`` metrics
# list no cells and so belong to every cell, as ``setup_s`` does (the
# file is the benchmark's: PERF.md section 7)
del test_a_cell_and_a_metric_are_added_by_adding_files  # noqa: F821

# kept, and expected to fail until a ``benchmark`` PR repairs it: it takes
# the ten ``setup_*_s`` metrics for the *last* ten entries of
# ``per_layer``.  The contract a PR is built under says of
# ``BENCHMARK.json``: "Put new entries at the end of their lists: one
# put first or in the middle reads as a change to what was there", and
# every accepted revision of the file appended (``git log -- BENCHMARK.json``),
# so PR 55's five per-layer metrics stand behind the ten; the test's file
# is the benchmark's, which only a ``benchmark`` PR may edit (PERF.md
# section 7 asks for the repair: find the ten by name).  ``strict``: the
# day it passes again this mark has to go.  What the test holds besides
# the place is held below, the ten found by name.
_the_ten_last = test_the_manifest_gives_every_cell_the_ten_readers  # noqa: F821


@pytest.mark.xfail(strict=True, raises=AssertionError,
                   reason="the ten setup readers are no longer the last "
                          "entries of per_layer (PR 55 appended five)")
def test_the_manifest_gives_every_cell_the_ten_readers():  # noqa: F811
    _the_ten_last()


def test_the_manifest_gives_every_cell_the_ten_setup_readers():
    from test_setup_readers import KINDS, LAYER

    from chipbench.manifest import Manifest
    m = Manifest()
    names = [f"setup_{k}_s" for k in KINDS]
    ten = [e for e in m.doc["per_layer"] if e["name"] in names]
    assert [e["name"] for e in ten] == names        # together, in order
    at = m.doc["per_layer"].index(ten[0])
    assert m.doc["per_layer"][at:at + len(ten)] == ten
    for entry in ten:
        assert entry == {
            "name": entry["name"], "unit": "s", "better": "lower",
            "source": KINDS[entry["name"][len("setup_"):-len("_s")]],
            "layer": LAYER, "moves": "setup_s"}
        reader = m.reader(entry["name"])
        assert (reader.LAYER, reader.SOURCE, reader.MOVES) == (
            LAYER, entry["source"], "setup_s")
    for cell in m.workload_names():
        assert set(names) <= set(m.cell(cell).per_layer)


def test_the_queued_gpt2_cell_is_the_batch_cells_traffic_at_full_depth():
    """``gpt2xl_full_batch_decode``: configuration ``gpt2-xl`` (48
    layers), one chip, ``gpt2xl_batch_decode``'s traffic number for
    number.  ISSUE 55 named that cell's traffic file itself; the
    four-chip cell already pairs ``gpt2-xl`` with
    ``batch8_512in_256out_chunk4``, and the contract a PR is built under
    says of ``workloads``: "A pair of configuration and traffic appears
    once."  So the traffic stands under a name of its own, and this case
    (in tier-1, where every PR runs it) holds the copy to the file the
    24-layer cell is sized against: they cannot drift apart."""
    from chipbench.manifest import Manifest
    m = Manifest()
    full = m.cell("gpt2xl_full_batch_decode")
    half = m.cell("gpt2xl_batch_decode")
    assert full.traffic == half.traffic and full.chips == 1
    assert full.config["model_args"]["num_layers"] == 48
    assert full.config == m.cell("gpt2xl_pipe4_decode").config
    assert full.per_layer == half.per_layer
    assert full.end_to_end == half.end_to_end == ("tokens_per_s", "setup_s")
    pairs = [(w["config"], w["traffic"]) for w in m.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)


# -- PR 57's two cells: what of their own tests needs no tiny driver -------------------

import test_shortcut_latent_moe_driver as _shortcut  # noqa: E402

real_args = _shortcut.real_args
# kept as it is, and expected to fail until a ``benchmark`` PR repairs
# it: it takes LongCat's configuration and three metrics for the *last*
# entries of their lists, and PR 61 appended behind them, as the
# contract asks (the ten setup readers' case above, again; the file is
# the benchmark's).  ``strict``: the day it passes again this mark has
# to go.  Every other assertion it makes is held below, against the
# manifest cut where PR 57 left it.
_longcat_files = \
    _shortcut.test_the_real_manifest_gives_the_cell_its_files_and_metrics


@pytest.mark.xfail(strict=True, raises=AssertionError,
                   reason="LongCat's entries are no longer the last of "
                          "configs and per_layer (PR 61 appended)")
def test_the_longcat_cell_has_its_files_and_metrics():
    _longcat_files()


def test_the_longcat_cell_has_its_files_and_metrics_where_pr_57_left_them(
        monkeypatch):
    from chipbench.manifest import Manifest

    class AsPr57LeftIt(Manifest):
        def __init__(self, root=None):
            super().__init__(root)
            for group, last in (("configs", "longcat-flash-chat-4l-ep32"),
                                ("per_layer", "zero_expert_pair_share")):
                names = [e["name"] for e in self.doc[group]]
                del self.doc[group][names.index(last) + 1:]

    monkeypatch.setattr(_shortcut, "Manifest", AsPr57LeftIt)
    _longcat_files()


test_the_longcat_readers_return_nothing_without_their_counters = \
    _shortcut.test_the_readers_return_nothing_without_their_counters
test_the_longcat_models_size_against_the_issues_count = \
    _shortcut.test_the_models_size_against_the_issues_count
test_the_longcat_decode_step_needs_against_the_issues_count = \
    _shortcut.test_decode_step_needs_against_the_issues_count
test_the_longcat_prefill_needs_against_the_issues_count = \
    _shortcut.test_prefill_needs_against_the_issues_count


# -- PR 61's cell: what of its own tests needs no tiny driver ------------------------

import test_conv_moe_driver as _conv_moe  # noqa: E402

lfm2_args = _conv_moe.lfm2_args
test_the_lfm2_cell_has_its_files_and_metrics = \
    _conv_moe.test_the_real_manifest_gives_the_cell_its_files_and_metrics
test_the_lfm2_readers_on_a_trace_made_by_hand = \
    _conv_moe.test_the_readers_on_a_trace_made_by_hand
test_the_lfm2_readers_on_the_recorded_trace_find_nothing_to_read = \
    _conv_moe.test_the_readers_on_the_recorded_trace_find_nothing_to_read
test_the_lfm2_readers_return_nothing_without_their_counters = \
    _conv_moe.test_the_readers_return_nothing_without_their_counters
test_the_lfm2_models_size_against_the_issues_count = \
    _conv_moe.test_the_models_size_against_the_issues_count
test_the_lfm2_decode_step_needs_against_the_issues_count = \
    _conv_moe.test_decode_step_needs_against_the_issues_count
test_the_lfm2_prefill_needs_against_the_issues_count = \
    _conv_moe.test_prefill_needs_against_the_issues_count


# -- PR 65's cell: what of its own tests needs no tiny driver ------------------------

import test_delta_moe_driver as _delta_moe  # noqa: E402

solar_args = _delta_moe.solar_args
test_the_solar_cell_has_its_files_and_metrics = \
    _delta_moe.test_the_real_manifest_gives_the_cell_its_files_and_metrics
test_the_solar_readers_on_a_trace_made_by_hand = \
    _delta_moe.test_the_readers_on_a_trace_made_by_hand
test_the_solar_readers_on_the_recorded_trace_find_nothing_to_read = \
    _delta_moe.test_the_readers_on_the_recorded_trace_find_nothing_to_read
test_the_solar_readers_return_nothing_without_their_counters = \
    _delta_moe.test_the_readers_return_nothing_without_their_counters
test_the_solar_models_size_against_the_issues_count = \
    _delta_moe.test_the_models_size_against_the_issues_count
test_the_solar_programs_tree_has_the_issues_count = \
    _delta_moe.test_the_programs_tree_has_the_issues_count
test_the_solar_decode_step_needs_against_the_issues_count = \
    _delta_moe.test_decode_step_needs_against_the_issues_count
test_the_solar_prefill_needs_against_the_issues_count = \
    _delta_moe.test_prefill_needs_against_the_issues_count


# PR 67's cell (Nemotron-3-Super): the tests of its driver's file that
# need no tiny driver run — the manifest's entries found by name, the
# readers on traces made by hand, the roofline's counts against the
# issue's
import test_ssd_latent_moe_driver as _ssd_latent_moe  # noqa: E402

nemotron_args = _ssd_latent_moe.nemotron_args
test_the_nemotron_cell_has_its_files_and_metrics = \
    _ssd_latent_moe.test_the_real_manifest_gives_the_cell_its_files_and_metrics
test_the_nemotron_readers_on_a_trace_made_by_hand = \
    _ssd_latent_moe.test_the_readers_on_a_trace_made_by_hand
test_the_nemotron_readers_return_nothing_without_their_counters = \
    _ssd_latent_moe.test_the_readers_return_nothing_without_their_counters
test_the_nemotron_models_size_against_the_issues_count = \
    _ssd_latent_moe.test_the_models_size_against_the_issues_count
test_the_nemotron_programs_tree_has_the_issues_count = \
    _ssd_latent_moe.test_the_programs_tree_has_the_issues_count
test_the_nemotron_decode_step_needs_against_the_issues_count = \
    _ssd_latent_moe.test_decode_step_needs_against_the_issues_count
test_the_nemotron_prefill_needs_against_the_issues_count = \
    _ssd_latent_moe.test_prefill_needs_against_the_issues_count


def test_the_long_prompt_cell_is_the_full_cells_model_on_a_long_prompt():
    """``gpt2xl_long_prompt`` (queued since PR 32 as B0.5): configuration
    ``gpt2-xl`` (48 layers), one chip, driver ``batch_decode``, 8 x (896
    in, 32 out) 4 a call inside GPT-2's 1024 positions; it reports what
    the full batch cell reports, ``decode_step_roofline`` with it."""
    from chipbench.manifest import Manifest
    m = Manifest()
    cell = m.cell("gpt2xl_long_prompt")
    full = m.cell("gpt2xl_full_batch_decode")
    assert cell.config == full.config and cell.chips == 1
    assert cell.config["model_args"]["num_layers"] == 48
    assert {k: cell.traffic[k] for k in (
        "driver", "batch", "prompt_len", "new_tokens", "token_chunk",
        "max_len", "compute_dtype", "kv_cache", "check_sequences")} == {
        "driver": "batch_decode", "batch": 8, "prompt_len": 896,
        "new_tokens": 32, "token_chunk": 4, "max_len": 928,
        "compute_dtype": "bfloat16", "kv_cache": "buffer",
        "check_sequences": 2}
    assert cell.traffic["max_len"] <= cell.config["model_args"]["seq_len"]
    assert cell.per_layer == full.per_layer
    assert cell.end_to_end == ("tokens_per_s", "setup_s")
    # (a later PR may append cells: these two stand behind PR 55's eleven)
    assert [w["name"] for w in m.doc["workloads"]][11:13] == [
        "longcatflash_batch_decode", "gpt2xl_long_prompt"]
    assert sum(w["chips"] == 4 for w in m.doc["workloads"]) == 1
    pairs = [(w["config"], w["traffic"]) for w in m.doc["workloads"]]
    assert len(set(pairs)) == len(pairs)
