"""``python3 -m chipbench.steady`` against a stub child: the spread's
definition, the three words at their edges, the exit codes, the seeds."""

import json
import statistics
import sys

import pytest

from chipbench import steady
from chipbench.manifest import Manifest
from chipbench.readings import spread

STUB = '''
import json, os, sys
here = os.path.dirname(os.path.abspath(__file__))
args = dict(zip(sys.argv[1::2], sys.argv[2::2]))
with open(os.path.join(here, "calls"), "a") as f:
    f.write(json.dumps(args) + "\\n")
n = sum(1 for _ in open(os.path.join(here, "calls"))) - 1
doc = json.load(open(os.path.join(here, "docs.json")))[n]
print("chipbench: platform=stub")
print('chipbench: readings {"count": 101, "min": 0.3, "median": 0.3244} '
      'work_over_wall {"tokens": 1}')
if doc.get("pauses"):
    print("defer_tpu: host_pause layer=decode phase=sync\\n" * doc.pop("pauses"),
          file=sys.stderr, end="")
if "exit" in doc:
    sys.exit(doc["exit"])
print(doc["last"] if "last" in doc else json.dumps(doc))
'''

CELL = "gpt2xl_batch_decode"
BOUND = Manifest().metric("tokens_per_s")["bound"]


def _doc(tokens, setup=30.0, correct=True, **more):
    return dict({"correct": correct, "attempted": 1, "failed": 0, "metrics": {
        "tokens_per_s": {"value": tokens, "unit": "tokens/s"},
        "setup_s": {"value": setup, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "stub", "count": 1,
                   "memory_peak_bytes": 1}}, **more)


def _steady(tmp_path, capsys, docs, *argv):
    (tmp_path / "stub.py").write_text(STUB)
    (tmp_path / "docs.json").write_text(json.dumps(docs))
    rc = steady.main(["--workload", CELL, "--runs", str(len(docs))]
                     + list(argv),
                     command=[sys.executable, str(tmp_path / "stub.py")])
    out = capsys.readouterr()
    calls = [json.loads(line) for line in
             (tmp_path / "calls").read_text().splitlines()]
    last = out.out.strip().splitlines()[-1] if out.out.strip() else ""
    return rc, calls, (json.loads(last) if last.startswith("{") else None), out


def test_spread_is_the_quartiles_distance_over_the_median():
    xs = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs, trim=False) == pytest.approx((q3 - q1) / 102.5)
    # the run farthest from the median goes where that narrows it
    far = xs[:5] + [150.0]
    q1, _, q3 = statistics.quantiles(xs[:5], n=4)
    assert spread(far) == pytest.approx((q3 - q1) / 102.0)
    assert spread(far, trim=False) > 4 * spread(far)
    # and stays where it does not, or where three runs are all there are
    three = [2589.1, 2590.2, 2588.7]
    assert spread(three) == spread(three, trim=False) \
        == pytest.approx(1.5 / 2589.1)
    assert spread([5.0, 5.0, 5.0, 9.0]) == 0.0
    assert spread([7.0]) is None and spread([]) is None
    assert spread([0.0, 0.0]) is None


@pytest.mark.parametrize("share, said", [
    (0.0, "steady"), (0.5 * BOUND, "steady"), (0.5 * BOUND + 1e-9, "wide"),
    (BOUND, "wide"), (BOUND + 1e-9, "over"), (None, "-")])
def test_the_three_words_at_their_edges(share, said):
    assert steady.word(share, BOUND) == said


def test_steady_runs_exit_0_and_unlike_seeds_count_up(tmp_path, capsys):
    docs = [_doc(1000.0 + i, setup=30.0 + i) for i in range(6)]
    docs[2]["pauses"] = 2
    rc, calls, doc, out = _steady(tmp_path, capsys, docs,
                                  "--seeds", "unlike", "--seed", "2147483700")
    assert rc == 0
    assert [c["--seed"] for c in calls] == [
        str(2147483700 + i) for i in range(6)]
    assert all(c["--workload"] == CELL and c["--trace"] == "0"
               and float(c["--seconds"]) == Manifest().doc["run_seconds"]
               for c in calls)
    rows = {r["metric"]: r for r in doc["metrics"]}
    tok = rows["tokens_per_s"]
    assert tok["values"] == [1000.0 + i for i in range(6)]
    assert tok["median"] == 1002.5 and tok["bound"] == BOUND
    assert tok["spread"] == spread(tok["values"]) and tok["word"] == "steady"
    # set-up leaves the call's first run out and shows it apart
    assert rows["setup_s"]["values"] == [31.0, 32.0, 33.0, 34.0, 35.0]
    assert rows["setup_s"]["first_run"] == 30.0
    assert [r["host_pauses"] for r in doc["runs"]] == [0, 0, 2, 0, 0, 0]
    assert "host_pause=2" in out.out and "tokens_per_s" in out.out
    # each run's readings stand beside its value
    assert doc["runs"][0]["readings"] == {"count": 101, "median": 0.3244}
    assert "readings=101 median 0.3244" in out.out


def test_same_seeds_pass_one_seed_every_time(tmp_path, capsys):
    rc, calls, doc, _ = _steady(tmp_path, capsys, [_doc(1000.0)] * 3,
                                "--seeds", "same", "--seed", "77",
                                "--seconds", "5")
    assert rc == 0 and [c["--seed"] for c in calls] == ["77"] * 3
    assert [c["--seconds"] for c in calls] == ["5"] * 3
    assert doc["metrics"][0]["spread"] == 0.0


def test_a_metric_over_its_bound_exits_1(tmp_path, capsys):
    wide = [1000.0 * (1 + 0.7 * BOUND * k) for k in range(6)]
    rc, _, doc, out = _steady(tmp_path, capsys, [_doc(v) for v in wide],
                              "--seeds", "unlike")
    assert doc["metrics"][0]["word"] == "over" and rc == 1
    assert " over" in out.out


def test_setup_alone_over_its_bound_still_exits_0(tmp_path, capsys):
    docs = [_doc(1000.0, setup=30.0 * (1 + 0.2 * k)) for k in range(6)]
    rc, _, doc, _ = _steady(tmp_path, capsys, docs, "--seeds", "unlike")
    assert doc["metrics"][-1]["word"] == "over" and rc == 0


def test_a_run_that_is_not_correct_exits_1(tmp_path, capsys):
    docs = [_doc(1000.0), _doc(1000.0, correct=False), _doc(1000.0)]
    rc, _, doc, out = _steady(tmp_path, capsys, docs, "--seeds", "unlike")
    assert rc == 1 and [r["correct"] for r in doc["runs"]] == [
        True, False, True]
    assert "correct=False" in out.out


@pytest.mark.parametrize("bad", [{"exit": 3}, {"last": "no json here"},
                                 {"last": json.dumps({"metrics": 1})}])
def test_a_child_that_fails_exits_2_and_stops(tmp_path, capsys, bad):
    rc, calls, doc, out = _steady(tmp_path, capsys,
                                  [_doc(1000.0), bad, _doc(1000.0)],
                                  "--seeds", "unlike")
    assert rc == 2 and len(calls) == 2 and doc is None
    assert "run 1" in out.err and "failed" in out.err


def test_without_a_chip_the_real_command_exits_2(capsys):
    """The parent holds no chip and the children find none here."""
    rc = steady.main(["--workload", CELL, "--runs", "2", "--seeds", "same"])
    assert rc == 2 and "no TPU" in capsys.readouterr().err


def test_the_parent_never_imports_jax():
    import subprocess
    subprocess.run([sys.executable, "-c", "import sys, chipbench.steady; "
                    "assert 'jax' not in sys.modules"], check=True,
                   cwd=Manifest().root)
