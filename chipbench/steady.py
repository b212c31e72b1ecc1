"""Measure a cell's spread against its bound: run the cell ``n`` times
and say, for each end-to-end metric, whether its runs lie close enough
together for the bound ``BENCHMARK.json`` holds it to.

    python3 -m chipbench.steady --workload <cell> --runs <n> \\
        --seeds unlike|same [--seed <first>] [--seconds <s>]

Each run is ``BENCHMARK.json``'s own ``command`` with ``--workload``,
``--seed``, ``--seconds`` (default: ``run_seconds``) and ``--trace 0``, a
child process of its own, one after another: this process never imports
JAX, because a chip belongs to one process.  ``unlike`` gives run ``i``
the seed ``first + i``, ``same`` gives every run ``first``: the first
reads what the check's sets of unlike seeds will spread by, the second
what one seed repeats within.

For every end-to-end metric of the cell it prints the runs' values (with
each run's ``correct`` flag, the ``host_pause`` lines the program
printed in it — a run under a freeze of the host shows as one — and the
count and median of its readings: slower units or fewer of them), their
median, the spread (``chipbench/readings.py::spread``: the quartiles'
distance over the median, the farthest run left out where that narrows
it, and beside it the spread of all runs), the bound, spread over
bound and one word: ``steady`` (at most half the bound: the check
admits it), ``wide`` (over half: the check may call the bound too
tight) or ``over`` (over the bound itself).  ``setup_s`` leaves out the
call's first run, which compiles where the cache is cold, and its word
is for reading only: the check judges it by its median alone.  The last
line of stdout is one JSON object with all of it.

Exit code 0; 1 if a metric other than ``setup_s`` reads ``over`` or a
run was not ``correct``; 2 if a child failed (without a TPU every child
exits non-zero, so this does too).
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys

from chipbench.manifest import Manifest
from chipbench.readings import spread

#: a run may take this long, compiling included (the contract's limit
#: for a cell's first run in a checkout is 1200 s)
CHILD_TIMEOUT_S = 1500.0
#: the program's line for a phase that took far longer than it does
PAUSE_MARK = "host_pause"
#: the harness's line with the count and quartiles of a run's readings
READINGS_MARK = "chipbench: readings "


def word(spread_share: float | None, bound: float) -> str:
    """``steady``, ``wide`` or ``over``; ``-`` where there is no spread."""
    if spread_share is None:
        return "-"
    if spread_share <= 0.5 * bound:
        return "steady"
    return "wide" if spread_share <= bound else "over"


def run_once(command, workload: str, seed: int, seconds: float, root: str):
    """One child: ``(its result or None, host_pause lines, the end of
    what it printed)``; the result is ``correct``, ``failed``, the
    metrics' values and the count and median of the run's readings
    (one unit of work each, in the driver's own unit: where two runs of
    one seed differ, whether the units were slower or there were fewer
    of them)."""
    argv = list(command) + ["--workload", workload, "--seed", str(seed),
                            "--seconds", f"{seconds:g}", "--trace", "0"]
    try:
        done = subprocess.run(argv, cwd=root, capture_output=True, text=True,
                              timeout=CHILD_TIMEOUT_S)
    except subprocess.TimeoutExpired as e:
        return None, 0, f"no end within {e.timeout:g} s"
    text = done.stdout + done.stderr
    pauses = sum(PAUSE_MARK in line for line in text.splitlines())
    tail = (done.stdout[-1500:] + done.stderr[-1500:]).strip()
    lines = done.stdout.strip().splitlines()
    if done.returncode or not lines:
        return None, pauses, f"exit code {done.returncode}: {tail}"
    try:
        doc = json.loads(lines[-1])
        values = {k: v["value"] for k, v in doc["metrics"].items()}
        doc = {"correct": bool(doc["correct"]), "failed": doc.get("failed"),
               "metrics": values, "readings": _readings(lines)}
    except (ValueError, KeyError, TypeError, AttributeError):
        return None, pauses, f"no result document in the last line: {tail}"
    return doc, pauses, tail


def _readings(lines) -> dict | None:
    """``{"count", "median"}`` from the harness's ``readings`` line."""
    for line in lines:
        if line.startswith(READINGS_MARK):
            try:
                d, _end = json.JSONDecoder().raw_decode(
                    line[len(READINGS_MARK):])
                return {"count": d.get("count"), "median": d.get("median")}
            except ValueError:
                return None
    return None


def summarize(name: str, bound: float, values: list[float]) -> dict:
    mid = statistics.median(values) if values else None
    s = spread(values)
    return {"metric": name, "values": values, "median": mid,
            "spread": s, "spread_all_runs": spread(values, trim=False),
            "bound": bound,
            "spread_over_bound": None if s is None else s / bound,
            "word": word(s, bound)}


def _line(cell: str, unit: str, row: dict) -> str:
    def pct(x):
        return "-" if x is None else f"{100 * x:.3f}%"

    over = row["spread_over_bound"]
    text = (f"steady: {cell} {row['metric']} [{unit}] median "
            f"{row['median']!r} spread {pct(row['spread'])} (all runs "
            f"{pct(row['spread_all_runs'])}) bound {pct(row['bound'])} "
            f"spread/bound {'-' if over is None else format(over, '.2f')} "
            f"{row['word']}")
    if "first_run" in row:
        text += (f" (first run {row['first_run']!r} apart; judged by its "
                 f"median alone)")
    return text


def main(argv=None, *, command=None, root: str | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.steady")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, required=True)
    ap.add_argument("--seeds", choices=("unlike", "same"), required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 520001,
                    help="the first run's seed")
    ap.add_argument("--seconds", type=float, default=None,
                    help="default: BENCHMARK.json's run_seconds")
    args = ap.parse_args(argv)
    if args.runs < 1:
        ap.error("--runs must be at least 1")
    manifest = Manifest(root)
    cell = manifest.cell(args.workload)
    command = command or manifest.doc["command"]
    seconds = manifest.doc["run_seconds"] if args.seconds is None \
        else args.seconds

    runs = []
    for i in range(args.runs):
        seed = args.seed + (i if args.seeds == "unlike" else 0)
        doc, pauses, tail = run_once(command, cell.name, seed, seconds,
                                     manifest.root)
        if doc is None:
            print(f"steady: run {i} (seed {seed}) failed: {tail}",
                  file=sys.stderr, flush=True)
            return 2
        runs.append(dict(doc, seed=seed, host_pauses=pauses))
        print(f"steady: run {i} seed {seed} correct={doc['correct']} "
              f"host_pause={pauses} " + " ".join(
                  f"{k}={v!r}" for k, v in doc["metrics"].items())
              + (f" readings={doc['readings']['count']} median "
                 f"{doc['readings']['median']!r}"
                 if doc["readings"] else ""), flush=True)
        if not doc["correct"]:
            print(f"steady: run {i} (seed {seed}) was not correct: {tail}",
                  file=sys.stderr, flush=True)

    rows = []
    for name in cell.end_to_end:
        # a call's first run compiles where the cache is cold: the check
        # records its set-up apart, and so does this
        used = runs[1:] if name == "setup_s" else runs
        row = summarize(name, manifest.metric(name)["bound"],
                        [r["metrics"][name] for r in used
                         if name in r["metrics"]])
        if name == "setup_s" and "setup_s" in runs[0]["metrics"]:
            row["first_run"] = runs[0]["metrics"]["setup_s"]
        rows.append(row)
        print(_line(cell.name, manifest.metric(name)["unit"], row),
              flush=True)
    incorrect = [r["seed"] for r in runs if not r["correct"]]
    over = [r["metric"] for r in rows
            if r["word"] == "over" and r["metric"] != "setup_s"]
    if incorrect:
        print(f"steady: not correct on seeds {incorrect}", flush=True)
    print(json.dumps({"workload": cell.name, "seeds": args.seeds,
                      "seconds": seconds, "runs": runs, "metrics": rows}),
          flush=True)
    return 1 if over or incorrect else 0


if __name__ == "__main__":
    sys.exit(main())
