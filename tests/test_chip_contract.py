"""The contracts of running on the chip (and of refusing to pretend).

No TPU means failure, not a fallback: ``bench.py`` and ``chip_smoke.py``
exit non-zero fast and print no result; the chip is identified from its
``device_kind`` alone; a local chain runs — parent and children — on the
CPU platform and says so; ``benchmarks/run.py`` labels each row with the
platform that measured it and fails when a row failed; the persistent
compile cache has exactly one setter, which the environment overrides.
"""

import importlib.util
import inspect
import json
import os
import subprocess
import sys
import time
import types

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(path, *, cwd=REPO, timeout=120):
    env = dict(os.environ, JAX_PLATFORMS="cpu")  # this sandbox: no chip
    t0 = time.monotonic()
    r = subprocess.run([sys.executable, path], capture_output=True,
                       text=True, timeout=timeout, env=env, cwd=cwd)
    return r, time.monotonic() - t0


def _json_lines(stdout):
    out = []
    for ln in stdout.splitlines():
        try:
            out.append(json.loads(ln))
        except ValueError:
            pass
    return [d for d in out if isinstance(d, dict)]


# ---------------------------------------------------------------------------
# no chip -> no result
# ---------------------------------------------------------------------------

def test_bench_without_tpu_exits_nonzero_and_prints_no_value():
    r, dt = _run_script(os.path.join(REPO, "bench.py"))
    assert r.returncode != 0, r.stdout
    assert dt < 60, f"refusal took {dt:.0f}s"
    assert not any("value" in d for d in _json_lines(r.stdout)), r.stdout
    assert "needs a TPU" in r.stderr and "cpu" in r.stderr


def test_bench_has_no_probe_subprocess_or_cpu_path():
    src = open(os.path.join(REPO, "bench.py")).read()
    for gone in ("subprocess", "threading", "DEFER_BENCH_CPU",
                 "resnet_tiny", "last_good", "os._exit"):
        assert gone not in src, gone


def test_chip_smoke_refuses_cpu_fast_and_names_the_platform():
    r, dt = _run_script(os.path.join(REPO, "chip_smoke.py"))
    assert r.returncode != 0
    assert dt < 30, f"refusal took {dt:.0f}s"
    assert "platform=cpu" in r.stdout
    assert "needs platform=tpu" in r.stderr
    assert not any("ok" in d for d in _json_lines(r.stdout)), r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r, _ = _run_script(str(tmp_path / "chip_smoke.py"), cwd=str(tmp_path))
    assert r.returncode != 0
    assert not any("ok" in d for d in _json_lines(r.stdout)), r.stdout


@pytest.mark.slow
def test_chip_smoke_phases_at_toy_size_on_the_cpu_mesh():
    """Keeps the script from rotting between chip runs: every phase, toy
    models, 4 host devices (also a CI step)."""
    sys.path.insert(0, REPO)
    import chip_smoke
    report = chip_smoke.run_phases(chip_smoke.TINY, jax.devices()[:4])
    assert {"ring.buffer", "ring.int8", "ring.1_stage", "serve.tensor",
            "decode.pipelined", "serve.decode",
            "export.transformer_stage"} <= set(report["phases"])


# ---------------------------------------------------------------------------
# the chip is what device_kind says it is
# ---------------------------------------------------------------------------

def _dev(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_identify_chip_reads_device_kind_only(monkeypatch):
    from defer_tpu.utils import hw
    assert "environ" not in inspect.getsource(hw)
    for name in ("TPU_GEN", "TPU_ACCELERATOR_TYPE"):
        monkeypatch.setenv(name, "v4")
    assert hw.identify_chip(_dev("tpu", "TPU v5 lite")) == "v5e"
    assert hw.identify_chip(_dev("tpu", "TPU v5e")) == "v5e"
    assert hw.identify_chip(_dev("tpu", "TPU v4")) == "v4"
    assert hw.identify_chip(_dev("cpu", "cpu")) == "unknown"


def test_unknown_tpu_kind_is_an_error_but_cpu_is_not():
    from defer_tpu.utils import hw
    assert hw.detect_chip(_dev("cpu", "cpu")) == "unknown"
    assert hw.detect_chip(_dev("tpu", "TPU v5 lite")) == "v5e"
    with pytest.raises(ValueError, match="TPU v99"):
        hw.detect_chip(_dev("tpu", "TPU v99"))


def test_cost_model_says_assumed_target_off_chip(monkeypatch):
    from defer_tpu.models import resnet_tiny
    from defer_tpu.plan import StageCostModel
    g = resnet_tiny()
    cm = StageCostModel(g)  # CPU process, nothing named
    assert (cm.gen, cm.target) == ("v5e", "assumed")
    assert cm.describe()["target"] == "assumed"
    assert StageCostModel(g, gen="v4").target == "assumed"
    with pytest.raises(ValueError, match="v99"):
        StageCostModel(g, gen="v99")
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_dev("tpu", "TPU v5 lite")])
    assert StageCostModel(g).target == "detected"
    monkeypatch.setattr(jax, "devices", lambda *a: [_dev("tpu", "TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        StageCostModel(g)


def test_profile_trace_failure_fails_the_session_on_tpu(monkeypatch):
    from defer_tpu.obs.profile import ProfileSession

    def boom(_dir):
        raise RuntimeError("no profiler")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    ProfileSession({}, jax_trace_dir="/nonexistent").start()  # cpu: a note
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sess = ProfileSession({}, jax_trace_dir="/nonexistent")
    with pytest.raises(RuntimeError, match="no profiler"):
        sess.start()
    with pytest.raises(RuntimeError, match="never started"):
        sess.stop()


# ---------------------------------------------------------------------------
# one process per chip host: local chains and script rows are CPU, and say so
# ---------------------------------------------------------------------------

def test_local_chain_env_is_stated_and_parent_must_match(monkeypatch):
    from defer_tpu.runtime import node
    assert node.LOCAL_CHAIN_ENV["JAX_PLATFORMS"] == "cpu"
    env = node._local_chain_env(None)
    assert env["JAX_PLATFORMS"] == "cpu" and "PATH" in env
    assert node._local_chain_env({"JAX_PLATFORMS": "x"})[
        "JAX_PLATFORMS"] == "x"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU platform"):
        node._local_chain_env(None)
    # a caller that names the children's environment owns that choice
    assert node._local_chain_env({"A": "b"})["A"] == "b"


def test_chain_row_carries_platform(monkeypatch, capsys):
    from defer_tpu import cli
    from defer_tpu.runtime import node

    def fake_run_chain(stages, params, xs, *, stats_out=None, **kw):
        fwd = jax.jit(stages[0].graph.apply)
        stats_out.extend({"stage": k, "tier": "tcp"}
                         for k in range(len(stages)))
        return [np.asarray(fwd(params, x)) for x in xs]

    monkeypatch.setattr(node, "run_chain", fake_run_chain)
    cli.main(["chain", "--model", "resnet_tiny", "--stages", "2",
              "--count", "2"])
    row = _json_lines(capsys.readouterr().out)[-1]
    assert row["platform"] == "cpu" and row["stages"] == 2
    assert jax.config.jax_platforms == "cpu"


def _load_benchmarks_run():
    spec = importlib.util.spec_from_file_location(
        "benchmarks_run", os.path.join(REPO, "benchmarks", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_script_rows_are_stamped_with_the_childs_platform(monkeypatch):
    run = _load_benchmarks_run()
    seen = {}

    def fake_run(argv, *, env, **kw):
        seen.update(env)
        return types.SimpleNamespace(
            returncode=0, stderr="",
            stdout='noise\n{"metric": "m", "value": 2.0, "unit": "x"}\n')

    monkeypatch.setattr(subprocess, "run", fake_run)
    row = run.run_script_row("serve_smoke.py")
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert row["platform"] == "cpu" and row["value"] == 2.0


def test_benchmarks_run_fails_when_a_row_failed(monkeypatch, tmp_path,
                                                capsys):
    run = _load_benchmarks_run()
    ledger = tmp_path / "l.jsonl"
    monkeypatch.setattr(sys, "argv", ["run.py", "--configs",
                                      "no_such_bench", "--ledger",
                                      str(ledger)])
    with pytest.raises(SystemExit) as e:
        run.main()
    assert e.value.code == 1
    row = json.loads(ledger.read_text().splitlines()[-1])
    assert row["status"] == "failed" and row["platform"] == "cpu"
    assert "backend" not in row


# ---------------------------------------------------------------------------
# the compile cache: one setter, placed from outside when asked
# ---------------------------------------------------------------------------

def test_compile_cache_default_is_the_fixed_in_checkout_dir(monkeypatch):
    from defer_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.DEFAULT_DIR == os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == compile_cache.DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR
    assert ".jax_cache/" in open(
        os.path.join(REPO, ".gitignore")).read().split()


def test_compile_cache_env_wins_and_code_sets_nothing(monkeypatch):
    from defer_tpu.utils import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.configure() == "/some/dir"
    assert calls == []


def test_compile_cache_has_exactly_one_setter_in_the_tree():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out", "tests")]
        for f in files:
            if f.endswith(".py"):
                p = os.path.join(root, f)
                if "compilation_cache_dir" in open(p).read():
                    hits.append(os.path.relpath(p, REPO))
    assert hits == ["defer_tpu/utils/compile_cache.py"], hits
