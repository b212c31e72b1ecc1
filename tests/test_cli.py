"""CLI surface: models / partition / bench commands."""

import json
import os
import subprocess
import sys

import pytest

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "JAX_PLATFORMS": "cpu"}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cli(*args, timeout=300):
    return subprocess.run(
        [sys.executable, "-m", "defer_tpu", *args], cwd=ROOT, env=ENV,
        capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_cli_models():
    r = run_cli("models")
    assert r.returncode == 0
    assert "resnet50" in r.stdout and "bert_base" in r.stdout


@pytest.mark.slow
def test_cli_partition_and_dot(tmp_path):
    dot = str(tmp_path / "g.dot")
    r = run_cli("partition", "--model", "resnet_tiny", "--stages", "4",
                "--dot", dot)
    assert r.returncode == 0, r.stderr
    assert "valid cut points" in r.stdout
    assert "StageSpec(0" in r.stdout
    assert open(dot).read().startswith("digraph")


@pytest.mark.slow
def test_cli_bench_json():
    r = run_cli("bench", "--model", "resnet_tiny", "--stages", "2",
                "--chunk", "4", "--seconds", "1")
    assert r.returncode == 0, r.stderr
    line = r.stdout.strip().splitlines()[-1]
    d = json.loads(line)
    assert d["unit"] == "inferences/sec" and d["value"] > 0
