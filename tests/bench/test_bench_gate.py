"""run.py on a machine without a chip: no result, a non-zero exit code, and
the platform it found named. The harness has no option that lets it pass."""

import os
import shutil
import subprocess
import sys

from benchmark import plugins

RUN = [sys.executable, os.path.join(plugins.HERE, "run.py"), "--workload",
       "mistral7b.agent-sessions", "--seed", "3000000011", "--seconds", "2",
       "--trace", "0"]


def _no_result(proc):
    assert proc.returncode != 0
    last = (proc.stdout.strip().splitlines() or [""])[-1]
    assert '"correct"' not in last and '"metrics"' not in proc.stdout


def test_run_fails_at_the_device_gate_on_the_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(RUN, capture_output=True, text=True, env=env,
                          timeout=300, cwd=plugins.REPO)
    _no_result(proc)
    assert "JAX found platform 'cpu'" in proc.stderr
    # ... and standard output says at which stage, with the child's own words
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("benchmark: no result: boot: ")
    assert "exit code 1" in last and "JAX found platform 'cpu'" in last


def test_run_has_no_option_that_skips_the_gate():
    proc = subprocess.run(RUN[:2] + ["--help"], capture_output=True, text=True,
                          timeout=60)
    options = {w for w in proc.stdout.split() if w.startswith("--")}
    assert options <= {"--help", "--workload", "--seed", "--seconds", "--trace"}


def test_run_fails_where_only_the_benchmark_was_copied(tmp_path):
    shutil.copy(os.path.join(plugins.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(plugins.HERE, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    argv = [sys.executable, str(tmp_path / "benchmark" / "run.py")] + RUN[2:]
    proc = subprocess.run(argv, capture_output=True, text=True, env=env,
                          timeout=300, cwd=tmp_path)
    _no_result(proc)
