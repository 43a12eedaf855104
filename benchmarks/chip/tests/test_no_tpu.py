"""Without a TPU the run exits non-zero and prints no result line."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "mc_backlog", "--seed", "3000000007", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_nonzero_without_tpu():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout)
