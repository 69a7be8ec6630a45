"""The command line: no chip, no result; and a benchmark tree without
the program cannot run."""
import json
import os
import shutil
import subprocess
import sys

from chipbench.lib import harness

ROOT = harness.ROOT


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "table3.serve",
         "--seed", str(2 ** 31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_exits_nonzero_with_no_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no CPU fallback" in p.stderr
    _no_result(p)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    _no_result(p)
