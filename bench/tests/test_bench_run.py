"""``bench/run.py`` refuses to run without a TPU, and without the
program beside it, and prints no result then."""

import json
import os
import shutil
import subprocess
import sys

import benchkit

CHECKOUT = os.path.dirname(benchkit.BENCH)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "deepcam-train",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        return False
    return True


def test_exits_non_zero_on_the_cpu():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert _no_result(p.stdout)


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(benchkit.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
