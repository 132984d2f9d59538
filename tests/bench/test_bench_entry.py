"""``bench/run.py`` refuses to run without a TPU, or without the program."""
import json
import os
import shutil
import subprocess
import sys

from bench import spec


def _run(cwd, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen2-7b.longctx-decode", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=e, capture_output=True, text=True, timeout=300)


def _no_result(out):
    for line in out.strip().splitlines()[-1:]:
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_exits_nonzero_without_a_tpu():
    r = _run(spec.ROOT)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    _no_result(r.stdout)


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    bm = spec.benchmark()
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    for p in bm["paths"]:
        shutil.copytree(spec.ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    _no_result(r.stdout)
