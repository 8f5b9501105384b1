"""Without a TPU the benchmark fails and prints no result; and it fails in
a directory that holds only BENCHMARK.json and the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from conftest import ROOT


def _run(cwd, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "yi6b.agent",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return False
        except ValueError:
            pass
    return True


def test_no_tpu_fails_and_names_the_platform(tmp_path):
    p = _run(ROOT, tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "platform cpu" in p.stdout and "needs a TPU" in p.stderr


def test_fails_with_only_the_benchmarks_files(tmp_path):
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    co = tmp_path / "co"
    co.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co)
    for path in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, path), co / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(co, tmp_path)
    assert p.returncode != 0
    assert _no_result(p.stdout)
