"""Without a TPU the benchmark prints no result and exits non-zero, and
so it does in a directory that holds only the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

from bench import spec

CMD = [sys.executable, "-m", "bench.run", "--workload", "smollm-135m.chat",
       "--seed", "3", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_cpu_run_exits_nonzero_and_names_the_device():
    p = subprocess.run(CMD, cwd=spec.ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "cpu" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in json.loads((spec.ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(spec.ROOT / d, tmp_path / d, ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip()
