"""Off the TPU the benchmark prints no result and exits non-zero."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, ROOT


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "tp_decode.1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)
    return p.returncode, p.stdout


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_refuses_off_tpu():
    rc, out = _run(ROOT)
    assert rc != 0 and not _has_result(out)


def test_refuses_with_only_its_own_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    rc, out = _run(tmp_path)
    assert rc != 0 and not _has_result(out)
