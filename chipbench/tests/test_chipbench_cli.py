"""The command refuses to run without a TPU: it exits non-zero and
prints no result, and there is no fallback to the CPU."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
ARGS = ["--workload", "c2-easy-backlog", "--seed", str(2**31 + 5),
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chipbench/run.py"] + ARGS,
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        assert "correct" not in rec and "metrics" not in rec


def test_no_tpu_no_result():
    r = _run(ROOT)
    assert r.returncode == 2
    assert "TPU" in r.stderr
    _no_result(r.stdout)


def test_bare_checkout_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and the benchmark's files."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    _no_result(r.stdout)
