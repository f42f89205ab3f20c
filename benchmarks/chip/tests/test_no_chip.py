"""The harness refuses to run where it cannot measure the chip."""

import json
import os
import shutil
import subprocess
import sys

import tiny

RUN = os.path.join(tiny.CHIP, "run.py")


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, RUN if cwd is None else
         os.path.join(cwd, "benchmarks", "chip", "run.py"),
         "--workload", "stablelm-1.6b-l4.seq2k", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)


def _no_result(out):
    for line in out.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")


def test_no_tpu_fails_without_result():
    out = _run(None, {})
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    _no_result(out)


def test_pallas_opt_in_is_refused():
    out = _run(None, {"REPRO_USE_PALLAS": "tpu"})
    assert out.returncode != 0
    _no_result(out)


def test_benchmark_files_alone_fail(tmp_path):
    """A directory with only BENCHMARK.json and the harness: no program."""
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    out = _run(str(tmp_path), {})
    assert out.returncode != 0
    _no_result(out)
