"""``bench/run.py`` refuses to run without a TPU: it exits non-zero and
prints no result, also from a directory that holds only BENCHMARK.json and
the benchmark's own files."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ARGS = ["--workload", "qwen1.5-4b-tp1.code", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py"] + ARGS, cwd=root,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def _no_result(p):
    assert p.returncode != 0
    lines = p.stdout.strip().splitlines()
    assert not lines or not lines[-1].startswith("{")


def test_refuses_without_tpu():
    p = _run(ROOT)
    _no_result(p)
    assert "no TPU" in p.stderr


def test_refuses_in_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))


def test_refuses_a_configuration_without_a_limit():
    """A configuration whose limit has not been read on the chip
    (``check.max_logit_gap`` null) is refused before anything is built."""
    import pytest

    from bench.harness.cell import run_cell
    from bench.harness.spec import Cell
    from bench.tests import tiny_run
    cell = Cell(tiny_run.bench("tiny-f32", "tiny-open"),
                "tiny-f32.tiny-open", data_dir=tiny_run.DATA)
    cell.config["check"] = {"max_logit_gap": None}
    with pytest.raises(SystemExit, match="max_logit_gap is not set"):
        run_cell(cell, 1, 1.0, False, [], 0.0, log=lambda m: None)
