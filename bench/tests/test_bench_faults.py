"""A whole benchmark run at a tiny size on the CPU (past the harness's look
for a chip) passes its correctness check on the sound program, and fails it
for each fault planted in the timed path underneath."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import tiny_run

HERE = os.path.dirname(os.path.abspath(__file__))


def test_sound_run_is_correct():
    out = tiny_run.run("tiny-f32", "tiny-open")
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}


def test_closed_loop_run_is_correct():
    out = tiny_run.run("tiny-f32", "tiny-closed")
    assert out["correct"], out["checks"]
    assert out["metrics"]["output_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["token", "state", "half"])
def test_fault_makes_run_incorrect(fault):
    out = tiny_run.run("tiny-f32", "tiny-open", fault)
    assert not out["correct"], out["checks"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > 100 * gap["limit"]


def _tp4(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, os.path.join(HERE, "tiny_run.py"),
                        "tiny-f32-tp4", "tiny-open", fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange", False)])
def test_four_devices(fault, correct):
    out = _tp4(fault)
    assert out["device"]["count"] == 4
    assert out["correct"] is correct, out["checks"]
