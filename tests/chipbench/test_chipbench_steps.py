"""The DDP cell's generator on four host devices at a tiny size (two buckets):
the sound program is correct, and every fault planted under the timed path
and the bfloat16 control make ``correct`` false. The cases run in one child
process, since the host device count is fixed when JAX starts."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).parent


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "steps_cases.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_and_reports_its_metrics(cases):
    r = cases["sound"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"step_ms", "setup_s"}
    assert r["metrics"]["step_ms"]["value"] > 0
    assert 0 <= r["checks"]["ar_rel_err"]["value"] < 1e-6
    assert list(r)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(cases):
    r = cases["sound_traced"]
    assert r["correct"]
    # no TPU plane on the host: only the program's own count is read
    assert r["metrics"] == {"rounds": {"value": 8, "unit": "rounds"}}
    assert "busy_s" in r["device"] and "breakdown" in r


@pytest.mark.parametrize("case", ["exchange_left_out", "half_left_out",
                                  "answer_altered", "control_bf16"])
def test_fault_or_control_is_not_correct(cases, case):
    r = cases[case]
    assert r["correct"] is False
    assert r["checks"]["ar_rel_err"]["value"] > r["checks"]["ar_rel_err"]["limit"]
