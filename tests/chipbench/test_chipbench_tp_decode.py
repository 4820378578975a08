"""The TP decode cell: its call list at the published widths, its generator
on four host devices at a small size (a sound run is correct, each control
and the planted fault make ``correct`` false), and the ``round_us`` reader.
The runs share one child process, since the host device count is fixed when
JAX starts."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

from chipbench import cells
from chipbench.device import ROOT
from chipbench.generators import tp_decode
from chipbench.trace import Summary

HERE = Path(__file__).parent
CELL = "tp4-internlm2-20b.decode-2x2"
LIMIT = 4 * 2.0**-8


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "tp_decode_cases.py")],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_call_list_of_the_published_widths():
    cell = cells.load(ROOT, CELL)
    assert tp_decode.step_calls(cell.config) == [
        tp_decode.Call("all_reduce", (32, 6144), 97),
        tp_decode.Call("all_gather", (32, 23168), 1)]
    assert cell.config["dtype"] == "bfloat16" and cell.chips == 4


@pytest.mark.parametrize("layers, hidden, tp, want", [
    (2, 256, 4, [("all_reduce", (32, 256), 5), ("all_gather", (32, 23168), 1)]),
    (48, 6144, 4, [("all_reduce", (32, 6144), 97),
                   ("all_gather", (32, 23168), 1)]),
    (1, 6144, 8, [("all_reduce", (32, 6144), 3),
                  ("all_gather", (32, 11584), 1)]),
])
def test_call_list_follows_the_configuration(layers, hidden, tp, want):
    cfg = {"layers": layers, "hidden": hidden, "padded_vocab": 92672,
           "tensor_parallel": tp, "decode_batch": 32}
    assert tp_decode.step_calls(cfg) == [tp_decode.Call(*c) for c in want]


def test_note_line_lists_the_published_calls():
    import jax.numpy as jnp

    calls = tp_decode.step_calls(cells.load(ROOT, CELL).config)
    assert tp_decode.describe(calls, jnp.dtype(jnp.bfloat16)) == (
        "step calls: 97 x all_reduce bfloat16[32, 6144] (384 KiB per chip), "
        "1 x all_gather bfloat16[32, 23168] (1448 KiB per chip)")


def test_call_list_refuses_an_unpadded_vocab():
    with pytest.raises(ValueError):
        tp_decode.step_calls({"layers": 48, "hidden": 6144,
                              "padded_vocab": 92545, "tensor_parallel": 4,
                              "decode_batch": 32})


def test_sound_run_is_correct_and_reports_its_metrics(cases):
    r = cases["sound"]
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"step_ms", "setup_s"}
    assert r["checks"]["ar_rel_err"]["limit"] == LIMIT
    assert 0 < r["checks"]["ar_rel_err"]["value"] < LIMIT
    assert r["checks"]["ag_mismatches"] == {"value": 0, "limit": 0}
    assert list(r)[-1] == "checks"


def test_traced_run_reports_the_program_counts(cases):
    r = cases["sound_traced"]
    assert r["correct"]
    # no TPU plane on the host: only the program's own count is read
    assert r["metrics"] == {"rounds": {"value": 8, "unit": "rounds"}}


def test_xla_builtin_reads_correct(cases):
    assert cases["xla_builtin"]["correct"]


@pytest.mark.parametrize("case, check", [("member_left_out", "ar_rel_err"),
                                         ("fp8_cast", "ar_rel_err"),
                                         ("rows_swapped", "ag_mismatches")])
def test_control_or_fault_is_not_correct(cases, case, check):
    r = cases[case]
    assert r["correct"] is False
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]


def _summary(runs):
    mod = tp_decode.STEP_MODULE
    return Summary(window_s=1.0, busy_s=0.5, chips=4, op_s={},
                   module_runs={mod: runs} if runs else {},
                   permute_s={mod: 0.3}, other_s={mod: 0.2},
                   loop_s={mod: 0.1}, idle_gaps=[])


def test_round_us_reads_device_time_per_round():
    read = cells.load(ROOT, CELL).reader("round_us")
    ctx = SimpleNamespace(trace=_summary(100), step_module=tp_decode.STEP_MODULE,
                          counters={"rounds": 8, "rounds_per_step": 780})
    # 0.6 s over 100 runs is 6 ms a step, over 780 rounds
    assert read(ctx) == pytest.approx(6e-3 / 780 * 1e6)


@pytest.mark.parametrize("runs, counters", [(0, {"rounds_per_step": 780}),
                                            (100, {"rounds": 8})])
def test_round_us_reads_nothing_without_its_inputs(runs, counters):
    read = cells.load(ROOT, CELL).reader("round_us")
    ctx = SimpleNamespace(trace=_summary(runs), counters=counters,
                          step_module=tp_decode.STEP_MODULE)
    assert read(ctx) is None
