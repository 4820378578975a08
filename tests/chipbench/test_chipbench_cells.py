"""Cells are found by name, a new cell is added by adding files, the plan
cell's reference catches broken plans, and the entry point refuses a
machine without a TPU."""

import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from itertools import islice

import numpy as np
import pytest

from chipbench import calibrate, cells, harness
from chipbench.device import ROOT
from chipbench.generators import plans

TINY_CONFIG = {"fabric": {"generator": "tpu_v5e_pod", "args": [4, 4]},
               "guarantee": "exact collective result on every member",
               "reduced": {}, "assumed": []}
TINY_TRAFFIC = {"generator": "plans", "loop": "closed",
                "classes": [[[2, 2]], [[2, 4], [4, 2]]],
                "kinds": ["all_gather", "reduce_scatter", "all_reduce",
                          "all_to_all"],
                "payload_mib_per_member": 32}


def _add_cell(root):
    """A 4x4 pod with 2x2 and 2x4 slices, added as files and an entry."""
    (root / "chipbench" / "configs" / "pg-tiny.json").write_text(
        json.dumps(TINY_CONFIG))
    (root / "chipbench" / "traffic" / "tiny-slices.json").write_text(
        json.dumps(TINY_TRAFFIC))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "pg-tiny", "source": "test",
                             "file": "chipbench/configs/pg-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "pg-tiny.tiny-slices",
                               "config": "pg-tiny", "traffic": "tiny-slices",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "pg-slices-v5e-256.cold" in m.get("workloads", []):
            m["workloads"].append("pg-tiny.tiny-slices")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "chipbench").rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_adding_a_cell_is_adding_files(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = _digests(tmp_path)
    _add_cell(tmp_path)
    after = _digests(tmp_path)
    assert {k: after[k] for k in before} == before  # nothing edited
    assert len(after) == len(before) + 2
    script = (
        "import json, sys, time\n"
        f"sys.path[0:0] = [{str(tmp_path)!r}, {str(ROOT / 'src')!r}]\n"
        "import jax, chipbench\n"
        "from chipbench import cells, harness\n"
        f"cell = cells.load({str(tmp_path)!r}, 'pg-tiny.tiny-slices')\n"
        "r = harness.run_cell(cell, jax.devices()[:1], None, seed=3,\n"
        "                     seconds=0.5, traced=False, t0=time.perf_counter())\n"
        "print(json.dumps(dict(r, where=chipbench.__file__,\n"
        "                      generator=cell.generator().__file__)))\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["where"].startswith(str(tmp_path))
    assert r["generator"].startswith(str(tmp_path))
    assert r["correct"] and r["attempted"] > 0
    assert set(r["metrics"]) == {"plan_ms", "plan_ms.p95", "setup_s"}


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "pg-slices-v5e-256.cold", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_past_the_device_check(tmp_path):
    """The entry point end to end on the host CPU, with only its look for a
    TPU stood in for: the plan cell prints its result as the last line."""
    script = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "from chipbench import device, run\n"
        "device.tpu_devices = lambda chips: jax.devices()[:chips]\n"
        "run.main(['--workload', 'pg-slices-v5e-256.cold', '--seed',\n"
        "          str(2**31 + 3), '--seconds', '0.5', '--trace', '0'])\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] and r["device"]["platform"] == "cpu"
    assert list(r["metrics"]) == ["plan_ms", "plan_ms.p95", "setup_s"]
    assert "compilations in the timed window: 0" in p.stdout
    assert p.stderr.strip().splitlines()[-1].startswith(
        "check staged_receives_wrong: 0 limit 0")


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        cells.load(ROOT, "no-such.cell")


def test_request_sequence_is_balanced_and_never_repeats():
    cfg = json.loads((ROOT / "chipbench/configs/pg-slices-v5e-256.json").read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/cold.json").read_text())
    warm, window = plans.requests(cfg, traffic, 2**31 + 5)
    assert len(warm) == 16  # every (shape, kind) once
    seq = list(islice(window, 3060))
    keys = [(k, g) for k, _, g in warm + seq]
    assert len(keys) == len(set(keys))
    assert all(0 <= n < 256 and len(set(g)) == len(g)
               for _, _, g in seq for n in g)
    for b in range(0, len(seq), 12):  # each block holds every (class, kind)
        block = {(k, max(s) * min(s)) for k, s, _ in seq[b:b + 12]}
        assert len(block) == 12
    again = plans.requests(cfg, traffic, 2**31 + 5)
    assert again[0] == warm and list(islice(again[1], 3060)) == seq
    assert list(islice(plans.requests(cfg, traffic, 6)[1], 12)) != seq[:12]


def test_slices_wrap_around_the_torus():
    assert plans.members(2, 2, 15, 15, (16, 16)) == (255, 240, 15, 0)
    assert plans.members(2, 4, 3, 5, (16, 16)) == (53, 54, 55, 56,
                                                    69, 70, 71, 72)


def _tiny_cell():
    cell = cells.load(ROOT, "pg-slices-v5e-256.cold")
    cell.config, cell.traffic = TINY_CONFIG, TINY_TRAFFIC
    return cell


def _run(cell, seed=4):
    import jax

    return harness.run_cell(cell, jax.devices()[:1], None, seed=seed,
                            seconds=0.5, traced=False, t0=time.perf_counter())


def _exchange_left_out(plan):
    return dataclasses.replace(plan, rounds=[])


def _half_left_out(plan):
    return dataclasses.replace(plan, rounds=plan.rounds[::2])


def _answer_altered(plan):
    """The last round's first receiver writes into the trash slot."""
    last = plan.rounds[-1]
    dst = last.perm[0][1]
    recv = last.recv_slot.copy()
    recv[dst] = plan.num_slots
    return dataclasses.replace(plan, rounds=[
        *plan.rounds[:-1], dataclasses.replace(last, recv_slot=recv)])


def test_plan_cell_sound_run_is_correct():
    r = _run(_tiny_cell())
    assert r["correct"]
    assert r["checks"] == {"plan_mismatches": {"value": 0, "limit": 0},
                           "staged_receives_wrong": {"value": 0, "limit": 0}}


@pytest.mark.parametrize("fault", [_exchange_left_out, _half_left_out,
                                   _answer_altered])
def test_plan_cell_fault_is_not_correct(monkeypatch, fault):
    import repro.comms

    real = repro.comms.synthesize_program

    def broken(*a, **kw):
        prog, plan = real(*a, **kw)
        return prog, fault(plan)

    monkeypatch.setattr(repro.comms, "synthesize_program", broken)
    r = _run(_tiny_cell())
    assert r["correct"] is False
    assert r["checks"]["plan_mismatches"]["value"] > 0


def test_plan_cell_control_is_not_correct():
    with calibrate.control("plans"):
        r = _run(_tiny_cell(), seed=9)
    assert r["correct"] is False
    assert r["checks"]["plan_mismatches"]["value"] > 0


def test_staged_rows_hold_every_round_in_whole_blocks():
    """Receive tables of any number of rounds stage in blocks of one shape,
    and the chip's count per row sums back to each plan's receives."""
    from types import SimpleNamespace as NS

    def plan(rounds, devices, slots):
        return NS(num_slots=slots, num_devices=devices, rounds=[
            NS(recv_slot=np.array([r % slots] + [slots] * (devices - 1)))
            for r in range(rounds)])

    plans_ = [plan(300, 4, 3), plan(2, 2, 5), plan(0, 4, 1)]
    recv, trash, owner = plans.staged_rows(plans_, 6, 128)
    assert recv.shape == (384, 6) and trash.shape == (384,)
    assert recv.dtype == trash.dtype == np.int32
    per_row = (recv != trash[:, None]).sum(axis=1)
    assert per_row[len(owner):].sum() == 0  # padding holds no receive
    got = np.bincount(owner, per_row[:len(owner)], minlength=3)
    assert got.tolist() == [300, 2, 0]
    assert plans.staged_rows([], 6, 128)[0].shape == (128, 6)


def test_reference_executes_a_hand_written_all_gather():
    """Two members, one send each way in one round: exact, and wrong once
    a send is dropped."""
    from repro.comms.executor import plan_buffers
    from repro.core.translate import PpermuteProgram, Send

    prog = PpermuteProgram(2, [[Send(0, 1, 0), Send(1, 0, 1)]],
                           {0: (0,), 1: (1,)}, {0: (0, 1), 1: (0, 1)})
    plan = plan_buffers(prog)
    x = np.array([[5], [7]])
    assert plans.mismatches("all_gather", (0, 1), prog, plan, x) == 0
    cut = dataclasses.replace(plan, rounds=[])
    assert plans.mismatches("all_gather", (0, 1), prog, cut, x) == 2
