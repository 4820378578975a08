"""Waves: the executor issues consecutive rounds of a plan together unless a
round sends a slot that an earlier round of its wave writes. Running each
wave with its sends read before any receive must give, on every device and
in every slot, what running the rounds one after another gives."""

import numpy as np
import pytest

from _exec_harness import KINDS, run_rounds

from repro.comms import primitives
from repro.comms.executor import (BufferPlan, RoundTables, plan_buffers,
                                  round_waves)
from repro.core import CollectiveRequest
from repro.topology import mesh2d, ring
from repro.topology.generators import grid_hypercube


def _plan(topo, kind, group, **kw):
    return primitives.synthesize_program(
        topo, CollectiveRequest(kind, group=tuple(group), **kw))[1]


@pytest.mark.parametrize("kind, group, waves", [
    ("all_reduce", (0, 1, 2, 3), [[0, 1], [2, 3], [4, 5], [6, 7]]),
    ("all_reduce", (0, 3), [[0, 1], [2, 3], [4, 5], [6, 7]]),
    ("all_gather", (0, 1, 2, 3), [[0, 1], [2, 3]]),
    ("all_gather", (0, 3), [[0, 1], [2, 3]]),
])
def test_waves_of_the_2x2_plans(kind, group, waves):
    plan = _plan(mesh2d(2, 2), kind, group)
    assert plan.waves() == waves


def test_waves_are_built_once_and_not_by_plan_buffers():
    prog, plan = primitives.synthesize_program(
        mesh2d(2, 2), CollectiveRequest("all_reduce", group=(0, 1, 2, 3)))
    fresh = plan_buffers(prog)
    assert fresh._waves is None  # planning leaves them to the first trace
    first = fresh.waves()
    assert fresh.waves() is first
    assert first == plan.waves()


def _round(perm, send, recv, is_recv, n=3):
    """Hand-built round tables on ``n`` devices: ``send``/``recv`` map a
    device to its slot; devices left out hold slot 0 / the trash slot 4."""
    rt = RoundTables(list(perm), np.zeros(n, np.int32),
                     np.full(n, 4, np.int32), np.zeros(n, bool),
                     np.zeros(n, bool))
    for d, s in send.items():
        rt.send_slot[d] = s
    for d, s in recv.items():
        rt.recv_slot[d] = s
    for d in is_recv:
        rt.is_recv[d] = True
    return rt


def _waves(*rounds):
    return round_waves(BufferPlan(3, 4, {}, list(rounds)))


def test_a_non_senders_slot_zero_is_no_read():
    # round 0 writes device 1's slot 0; in round 1 device 1 sends nothing,
    # so its send_slot of 0 is no read and round 1 joins the wave
    a = _round([(0, 1)], {0: 2}, {1: 0}, [1])
    b = _round([(2, 0)], {2: 1}, {0: 3}, [0])
    assert _waves(a, b) == [[0, 1]]
    # device 1 sending that slot does read it, and splits the wave
    c = _round([(1, 2)], {1: 0}, {2: 3}, [2])
    assert _waves(a, c) == [[0], [1]]


def test_a_non_receivers_write_is_no_write():
    # round 0's table names slot 2 for device 0, which receives nothing:
    # device 0 sending slot 2 in round 1 reads nothing the wave wrote
    a = _round([(0, 1)], {0: 1}, {0: 2, 1: 0}, [1])
    b = _round([(0, 2)], {0: 2}, {2: 0}, [2])
    assert _waves(a, b) == [[0, 1]]


def test_a_read_of_any_earlier_round_of_the_wave_splits_it():
    # round 2 reads what round 0 wrote, not what round 1 wrote
    a = _round([(0, 1)], {0: 0}, {1: 1}, [1])
    b = _round([(2, 0)], {2: 0}, {0: 1}, [0])
    c = _round([(1, 2)], {1: 1}, {2: 2}, [2])
    assert _waves(a, b, c) == [[0, 1], [2]]
    assert _waves() == []


# (topology, kind, group, request keywords): every executable kind on the
# 2x2 and on a one-way ring, process-group subsets of a 4x4 mesh, and one
# hierarchical and one pipelined request
_TOPOS = {"mesh2x2": lambda: mesh2d(2, 2), "ring8": lambda: ring(8),
          "mesh4x4": lambda: mesh2d(4, 4),
          "grid23": lambda: grid_hypercube(2, 3),
          "ring8_bidir": lambda: ring(8, bidirectional=True)}
PLANS = [
    *(("mesh2x2", k, (0, 1, 2, 3), {}) for k in KINDS),
    *(("ring8", k, tuple(range(8)), {}) for k in KINDS),
    *(("mesh4x4", k, g, {}) for k in KINDS
      for g in ((0, 1, 4, 5), (0, 5, 10, 15), (1, 2, 6, 9, 14))),
    ("grid23", "all_reduce", tuple(range(8)), {"hierarchy": "always"}),
    ("ring8_bidir", "all_reduce", tuple(range(8)), {"pipelined": True}),
]


def _plan_id(p):
    topo, kind, group, kw = p
    return "-".join([topo, kind, "g" + ".".join(map(str, group)), *kw])


def _buffers(plan, seed):
    """Exact integers, distinct in every slot, the trash slot too, so that
    any read out of order shows."""
    return np.random.default_rng(seed).integers(
        -2**40, 2**40, (plan.num_devices, plan.buffer_slots, 3))


@pytest.mark.parametrize("case", PLANS, ids=_plan_id)
def test_waves_give_what_rounds_in_series_give(case):
    topo, kind, group, kw = case
    plan = _plan(_TOPOS[topo](), kind, group, **kw)
    waves = plan.waves()
    assert [r for w in waves for r in w] == list(range(len(plan.rounds)))
    assert all(waves)
    buf = _buffers(plan, seed=len(plan.rounds))
    np.testing.assert_array_equal(run_rounds(plan, buf, waves),
                                  run_rounds(plan, buf))


def test_interpreter_sees_a_wave_that_breaks_a_dependence():
    """The check above can fail: one wave of all the rounds of the one-way
    ring's all-reduce, each of which reads the last one's receive, does not
    give what the rounds in series give."""
    plan = _plan(ring(8), "all_reduce", tuple(range(8)))
    assert plan.waves() == [[r] for r in range(len(plan.rounds))]
    buf = _buffers(plan, seed=0)
    one_wave = [list(range(len(plan.rounds)))]
    assert (run_rounds(plan, buf, one_wave) != run_rounds(plan, buf)).any()
