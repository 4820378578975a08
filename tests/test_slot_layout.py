"""The slot buffer's layout: a 1-D chunk that fills whole TPU tiles is held
as rows of 128 lanes, any other chunk as it is. The layout moves bytes only,
so every kind must return bit for bit what the flat layout returns on the
same data, and what a plain reference returns."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from _exec_harness import emulate_collective

from repro import tracing
from repro.comms import primitives
from repro.comms.executor import slot_rows
from repro.core import CollectiveRequest
from repro.topology import mesh2d

KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
FULL, SUBSET = (0, 1, 2, 3), (0, 3)  # (0, 3) routes through a non-member


@pytest.mark.parametrize("shape, dtype, rows", [
    ((1638400,), jnp.float32, (12800, 128)),
    ((1024,), jnp.float32, (8, 128)),
    ((1536,), jnp.float32, None),  # whole lanes, not whole sublanes
    ((1000,), jnp.float32, None),
    ((2048,), jnp.bfloat16, (16, 128)),
    ((1024,), jnp.bfloat16, None),
    ((4096,), jnp.int8, (32, 128)),
    ((2048,), jnp.int8, None),
    ((8, 128), jnp.float32, None),  # not 1-D
    ((0,), jnp.float32, None),
])
def test_slot_rows_shape_and_counter(shape, dtype, rows):
    before = tracing.counters()
    got = slot_rows(shape, dtype)
    after = tracing.counters()
    assert got == (rows or shape)
    engaged = "slot_layout.rows" if rows else "slot_layout.flat"
    other = "slot_layout.flat" if rows else "slot_layout.rows"
    assert after.get(engaged, 0) == before.get(engaged, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)


# (kind, group, dtype, chunk length, layout the chunk takes)
CASES = [
    *((k, FULL, "float32", 2048, "rows") for k in KINDS),
    *((k, FULL, "float32", 1000, "flat") for k in KINDS),
    *((k, FULL, "bfloat16", 2048, "rows") for k in KINDS),
    ("all_reduce", FULL, "bfloat16", 1024, "flat"),
    *((k, SUBSET, "float32", 2048, "rows") for k in KINDS),
    # tensor-parallel decode's payloads, given as each chip's 2-D input: a
    # [32, 6144] bf16 all-reduce and a [32, 23168] bf16 logits all-gather
    *((k, group, "bfloat16", shape, "flat") for group in (FULL, SUBSET)
      for k, shape in (("all_reduce", [32, 6144]),
                       ("all_gather", [32, 23168]))),
]


def _case_id(case):
    kind, group, dtype, chunk, _ = case
    if isinstance(chunk, list):
        chunk = "x".join(map(str, chunk))
    return f"{kind}-g{len(group)}-{dtype}-{chunk}"


_SCRIPT = r"""
import json, sys
import numpy as np
import jax.numpy as jnp
from _exec_harness import make_input, run_on_mesh
from repro import tracing
from repro.comms import primitives
from repro.core import CollectiveRequest
from repro.topology import mesh2d

topo = mesh2d(2, 2)
cases, out = json.loads(sys.argv[1]), sys.argv[2]
rows_layout = primitives.slot_rows
saved, counts, executor = {}, [], []
for i, (kind, group, dtype, chunk, _) in enumerate(cases):
    if isinstance(chunk, list):  # each chip's whole input
        x = np.random.default_rng(i).standard_normal((4, *chunk)).astype(
            jnp.dtype(dtype))
    else:
        x = make_input(kind, group, 4, payload=chunk, seed=i,
                       dtype=jnp.dtype(dtype))
    spec = CollectiveRequest(kind, group=tuple(group))
    before = tracing.counters()
    got = run_on_mesh(kind, topo, spec, x, n=4)
    after = tracing.counters()
    counts.append({k: after.get(k, 0) - before.get(k, 0)
                   for k in ("slot_layout.rows", "slot_layout.flat")})
    prog, plan = primitives.synthesize_program(topo, spec)
    executor.append({"rounds": [after.get("executor.rounds", 0)
                                - before.get("executor.rounds", 0),
                                prog.num_rounds],
                     "waves": [after.get("executor.waves", 0)
                               - before.get("executor.waves", 0),
                               len(plan.waves())]})
    primitives.slot_rows = lambda shape, dtype: tuple(shape)
    flat = run_on_mesh(kind, topo, spec, x, n=4)
    primitives.slot_rows = rows_layout
    for name, a in (("x", x), ("got", got), ("flat", flat)):
        saved[f"{i}_{name}"] = a.view(f"u{a.dtype.itemsize}")
np.savez(out + "/arrays.npz", **saved)
json.dump({"counts": counts, "executor": executor},
          open(out + "/counts.json", "w"))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every case run twice on 4 host CPU devices, with the layout the
    chunk takes and with the flat layout forced (a child process: the
    device count is fixed when JAX starts)."""
    root = Path(__file__).resolve().parents[1]
    out = tmp_path_factory.mktemp("slot_layout")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root / "src"),
                                           str(root / "tests")]))
    p = subprocess.run([sys.executable, "-c", _SCRIPT, json.dumps(CASES),
                        str(out)], capture_output=True, text=True,
                       timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    with np.load(out / "arrays.npz") as npz:
        arrays = dict(npz)
    counted = json.loads((out / "counts.json").read_text())

    def case(c):
        i = CASES.index(c)
        dtype = jnp.dtype(c[2])
        x, got, flat = (arrays[f"{i}_{n}"].view(dtype)
                        for n in ("x", "got", "flat"))
        return x, got, flat, counted["counts"][i], counted["executor"][i]

    return case


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_layout_is_bit_identical_to_flat(case, runs):
    _, got, flat, *_ = runs(case)
    np.testing.assert_array_equal(got.view(f"u{got.dtype.itemsize}"),
                                  flat.view(f"u{flat.dtype.itemsize}"))


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_layout_matches_plain_reference(case, runs):
    """Data movement exactly; a sum of g terms within (g - 1) units of
    round-off of the sum of magnitudes, in any order."""
    kind, group, _, _, _ = case
    x, got, *_ = runs(case)
    gl, g = list(group), len(group)
    xf = x.astype(np.float64)
    eps = float(jnp.finfo(x.dtype).eps)
    for i, d in enumerate(gl):
        out = got[d].astype(np.float64)
        if kind == "all_gather":
            np.testing.assert_array_equal(out, xf[gl], err_msg=f"device {d}")
        elif kind == "all_to_all":
            np.testing.assert_array_equal(out, xf[gl, i], err_msg=f"device {d}")
        else:
            terms = xf[gl, i] if kind == "reduce_scatter" else xf[gl]
            bound = (g - 1) * eps * np.abs(terms).sum(0)
            assert (np.abs(out - terms.sum(0)) <= bound).all(), f"device {d}"


@pytest.mark.parametrize("case", [c for c in CASES if c[1] == SUBSET],
                         ids=_case_id)
def test_non_members_return_exact_zeros(case, runs):
    _, got, *_ = runs(case)
    for d in sorted(set(FULL) - set(case[1])):
        assert not got[d].view(f"u{got.dtype.itemsize}").any(), f"device {d}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_counter_says_which_layout(case, runs):
    *_, counts, _ = runs(case)
    layout = case[4]
    assert counts == {"slot_layout.rows": int(layout == "rows"),
                      "slot_layout.flat": int(layout == "flat")}


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_executor_rounds_counts_the_programs_rounds(case, runs):
    """Tracing one collective adds its program's ``num_rounds``."""
    *_, executor = runs(case)
    counted, planned = executor["rounds"]
    assert counted == planned > 0


# the waves each kind's plan on the 2x2 runs in: its rounds two at a time,
# the full group's all-to-all's five as two and three
WAVES = {"all_reduce": 4, "all_gather": 2, "reduce_scatter": 2,
         "all_to_all": 2}


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_executor_waves_counts_the_plans_waves(case, runs):
    """Tracing one collective adds its plan's waves: 4 for the 2x2
    all-reduce's 8 rounds, 2 for the all-gather's 4."""
    *_, executor = runs(case)
    counted, planned = executor["waves"]
    assert counted == planned == WAVES[case[0]]


@pytest.mark.parametrize(
    "case", [c for c in CASES if c[0] in ("all_reduce", "all_gather")],
    ids=_case_id)
def test_waves_are_bit_equal_to_rounds_in_series(case, runs):
    """The executor runs each wave's permutes together; what it returns is
    bit for bit a numpy emulation that runs the plan's rounds one after
    another, in the payload's dtype."""
    kind, group, *_ = case
    x, got, *_ = runs(case)
    prog, plan = primitives.synthesize_program(
        mesh2d(2, 2), CollectiveRequest(kind, group=tuple(group)))
    want = emulate_collective(kind, prog, plan, group, x)
    assert want.dtype == got.dtype
    np.testing.assert_array_equal(got.view(f"u{got.dtype.itemsize}"),
                                  want.view(f"u{want.dtype.itemsize}"))
