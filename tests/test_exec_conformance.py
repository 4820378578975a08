"""End-to-end numerical conformance of executed PCCL plans on a jax mesh.

The differential harness the executor work hangs off: every collective kind
x synthesis route x process-group shape runs as a shard_map ppermute program
on an 8-device host mesh and is compared against ``jax.lax`` built-ins
and/or pure-numpy references — bit-identical for data-movement collectives,
fixed-order tolerance for reductions. Routes cover flat, hierarchical
sequential, chunk-pipelined, switch-unrolled (multi_pod DCI), TE-routed,
time-reversed (reduce_scatter *is* the time-reversed all_gather route), and
``PlanRepairer``-repaired plans; strict-subset process groups check that
non-participant buffers come back untouched even when those devices forward
traffic for the group.

Run with ``XLA_FLAGS=--xla_force_host_platform_device_count=8 pytest -m mesh``.
"""

from __future__ import annotations

import numpy as np
import pytest

from _exec_harness import (
    KINDS,
    assert_conformant,
    check_collective,
    make_input,
    reference,
    run_on_mesh,
)

pytestmark = pytest.mark.mesh

N = 8

# route name -> (topology builder, CollectiveRequest keywords). The two
# multi_pod routes traverse a DCI switch node, so they only execute through
# the translator's switch unrolling; te_multipod additionally forces the
# traffic-engineered gateway assignment on skewed uplinks.
ROUTES = {
    "flat_ring": ("ring8", {"hierarchy": "never"}),
    "hier_grid": ("grid23", {"hierarchy": "always"}),
    "hier_multipod": ("mp222", {"hierarchy": "always"}),
    "te_multipod": ("mp222_skew", {"hierarchy": "always",
                                   "gateway_strategy": "te"}),
}

_TOPO_CACHE: dict[str, object] = {}


def build_topo(name: str):
    if name not in _TOPO_CACHE:
        from repro.topology import line, ring, torus2d
        from repro.topology.generators import grid_hypercube, multi_pod

        _TOPO_CACHE[name] = {
            "ring8": lambda: ring(8, bidirectional=True),
            "line8": lambda: line(8),
            "torus24": lambda: torus2d(2, 4),
            "grid23": lambda: grid_hypercube(2, 3),
            "mp222": lambda: multi_pod(2, 2, 2, unit_links=True,
                                       dci_ports_per_pod=2),
            "mp222_skew": lambda: multi_pod(2, 2, 2,
                                            dci_port_gbps=[100.0, 10.0]),
        }[name]()
    return _TOPO_CACHE[name]


def request(kind, group, **kw):
    from repro.core import CollectiveRequest

    return CollectiveRequest(kind, group=tuple(group), **kw)


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("route", sorted(ROUTES))
def test_full_group_conformance(route, kind):
    """Every kind x route over the full 8-device group vs numpy."""
    topo_name, kw = ROUTES[route]
    topo = build_topo(topo_name)
    req = request(kind, range(N), **kw)
    check_collective(kind, topo, req, tuple(range(N)), n=N,
                     seed=hash((route, kind)) % 2**32)


@pytest.mark.parametrize("route", ["flat_ring", "hier_grid"])
def test_pipelined_all_reduce(route):
    """The chunk-pipelined RS->AG junction (per-chunk release floors)
    collapses to wave order at execution and stays numerically exact."""
    topo_name, kw = ROUTES[route]
    topo = build_topo(topo_name)
    req = request("all_reduce", range(N), pipelined=True, **kw)
    check_collective("all_reduce", topo, req, tuple(range(N)), n=N, seed=7)


@pytest.mark.parametrize("kind", KINDS)
def test_vs_lax_reference(kind):
    """PCCL vs the XLA built-in inside one traced program, on the
    hierarchical grid route: all_gather / psum_scatter / psum / all_to_all."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.comms import primitives
    from repro.launch.mesh import make_mesh

    topo = build_topo("grid23")
    req = request(kind, range(N), hierarchy="always")
    fn = getattr(primitives, f"pccl_{kind}")
    x = make_input(kind, tuple(range(N)), N, seed=11)
    mesh = make_mesh((N,), ("x",))

    def f(xl):
        v = xl[0]
        mine = fn(v, "x", topo, req)
        if kind == "all_gather":
            ref = lax.all_gather(v, "x")
        elif kind == "reduce_scatter":
            ref = lax.psum_scatter(v, "x", scatter_dimension=0, tiled=False)
        elif kind == "all_reduce":
            ref = lax.psum(v, "x")
        else:
            ref = lax.all_to_all(v[:, None], "x", split_axis=0,
                                 concat_axis=0)[:, 0]
        return mine[None], ref[None]

    run = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                out_specs=(P("x"), P("x"))))
    mine, ref = run(x)
    assert_conformant(kind, np.asarray(mine), np.asarray(ref),
                      f"{kind} vs lax built-in")


# strict-subset process groups: (topology, group). line8 groups force
# forwarding through out-of-group devices; the grid/multipod groups span
# both pods, so subset-group traffic rides the hierarchical machinery.
SUBSET_CASES = [
    ("line8", (0, 3, 7), {}),
    ("grid23", (0, 2, 5, 6), {}),
    ("mp222", (1, 2, 4, 7), {}),
]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("case", range(len(SUBSET_CASES)),
                         ids=[c[0] for c in SUBSET_CASES])
def test_subset_groups_leave_non_participants_untouched(case, kind):
    topo_name, group, kw = SUBSET_CASES[case]
    topo = build_topo(topo_name)
    req = request(kind, group, **kw)
    check_collective(kind, topo, req, group, n=N,
                     seed=hash((topo_name, group, kind)) % 2**32)


@pytest.mark.parametrize("kind", KINDS)
def test_repaired_plan_route(kind):
    """Degrade a pod-internal link, repair the captured PhasePlan, lower the
    repaired algorithm with ``lower_algorithm``, execute it via the
    ``program=`` override, and check numerics."""
    from repro.comms import lower_algorithm
    from repro.core import AlgorithmRegistry, DegradationEvent, PlanRepairer

    topo = build_topo("grid23")
    group = tuple(range(N))
    rp = PlanRepairer(topo, registry=AlgorithmRegistry(), pipeline=False)
    req = request(kind, group, hierarchy="always")
    rp.plan(req)
    boundary = {b.id for b in topo.boundary_links()}
    victim = next(l.id for l in topo.links if l.id not in boundary)
    res = rp.repair(req, DegradationEvent(failed_links=[victim]))
    res.algorithm.validate()
    prog_plan = lower_algorithm(res.algorithm,
                                key=("conformance-repair", kind, victim))
    check_collective(kind, None, req, group, n=N, seed=13,
                     program=prog_plan)


def test_planner_program_roundtrip():
    """A MeshCollectivePlanner/PlanService-served program executes through
    the primitives' program= override — the serving path train_lm uses."""
    from repro.core import CollectiveRequest
    from repro.core.planservice import PlanService

    topo = build_topo("grid23")
    svc = PlanService()
    try:
        prog_plan = svc.program(
            topo, {"x": N},
            CollectiveRequest("all_reduce", hierarchy="always"), "x")
        req = request("all_reduce", range(N), hierarchy="always")
        check_collective("all_reduce", topo, req, tuple(range(N)), n=N,
                         seed=17, program=prog_plan)
    finally:
        svc.close()


@pytest.mark.slow
def test_train_lm_step_matches_xla_baseline():
    """One data-parallel train_lm step with PCCL-executed gradient
    all-reduce matches the lax.pmean baseline (loss and updated params)."""
    import re
    import subprocess
    import sys
    from pathlib import Path

    root = Path(__file__).resolve().parent.parent
    env_cmd = [sys.executable, str(root / "examples" / "train_lm.py"),
               "--model", "tiny", "--steps", "2", "--batch", "8",
               "--seq", "32", "--dp", "8", "--host-devices", "8",
               "--compare-collectives", "--seed", "0"]
    import os

    env = dict(os.environ)
    env["PYTHONPATH"] = str(root / "src")
    env.pop("XLA_FLAGS", None)  # train_lm sets it from --host-devices
    out = subprocess.run(env_cmd, capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"train_lm failed:\n{out.stdout}\n{out.stderr}"
    m = re.search(r"PCCL_CONFORMANCE max_loss_diff=([0-9.e+-]+) "
                  r"max_param_diff=([0-9.e+-]+)", out.stdout)
    assert m, f"no conformance line in output:\n{out.stdout}"
    loss_diff, param_diff = float(m.group(1)), float(m.group(2))
    assert loss_diff < 1e-4, out.stdout
    assert param_diff < 1e-3, out.stdout
