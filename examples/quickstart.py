"""Quickstart: synthesize a topology-aware, process-group-aware collective.

    PYTHONPATH=src python examples/quickstart.py

Builds a 4x4 mesh, synthesizes an All-Gather for a 3-NPU process group and
an All-to-All for the whole mesh through the :class:`CollectiveRequest`
API, validates both, compares against the Direct baseline, prints the
ppermute translation, *executes* the process-group All-Gather on a real
16-device jax mesh, and finishes with a fault drill: a link dies and the
plan is repaired incrementally instead of re-synthesized from scratch.
"""

import os

# the execution demo wants one (host CPU) jax device per NPU of the 4x4
# mesh; must be set before jax initializes its backend
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=16")

from repro.core import (  # noqa: E402
    AlgorithmRegistry,
    CollectiveRequest,
    DegradationEvent,
    PlanRepairer,
    SynthesisEngine,
    direct_all_to_all,
    to_msccl_json,
    to_ppermute_program,
)
from repro.topology import mesh2d, multi_pod  # noqa: E402


def main():
    topo = mesh2d(4, 4)
    eng = SynthesisEngine(topo)
    print(f"topology: {topo}")

    # --- process-group All-Gather: corners only ---
    # one request object carries the whole collective spec (kind, group,
    # payload, chunking, routing) — the same value keys the plan registry
    req = CollectiveRequest("all_gather", group=(0, 3, 12))
    alg = eng.collective(req)
    alg.validate()
    used = {t.src for t in alg.transfers} | {t.dst for t in alg.transfers}
    print(f"\nAll-Gather over process group {list(req.group)}:")
    print(f"  makespan={alg.makespan} steps, transfers={alg.num_transfers}")
    print(f"  NPUs touched: {sorted(used)} (out-of-group forwarding: "
          f"{sorted(used - set(req.group))})")
    for t in alg.transfers[:6]:
        print(f"    t={t.start:>4}: chunk {t.chunk} {t.src} -> {t.dst}")

    # --- whole-mesh All-to-All vs Direct ---
    full = tuple(range(16))
    a2a = eng.collective(CollectiveRequest("all_to_all", group=full))
    a2a.validate()
    direct = direct_all_to_all(topo, list(full))
    print("\nAll-to-All over all 16 NPUs:")
    print(f"  PCCL makespan   = {a2a.makespan}")
    print(f"  Direct makespan = {direct.makespan}")
    print(f"  speedup         = {direct.makespan / a2a.makespan:.2f}x")

    # --- translations ---
    prog = to_ppermute_program(a2a)
    print(f"\nppermute program: {prog.num_rounds} rounds "
          f"({sum(len(r) for r in prog.rounds)} sends)")
    print("first round:", [(s.src, s.dst) for s in prog.rounds[0]][:8], "...")
    ir = to_msccl_json(alg)
    print(f"\nMSCCL-IR export: {len(ir)} bytes of JSON (alg 'pccl_all_gather')")

    # --- execute the process-group All-Gather on a real jax mesh ---
    # the same request lowers to shard_map ppermute rounds; out-of-group
    # NPUs forward chunks in transit but return zeros
    import numpy as np
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.comms import pccl_all_gather
    from repro.launch.mesh import make_mesh

    n = len(topo.npus)
    if jax.device_count() >= n:
        jmesh = make_mesh((n,), ("x",))
        x = (np.arange(n, dtype=np.float32) + 1.0)[:, None]  # NPU d holds d+1

        def run_ag(xl):
            return pccl_all_gather(xl[0], "x", topo, req)[None]

        step = jax.jit(jax.shard_map(run_ag, mesh=jmesh,
                                     in_specs=P("x"), out_specs=P("x")))
        out = np.asarray(step(x))  # [n, group_size, 1]
        m = req.group[0]
        print(f"\nexecuted on {n} jax devices: NPU {m} gathered "
              f"{out[m, :, 0].tolist()} (group {list(req.group)}), "
              f"non-member NPU 1 got {out[1, :, 0].tolist()}")
    else:
        print(f"\n(skipping mesh execution: {jax.device_count()} jax "
              f"devices < {n})")

    # --- degraded-fabric repair ---
    # plan a pod-spanning All-Gather with phase capture, kill one
    # pod-internal link, and patch only the damaged pod's phases; the
    # undamaged pods' schedules survive verbatim
    pods = multi_pod(4, 4, 4, unit_links=True)
    rp = PlanRepairer(pods, registry=AlgorithmRegistry(), pipeline=False)
    preq = CollectiveRequest("all_gather", group=tuple(pods.npus))
    rp.plan(preq)
    victim = next(
        l.id for l in pods.links
        if l.id not in {b.id for b in pods.boundary_links()})
    res = rp.repair(preq, DegradationEvent(failed_links=[victim]))
    res.algorithm.validate()
    print(f"\nlink {victim} died on {pods.name}: strategy={res.strategy}, "
          f"{res.phases_kept} phases kept verbatim, "
          f"{res.phases_resynthesized} re-synthesized")


if __name__ == "__main__":
    main()
