"""fig_exec_*: execute synthesized plans on a real (forced-host) jax mesh.

Per case: synthesis + translation happen in-process (deterministic
``rounds``/``sends`` counts — gated), then one subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` executes every case
as a shard_map ppermute program, checks numerics against the pure-numpy
reference (``valid`` — gated), and times the jitted collective against the
XLA built-in (``wall_ms``/``lax_ms`` — wall clock, report-only; host-CPU
"bandwidth" says nothing about ICI, the value of the row is that executed
plans are *measured at all* plus proven conformant in the bench gate).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import Row, timed

# tag -> (fabric, kind, request kwargs). ar_hier8 rides the chunk-pipelined
# hierarchical route; a2a_mp8 crosses the multi_pod DCI switch, so it only
# executes through the translator's switch unrolling.
CASES = [
    ("ag_ring8", "ring8", "all_gather", {"hierarchy": "never"}),
    ("rs_ring8", "ring8", "reduce_scatter", {"hierarchy": "never"}),
    ("ar_hier8", "grid23", "all_reduce",
     {"hierarchy": "always", "pipelined": True}),
    ("a2a_mp8", "mp222", "all_to_all", {"hierarchy": "always"}),
]

N = 8
PAYLOAD = 4096  # per-shard f32 elements


def _topo(name: str):
    from repro.topology import ring
    from repro.topology.generators import grid_hypercube, multi_pod

    return {
        "ring8": lambda: ring(8, bidirectional=True),
        "grid23": lambda: grid_hypercube(2, 3),
        "mp222": lambda: multi_pod(2, 2, 2, unit_links=True,
                                   dci_ports_per_pod=2),
    }[name]()


def _request(kind: str, kw: dict):
    from repro.core import CollectiveRequest

    return CollectiveRequest(kind, group=tuple(range(N)), **kw)


def _exec_worker() -> None:
    """Subprocess body: run every case on the forced host mesh, print one
    JSON dict tag -> {wall_ms, lax_ms, valid}."""
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N}").strip()
    # a CPU placeholder mesh: on a TPU host it must not take the chips
    os.environ["JAX_PLATFORMS"] = "cpu"
    import time

    import numpy as np

    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.comms import primitives
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((N,), ("x",))
    out: dict[str, dict] = {}
    for tag, fabric, kind, kw in CASES:
        topo = _topo(fabric)
        req = _request(kind, kw)
        fn = getattr(primitives, f"pccl_{kind}")
        rng = np.random.default_rng(42)
        if kind == "all_gather":
            x = rng.standard_normal((N, PAYLOAD)).astype(np.float32)
        elif kind == "all_reduce":
            x = rng.standard_normal((N, N * PAYLOAD)).astype(np.float32)
        else:
            x = rng.standard_normal((N, N, PAYLOAD)).astype(np.float32)

        def f(xl, _fn=fn, _topo=topo, _req=req):
            return _fn(xl[0], "x", _topo, _req)[None]

        def g(xl, _kind=kind):
            v = xl[0]
            if _kind == "all_gather":
                r = lax.all_gather(v, "x")
            elif _kind == "reduce_scatter":
                r = lax.psum_scatter(v, "x", scatter_dimension=0, tiled=False)
            elif _kind == "all_reduce":
                r = lax.psum(v, "x")
            else:
                r = lax.all_to_all(v[:, None], "x", split_axis=0,
                                   concat_axis=0)[:, 0]
            return r[None]

        mine = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                     out_specs=P("x")))
        ref = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P("x"),
                                    out_specs=P("x")))
        got = np.asarray(mine(x))
        want = np.asarray(ref(x))
        if kind in ("reduce_scatter", "all_reduce"):
            valid = int(np.allclose(got, want, rtol=1e-5, atol=1e-5))
        else:
            valid = int(np.array_equal(got, want))

        def _time(fjit, iters=5):
            fjit(x).block_until_ready()  # warmup/compile
            t0 = time.perf_counter()
            for _ in range(iters):
                fjit(x).block_until_ready()
            return (time.perf_counter() - t0) / iters * 1e3

        out[tag] = {"wall_ms": round(_time(mine), 3),
                    "lax_ms": round(_time(ref), 3),
                    "valid": valid}
    print(json.dumps(out))


def run(full: bool = False):
    from repro.core import SynthesisEngine
    from repro.core.translate import to_ppermute_program

    # deterministic lowering stats, in-process
    stats = {}
    for tag, fabric, kind, kw in CASES:
        topo = _topo(fabric)
        req = _request(kind, kw)
        alg, synth_us = timed(lambda t=topo, r=req:
                              SynthesisEngine(t).collective(r))
        prog = to_ppermute_program(alg)
        stats[tag] = (synth_us, prog.num_rounds, prog.num_sends)

    # execution wall clock + conformance, one forced-host-mesh subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.exec_mesh", "--exec-worker"],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    execd: dict[str, dict] = {}
    if proc.returncode == 0:
        try:
            execd = json.loads(proc.stdout.strip().splitlines()[-1])
        except (ValueError, IndexError):
            execd = {}
    else:
        sys.stderr.write(proc.stderr)

    for tag, fabric, kind, kw in CASES:
        synth_us, rounds, sends = stats[tag]
        e = execd.get(tag, {"wall_ms": 0.0, "lax_ms": 0.0, "valid": 0})
        yield Row(
            f"fig_exec_{tag}", synth_us,
            f"npus={N};rounds={rounds};sends={sends};"
            f"wall_ms={e['wall_ms']};lax_ms={e['lax_ms']};"
            f"valid={e['valid']}")


if __name__ == "__main__":
    if "--exec-worker" in sys.argv:
        _exec_worker()
    else:
        for row in run():
            print(row.csv())
