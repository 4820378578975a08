"""Multi-device numerical selftest of the PCCL ppermute executor.

Run as a subprocess (it forces 8 host-CPU devices, which must happen before
jax initializes): ``python -m repro.comms.selftest``. Exit code 0 = all
collectives bit-match their jax.lax references. The CPU platform is forced
too: on a TPU host the process would otherwise take the chips and find too
few devices for its 8-device mesh.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.comms.primitives import (  # noqa: E402
    pccl_all_gather,
    pccl_all_reduce,
    pccl_all_to_all,
    pccl_reduce_scatter,
)
from repro.core.request import CollectiveRequest  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.topology import line, ring, torus2d  # noqa: E402


def _mesh1d(n=8):
    return make_mesh((n,), ("x",))


def check(name, got, want, atol=1e-6):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol,
                               err_msg=name)
    print(f"  ok: {name}")


def test_all_gather_ring():
    mesh = _mesh1d()
    topo = ring(8, bidirectional=True)
    spec = CollectiveRequest("all_gather", tuple(range(8)))
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    @jax.jit
    def run(x):
        def f(xl):
            return pccl_all_gather(xl[0], "x", topo, spec)

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=P("x"))(x)

    got = run(x)  # [8 devices, 8 chunks, 4] -> every device row == full x
    want = jnp.broadcast_to(x, (8, 8, 4)).reshape(8 * 8, 4)
    check("all_gather ring8", got.reshape(-1, 4), want)


def test_all_gather_subgroup_with_forwarding():
    """Process group {0, 3, 7} on a line: chunks MUST forward through
    out-of-group devices 1, 2, 4, 5, 6 — the paper's §4.3 scenario."""
    mesh = _mesh1d()
    topo = line(8)
    group = (0, 3, 7)
    spec = CollectiveRequest("all_gather", group)
    x = jnp.arange(8 * 2, dtype=jnp.float32).reshape(8, 2)

    @jax.jit
    def run(x):
        def f(xl):
            return pccl_all_gather(xl[0], "x", topo, spec)

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=P("x"))(x)

    got = np.asarray(run(x)).reshape(8, 3, 2)
    want = np.asarray(x)[list(group)]
    for dev in group:
        check(f"subgroup AG at dev {dev}", got[dev], want)


def test_all_reduce():
    mesh = _mesh1d()
    topo = ring(8, bidirectional=True)
    spec = CollectiveRequest("all_reduce", tuple(range(8)), pipelined=True)
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8) * 0.25

    @jax.jit
    def run(x):
        def f(xl):
            mine = pccl_all_reduce(xl[0], "x", topo, spec)
            ref = lax.psum(xl[0], "x")
            return mine[None], ref[None]

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=(P("x"), P("x")))(x)

    mine, ref = run(x)
    check("all_reduce ring8 vs psum", mine, ref)


def test_reduce_scatter():
    mesh = _mesh1d()
    topo = ring(8, bidirectional=True)
    spec = CollectiveRequest("reduce_scatter", tuple(range(8)))
    x = jnp.arange(8 * 8 * 3, dtype=jnp.float32).reshape(8, 8, 3)

    @jax.jit
    def run(x):
        def f(xl):
            mine = pccl_reduce_scatter(xl[0], "x", topo, spec)
            ref = lax.psum_scatter(xl[0], "x", scatter_dimension=0, tiled=False)
            return mine[None], ref[None]

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=(P("x"), P("x")))(x)

    mine, ref = run(x)
    check("reduce_scatter ring8 vs psum_scatter", mine, ref)


def test_all_to_all_torus_rows():
    """A2A over the full 8-device group on a 2x4 torus."""
    mesh = _mesh1d()
    topo = torus2d(2, 4)
    spec = CollectiveRequest("all_to_all", tuple(range(8)))
    x = jnp.arange(8 * 8 * 2, dtype=jnp.float32).reshape(8, 8, 2)

    @jax.jit
    def run(x):
        def f(xl):
            mine = pccl_all_to_all(xl[0], "x", topo, spec)
            ref = lax.all_to_all(xl[0][:, None], "x", split_axis=0,
                                 concat_axis=0)[:, 0]
            return mine[None], ref[None]

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=(P("x"), P("x")))(x)

    mine, ref = run(x)
    check("all_to_all torus2x4 vs lax.all_to_all", mine, ref)


def test_all_to_all_subgroup():
    """A2A among process group {0,2,5} of a line-8: PG-aware forwarding."""
    mesh = _mesh1d()
    topo = line(8)
    group = (0, 2, 5)
    spec = CollectiveRequest("all_to_all", group)
    x = jnp.arange(8 * 3 * 2, dtype=jnp.float32).reshape(8, 3, 2)

    @jax.jit
    def run(x):
        def f(xl):
            return pccl_all_to_all(xl[0], "x", topo, spec)[None]

        return jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                             out_specs=P("x"))(x)

    got = np.asarray(run(x))
    xs = np.asarray(x)
    for i, dev in enumerate(group):
        want = np.stack([xs[src, i] for src in group])
        want[i] = xs[dev, i]
        check(f"subgroup A2A at dev {dev}", got[dev], want)


def test_two_axis_flattened():
    """Executor over a flattened ('r','c') mesh — the full-pod execution mode."""
    mesh = make_mesh((2, 4), ("r", "c"))
    topo = torus2d(2, 4)
    spec = CollectiveRequest("all_gather", tuple(range(8)))
    x = jnp.arange(8 * 2, dtype=jnp.float32).reshape(8, 2)

    @jax.jit
    def run(x):
        def f(xl):
            return pccl_all_gather(xl[0], ("r", "c"), topo, spec)[None]

        return jax.shard_map(f, mesh=mesh, in_specs=P(("r", "c")),
                             out_specs=P(("r", "c")))(x)

    got = np.asarray(run(x)).reshape(8, 8, 2)
    for dev in range(8):
        check(f"flattened-axes AG dev {dev}", got[dev], np.asarray(x))


def main():
    tests = [
        test_all_gather_ring,
        test_all_gather_subgroup_with_forwarding,
        test_all_reduce,
        test_reduce_scatter,
        test_all_to_all_torus_rows,
        test_all_to_all_subgroup,
        test_two_axis_flattened,
    ]
    for t in tests:
        print(f"[selftest] {t.__name__}")
        t()
    print("[selftest] ALL PASS")


if __name__ == "__main__":
    main()
