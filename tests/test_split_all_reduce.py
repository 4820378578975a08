"""The split all-reduce: each member's shard in two halves, half 0 reduced
and gathered around one strong orientation of the fabric and half 1 around
its reverse, so the two link-disjoint schedules keep every link busy.

On the 2x2 the whole-shard plan runs 4 waves of an S/4 chunk, two of them
on half the links; the split plan runs 6 waves of S/8 on all 8 directed
links, 0.75 S on the busiest link, which is the bound 2(n-1)/n * S / 2
for four chips of two out-links each."""

import json
import os
import subprocess
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from repro import tracing
from repro.comms import primitives
from repro.core import AlgorithmRegistry, CollectiveRequest
from repro.core.engine import SynthesisEngine
from repro.core.translate import to_ppermute_program
from repro.launch.sharding import MeshCollectivePlanner
from repro.topology import mesh2d

FULL = (0, 1, 2, 3)
DDP_MIB = (6.25, 1.2578125)  # the DDP cell's chunks: 25 and 5.03 MiB buckets
TP_MIB = 0.09375  # the TP decode cell's [32, 6144] bf16 activations


def _planner():
    return MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                 registry=AlgorithmRegistry())


def _split_request(group, mib):
    return CollectiveRequest("all_reduce", tuple(group), bytes=mib / 2,
                             chunks=2, pipelined=True)


def test_orientations_are_opposite_directed_cycles():
    topo = mesh2d(2, 2)
    first, second = topo.opposite_orientations(0)
    pairs = [{(topo.links[k].src, topo.links[k].dst) for k in v.links}
             for v in (first, second)]
    assert pairs[0] == {(b, a) for a, b in pairs[1]}
    assert len(pairs[0]) == len(pairs[1]) == 4
    assert set(first.links).isdisjoint(second.links)
    assert sorted(first.links + second.links) == list(range(8))
    # one out-link and one in-link a chip in each: a directed 4-cycle
    for p in pairs:
        assert sorted(a for a, _ in p) == sorted(b for _, b in p) == [0, 1, 2,
                                                                      3]


@pytest.mark.parametrize("mib", DDP_MIB)
def test_2x2_split_fills_every_wave(mib):
    prog, plan = _planner().program("all_reduce", "x", 0, nbytes=mib)
    assert len(prog.chunk_holders) == 8
    assert prog.num_rounds == 12
    waves = plan.waves()
    assert len(waves) == 6
    links = {(l.src, l.dst) for l in mesh2d(2, 2).links}
    assert len(links) == 8
    for wave in waves:
        used = [p for r in wave for p in plan.rounds[r].perm]
        assert sorted(used) == sorted(links), wave


@pytest.mark.parametrize("chunks, load", [(1, 1.0), (2, 0.75)])
def test_busiest_link_carries_three_quarters_of_a_shard(chunks, load):
    mib = 6.25
    req = CollectiveRequest("all_reduce", FULL, bytes=mib / chunks,
                            chunks=chunks, pipelined=True)
    alg = SynthesisEngine(mesh2d(2, 2)).collective(req)
    per_link = Counter()
    for link in alg.columns.link.tolist():
        per_link[link] += mib / chunks
    assert max(per_link.values()) == load * 4 * mib
    assert len(per_link) == 8


@pytest.mark.parametrize("rows, cols", [(2, 2), (2, 4), (4, 4)])
def test_split_plan_validates(rows, cols):
    """The two-orientation plan itself, and what a split request returns
    (on the 2x4 and the 4x4 the split plan is no shorter than the
    whole-shard one, which is then returned)."""
    topo = mesh2d(rows, cols)
    group = list(range(rows * cols))
    eng = SynthesisEngine(topo)
    views = topo.opposite_orientations(group[0])
    split = eng._all_reduce_families(group, list(views), 0.5, True)
    split.validate()
    assert sorted(c.chunk for c in split.conditions) == list(
        range(2 * len(group)))
    got = eng.collective(_split_request(group, 1.0))
    got.validate()
    whole = eng.collective(CollectiveRequest("all_reduce", tuple(group),
                                             bytes=1.0, pipelined=True))
    if split.makespan < whole.makespan:
        assert len(got.conditions) == 2 * len(group)
    else:
        assert to_ppermute_program(got).digest() == \
            to_ppermute_program(whole).digest()
    assert (rows, cols) != (2, 2) or len(got.conditions) == 8


def test_bridged_fabric_falls_back_to_whole_shards():
    topo = mesh2d(1, 4)  # a line: every link a bridge
    assert topo.opposite_orientations(0) is None
    eng = SynthesisEngine(topo)
    got = eng.collective(_split_request(range(4), 6.25))
    whole = eng.collective(CollectiveRequest("all_reduce", FULL, bytes=6.25,
                                             pipelined=True))
    assert len(got.conditions) == 4
    assert to_ppermute_program(got).digest() == \
        to_ppermute_program(whole).digest()


@pytest.mark.parametrize("kind, mib, digest", [
    ("all_reduce", DDP_MIB[0], "bd146ce0e9a9d5461c1adb1d731ca12c"),
    ("all_reduce", DDP_MIB[1], "bd146ce0e9a9d5461c1adb1d731ca12c"),
    ("all_reduce", TP_MIB, "bd146ce0e9a9d5461c1adb1d731ca12c"),
    ("all_gather", 1.4140625, "36adfeb61bb7adbe34f29b46bb131e82"),
], ids=["ddp-25mib", "ddp-5mib", "tp-all_reduce", "tp-all_gather"])
def test_whole_shard_programs_keep_their_digests(kind, mib, digest):
    """The one-chunk programs of the executed cells' requests are the ones
    they ran before the split existed."""
    req = CollectiveRequest(kind, FULL, bytes=mib,
                            pipelined=kind == "all_reduce")
    prog, _ = primitives.synthesize_program(mesh2d(2, 2), req,
                                            registry=AlgorithmRegistry())
    assert prog.digest() == digest
    if kind == "all_gather" or mib == TP_MIB:
        prog, _ = _planner().program(kind, "x", 0, nbytes=mib)
        assert prog.digest() == digest


@pytest.mark.parametrize("mib, split", [(6.25, True), (1.2578125, True),
                                        (0.09375, False)])
def test_planner_splits_by_chunk_size(mib, split):
    before = tracing.counters().get("all_reduce.split_shards", 0)
    prog, plan = _planner().program("all_reduce", "x", 0, nbytes=mib)
    counted = tracing.counters().get("all_reduce.split_shards", 0) - before
    assert len(prog.chunk_holders) == (8 if split else 4)
    assert counted == int(split)
    assert prog.num_rounds == (12 if split else 8)
    if split:
        want, _ = primitives.synthesize_program(
            mesh2d(2, 2), _split_request(FULL, mib),
            registry=AlgorithmRegistry())
        assert want.digest() == prog.digest()


@pytest.mark.parametrize("kind", ["all_gather", "reduce_scatter"])
def test_planner_leaves_other_kinds_whole(kind):
    before = tracing.counters().get("all_reduce.split_shards", 0)
    prog, _ = _planner().program(kind, "x", 0, nbytes=6.25)
    assert tracing.counters().get("all_reduce.split_shards", 0) == before
    want, _ = primitives.synthesize_program(
        mesh2d(2, 2), CollectiveRequest(kind, FULL, bytes=6.25),
        registry=AlgorithmRegistry())
    assert prog.digest() == want.digest()
    assert len(prog.chunk_holders) == 4


def test_all_reduce_splits_a_shard_in_at_most_two():
    with pytest.raises(ValueError, match="1 or 2"):
        CollectiveRequest("all_reduce", FULL, chunks=3)


# (dtype, group, per-chip length): S/8 = 1,288 rows of 128 (the DDP cell's
# 5.03 MiB bucket), 24 and 48 rows (whole tiles, no power of two), and
# pieces that fill no tile
CASES = [
    ("float32", FULL, 8 * 1288 * 128),
    ("float32", FULL, 8 * 24 * 128),
    ("float32", FULL, 8 * 100),
    ("bfloat16", FULL, 8 * 48 * 128),
    ("bfloat16", FULL, 8 * 100),
    ("float32", (0, 3), 8 * 24 * 128),
]
# the chip checks' limits on |out - sum| / sum(|x|): a float32 sum of 4
# terms, and 3 bfloat16 roundings of partial sums plus one of the output
LIMITS = {"float32": 1e-4, "bfloat16": 4 * 2.0 ** -8}

_SCRIPT = r"""
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro import tracing
from repro.comms import primitives
from repro.core import CollectiveRequest
from repro.launch.mesh import make_mesh
from repro.topology import mesh2d

topo = mesh2d(2, 2)
mesh = make_mesh((4,), ("x",), devices=jax.devices()[:4])
cases, out = json.loads(sys.argv[1]), sys.argv[2]
saved, counted = {}, []
for i, (dtype, group, length) in enumerate(cases):
    x = np.random.default_rng(i).standard_normal((4, length)).astype(
        jnp.dtype(dtype))
    req = CollectiveRequest("all_reduce", tuple(group), bytes=1.0, chunks=2,
                            pipelined=True)
    program = primitives.synthesize_program(topo, req)

    def f(xl):
        return primitives.pccl_all_reduce(xl[0], "x", topo, req,
                                          program=program)[None]

    before = tracing.counters()
    got = np.asarray(jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x))
    after = tracing.counters()
    counted.append({k: after.get(k, 0) - before.get(k, 0)
                    for k in ("executor.rounds", "executor.waves")})
    for name, a in (("x", x), ("got", got)):
        saved[f"{i}_{name}"] = a.view(f"u{a.dtype.itemsize}")
np.savez(out + "/arrays.npz", **saved)
json.dump(counted, open(out + "/counts.json", "w"))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Every case run once on 4 of 8 host CPU devices (a child process: the
    device count is fixed when JAX starts)."""
    root = Path(__file__).resolve().parents[1]
    out = tmp_path_factory.mktemp("split_all_reduce")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
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
        import jax.numpy as jnp

        i = CASES.index(c)
        dtype = jnp.dtype(c[0])
        x, got = (arrays[f"{i}_{n}"].view(dtype) for n in ("x", "got"))
        return x, got, counted[i]

    return case


def _case_id(case):
    dtype, group, length = case
    return f"{dtype}-g{len(group)}-{length}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_split_all_reduce_matches_float32_sum(case, runs):
    dtype, group, _ = case
    x, got, _ = runs(case)
    gl = list(group)
    xf = x.astype(np.float32)
    want = xf[gl].sum(0)
    scale = np.abs(xf[gl]).sum(0)
    for d in gl:
        err = np.abs(got[d].astype(np.float32) - want) / np.maximum(
            scale, np.finfo(np.float32).tiny)
        assert err.max() <= LIMITS[dtype], f"device {d}: {err.max()}"
    for d in sorted(set(FULL) - set(gl)):
        assert not got[d].view(f"u{got.dtype.itemsize}").any(), f"device {d}"


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_split_all_reduce_is_its_rounds_in_series(case, runs):
    """What the executor returns, its waves' permutes in flight together,
    is bit for bit the plan's rounds run one after another in numpy."""
    from _exec_harness import emulate_collective

    dtype, group, _ = case
    x, got, counted = runs(case)
    prog, plan = primitives.synthesize_program(
        mesh2d(2, 2), CollectiveRequest("all_reduce", tuple(group), bytes=1.0,
                                        chunks=2, pipelined=True))
    want = emulate_collective("all_reduce", prog, plan, group, x)
    np.testing.assert_array_equal(got.view(f"u{got.dtype.itemsize}"),
                                  want.view(f"u{want.dtype.itemsize}"))
    assert counted == {"executor.rounds": prog.num_rounds,
                       "executor.waves": len(plan.waves())}
    if group == FULL:
        assert (prog.num_rounds, len(plan.waves())) == (12, 6)
