"""Compile the main path for TPU v5e without a chip attached.

The TPU compiler is installed wherever libtpu is, and compiles for a
*described* v5e:2x2 topology: each test lowers a program at real widths for
the described devices and checks what the compiler put in. Nothing runs, so
these say nothing about results or times; they catch what interpret mode and
the CPU backend cannot (tiling, VMEM limits, partitioning).

The topology is described inside a module-scoped fixture, never at import:
only one process may hold libtpu, and pytest-xdist workers all import every
test file. Keep these tests in this one file so one worker holds it.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.comms import primitives
from repro.core.registry import AlgorithmRegistry
from repro.core.request import CollectiveRequest
from repro.kernels import flash_attention as fa
from repro.kernels import ssd_scan as ssd
from repro.launch.sharding import MeshCollectivePlanner
from repro.topology import mesh2d

KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
SHARD = 1 << 20  # f32 elements per device


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache would store these compiles but could never read
    # them back without a chip, and warn on every later compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    """The four chips as one axis in mesh2d(2, 2) NPU order: NPU r*2 + c is
    the chip at coordinates (x=c, y=r)."""
    at = {tuple(d.coords[:2]): d for d in topo.devices}
    return Mesh(np.array([at[(n % 2, n // 2)] for n in range(4)]), ("x",))


@pytest.mark.parametrize("kind", KINDS)
def test_pccl_collective_compiles_to_permutes(kind, mesh):
    planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                    registry=AlgorithmRegistry())
    program = planner.program(kind, "x", 0, nbytes=SHARD * 4)
    spec = CollectiveRequest(kind, group=(0, 1, 2, 3))
    fn = getattr(primitives, f"pccl_{kind}")
    shape = (4, SHARD) if kind in ("all_gather", "all_reduce") else (4, 4, SHARD // 4)

    def run(xl):
        return fn(xl[0], "x", None, spec, program=program)[None]

    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    compiled = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x"),
                                     out_specs=P("x"))).lower(x).compile()
    hlo = compiled.as_text()
    assert "collective-permute" in hlo
    for builtin in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all"):
        assert f" {builtin}(" not in hlo and f" {builtin}-start(" not in hlo


def test_all_reduce_slot_buffer_is_whole_tiles(mesh):
    """One 25 MiB f32 bucket, the DDP cell's, each shard split in two
    halves: the slot buffer holds each chunk as rows of 128 lanes, no array
    stripes the chunk across sublanes, and placement needs no relayout
    loop."""
    bucket = 6553600
    planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                    registry=AlgorithmRegistry())
    program = planner.program("all_reduce", "x", 0, nbytes=bucket / 4 / 2**18)
    spec = CollectiveRequest("all_reduce", group=(0, 1, 2, 3))
    assert len(program[0].chunk_holders) == 8
    chunk = bucket // 8

    def run(xl):
        return primitives.pccl_all_reduce(xl, "x", None, spec,
                                          program=program)

    # each chip's bucket is 1-D, laid out in 1024-element runs, as DDP's is
    x = jax.ShapeDtypeStruct((4 * bucket,), jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    hlo = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x"))).lower(x).compile().as_text()
    slots = program[1].buffer_slots
    assert f"f32[{slots},{chunk // 128},128]" in hlo
    # a 1-D f32[chunk] (the permute's operand) is whole 1024-element runs
    for dims in re.findall(r"f32\[(\d+(?:,\d+)+)\]", hlo):
        *_, second, minor = (int(d) for d in dims.split(","))
        assert not (minor == chunk and second < 8), f"f32[{dims}]"
    assert " while(" not in hlo


@pytest.mark.parametrize("kind, shape", [("all_reduce", (32, 6144)),
                                         ("all_gather", (32, 23168))])
def test_tp_decode_slot_buffer_is_whole_tiles(kind, shape, mesh):
    """Tensor-parallel decode's bf16 payloads, each chip's [32, 6144]
    activations and [32, 23168] logits shard: every bf16 array's rows
    fill its tiles (an all-reduce chunk is [8, 6144]), the slot buffer is
    [slots, *chunk], and placement needs no relayout loop."""
    planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                    registry=AlgorithmRegistry())
    nbytes = shape[0] * shape[1] * 2
    program = planner.program(kind, "x", 0, nbytes=nbytes / 2**20 / (
        4 if kind == "all_reduce" else 1))
    spec = CollectiveRequest(kind, group=(0, 1, 2, 3))
    fn = getattr(primitives, f"pccl_{kind}")
    chunk = (shape[0] // 4, shape[1]) if kind == "all_reduce" else shape

    def run(xl):
        return fn(xl[0], "x", None, spec, program=program)[None]

    x = jax.ShapeDtypeStruct((4, *shape), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("x")))
    hlo = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x"))).lower(x).compile().as_text()
    slots = program[1].buffer_slots
    assert f"bf16[{slots},{chunk[0]},{chunk[1]}]" in hlo
    tiled = re.findall(r"bf16\[(\d+(?:,\d+)+)\]\{[\d,]+:T\((\d+),128\)", hlo)
    assert tiled
    for dims, rows in tiled:
        *_, second, minor = (int(d) for d in dims.split(","))
        assert second % int(rows) == 0 and minor % 128 == 0, f"bf16[{dims}]"
    assert " while(" not in hlo


@pytest.mark.parametrize("shape, dtype, permutes", [
    ((6553600,), jnp.float32, 12), ((32, 6144), jnp.bfloat16, 8)],
    ids=["ddp-bucket-f32", "tp-decode-bf16"])
def test_all_reduce_keeps_two_permutes_in_flight(shape, dtype, permutes,
                                                 mesh):
    """The DDP cell's 25 MiB f32 bucket and the TP decode cell's [32, 6144]
    bf16 activations: the 2x2 all-reduce's permutes run in waves of 2 (12
    in 6 for the bucket, its shards split in two; 8 in 4 for the decode
    activations), so at the start of every wave's second permute the
    first is not yet done."""
    planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                    registry=AlgorithmRegistry())
    itemsize = jnp.dtype(dtype).itemsize
    program = planner.program("all_reduce", "x", 0, nbytes=int(
        np.prod(shape)) * itemsize / 4 / 2**20)
    spec = CollectiveRequest("all_reduce", group=(0, 1, 2, 3))

    def run(xl):
        return primitives.pccl_all_reduce(xl[0], "x", None, spec,
                                          program=program)[None]

    x = jax.ShapeDtypeStruct((4, *shape), dtype,
                             sharding=NamedSharding(mesh, P("x")))
    hlo = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x"))).lower(x).compile().as_text()
    entry = hlo[hlo.index("\nENTRY"):]  # a scheduled module lists in order
    events = re.findall(r"%collective-permute-(start|done)(?:\.\d+)? = ",
                        entry)
    assert events.count("start") == events.count("done") == permutes
    in_flight = overlapped = 0
    for event in events:
        if event == "start":
            overlapped += in_flight > 0
            in_flight += 1
        else:
            in_flight -= 1
    assert overlapped == permutes // 2


def test_flash_attention_compiles_natively(one_chip):
    # llama3.2-1b widths: 32 query heads, 8 kv heads, head_dim 64
    q = jax.ShapeDtypeStruct((1, 4096, 32, 64), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16, sharding=one_chip)
    compiled = fa.flash_attention.lower(
        q, kv, kv, causal=True, block_q=512, block_kv=512,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles_natively(one_chip):
    # mamba2-370m widths: d_inner 2048 = 32 heads of 64, state 128
    B, S, H, Pd, N = 1, 4096, 32, 64, 128

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = ssd.ssd_scan.lower(
        f32(B, S, H, Pd), f32(B, S, H), f32(H), f32(B, S, N), f32(B, S, N),
        chunk=128, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
