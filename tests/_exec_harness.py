"""Shared helpers for executing PCCL collectives on a host-device mesh and
comparing against pure-numpy references.

Used by the mesh conformance suite (`test_exec_conformance.py`) and the
hypothesis property suite (`test_exec_property.py`). Everything jax-touching
is imported lazily so that merely collecting the test modules never
initializes a backend (the ``mesh`` marker's skip logic decides that).

Input/output conventions (leading axis = mesh device, ``n`` devices,
group of ``g`` members; ``S`` = payload shape):

====================  =====================  ==========================
kind                  stacked input          stacked output
====================  =====================  ==========================
all_gather            ``[n, *S]``            ``[n, g, *S]``
reduce_scatter        ``[n, g, *S]``         ``[n, *S]``
all_reduce            ``[n, D]`` (g | D)     ``[n, D]``
all_to_all            ``[n, g, *S]``         ``[n, g, *S]``
====================  =====================  ==========================

Non-participating devices must come back as exact zeros — their buffers are
untouched by the collective even when they forwarded traffic for the group.
"""

from __future__ import annotations

import numpy as np

KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
REDUCTION_KINDS = ("reduce_scatter", "all_reduce")


def make_input(kind: str, group, n: int, *, payload: int = 3,
               seed: int = 0, dtype=np.float32) -> np.ndarray:
    """Random stacked input of the right shape for ``kind``."""
    rng = np.random.default_rng(seed)
    g = len(group)
    if kind == "all_gather":
        shape = (n, payload)
    elif kind in ("reduce_scatter", "all_to_all"):
        shape = (n, g, payload)
    elif kind == "all_reduce":
        shape = (n, g * payload)
    else:
        raise ValueError(kind)
    return rng.standard_normal(shape).astype(dtype)


def reference(kind: str, group, x: np.ndarray) -> np.ndarray:
    """Pure-numpy reference with zeros on non-participants."""
    n = x.shape[0]
    gl = list(group)
    g = len(gl)
    if kind == "all_gather":
        out = np.zeros((n, g) + x.shape[1:], x.dtype)
        for d in gl:
            out[d] = x[gl]
    elif kind == "reduce_scatter":
        out = np.zeros((n,) + x.shape[2:], x.dtype)
        for i, d in enumerate(gl):
            out[d] = x[gl, i].sum(axis=0)
    elif kind == "all_reduce":
        out = np.zeros_like(x)
        total = x[gl].sum(axis=0)
        for d in gl:
            out[d] = total
    elif kind == "all_to_all":
        out = np.zeros((n, g) + x.shape[2:], x.dtype)
        for i, d in enumerate(gl):
            out[d] = x[gl, i]
    else:
        raise ValueError(kind)
    return out


def run_on_mesh(kind: str, topo, spec, x: np.ndarray, *, n: int = 8,
                program=None, device_of_npu=None) -> np.ndarray:
    """Execute one pccl collective under jit+shard_map on an ``n``-device
    1-D mesh and return the stacked per-device outputs as numpy."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.comms import primitives
    from repro.launch.mesh import make_mesh

    fn = getattr(primitives, f"pccl_{kind}")
    mesh = make_mesh((n,), ("x",))

    def f(xl):
        out = fn(xl[0], "x", topo, spec, program=program,
                 device_of_npu=device_of_npu)
        return out[None]

    run = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("x"),
                                  out_specs=P("x")))
    return np.asarray(run(x))


def assert_conformant(kind: str, got: np.ndarray, want: np.ndarray,
                      label: str = "") -> None:
    """Bit-identical for data movement; fixed-order tolerance for
    reductions (the schedule fixes the accumulation order, but it differs
    from the reference's sum order)."""
    if kind in REDUCTION_KINDS:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                   err_msg=label)
    else:
        np.testing.assert_array_equal(got, want, err_msg=label)


def check_collective(kind: str, topo, spec, group, *, n: int = 8,
                     seed: int = 0, program=None) -> None:
    """End-to-end: build input, execute on the mesh, compare member outputs
    against the numpy reference and non-member outputs against zeros."""
    x = make_input(kind, group, n, seed=seed)
    got = run_on_mesh(kind, topo, spec, x, n=n, program=program)
    want = reference(kind, group, x)
    members = set(group)
    for d in range(n):
        if d in members:
            assert_conformant(kind, got[d], want[d],
                              f"{kind} member device {d}")
        else:
            np.testing.assert_array_equal(
                got[d], np.zeros_like(got[d]),
                err_msg=f"{kind}: non-participant device {d} buffer touched")


def run_rounds(plan, buf: np.ndarray, waves=None) -> np.ndarray:
    """Numpy interpreter of a ``BufferPlan`` on every device's buffer at once,
    ``buf``: ``[num_devices, buffer_slots, ...]``. Without ``waves`` the
    rounds run one after another, as the plan states them. With ``waves``
    (lists of round indices), every send of a wave reads the buffer as the
    wave found it, and then the wave's receives apply in round order. A
    receive writes ``old + got`` where it reduces, else ``got``; a device
    that receives nothing gets zeros, into its trash slot."""
    buf = buf.copy()
    dev = np.arange(plan.num_devices)
    tail = (1,) * (buf.ndim - 2)
    for wave in waves if waves is not None else [[r] for r in
                                                 range(len(plan.rounds))]:
        sent = [buf[dev, plan.rounds[r].send_slot] for r in wave]
        for r, vals in zip(wave, sent):
            rt = plan.rounds[r]
            got = np.zeros_like(vals)
            for s, d in rt.perm:
                got[d] = vals[s]
            old = buf[dev, rt.recv_slot]
            reduce_here = rt.is_reduce.reshape(-1, *tail)
            buf[dev, rt.recv_slot] = np.where(reduce_here, old + got, got)
    return buf


def emulate_collective(kind: str, prog, plan, group, x: np.ndarray) -> np.ndarray:
    """What ``pccl_all_reduce`` or ``pccl_all_gather`` returns on every
    device, emulated in numpy in ``x``'s dtype with the rounds run one after
    another: each chunk placed at its planned slot as the primitive places
    it, ``run_rounds``, then the chunks read back (the trash slot where a
    device holds none) and non-members masked to zeros."""
    n, trash = prog.num_devices, plan.num_slots
    members = list(group)
    buf = np.zeros((n, plan.buffer_slots, x[0].size // (
        len(members) if kind == "all_reduce" else 1)), x.dtype)
    if kind == "all_reduce":
        chunks = sorted(prog.chunk_holders)
        parts = x.reshape(n, len(members), -1)
        for d in range(n):
            for ci, c in enumerate(chunks):
                slot = plan.slot_of.get((d, c), trash) if d in members else trash
                buf[d, slot] = parts[d, ci]
    elif kind == "all_gather":
        srcs = prog.chunk_srcs
        chunks = [next(c for c in sorted(srcs) if srcs[c] == m) for m in members]
        for d in range(n):
            slot = (plan.slot_of[(d, chunks[members.index(d)])]
                    if d in members else 0)
            buf[d, slot] = x[d].reshape(-1)
    else:
        raise ValueError(kind)
    buf = run_rounds(plan, buf)
    out = np.stack([[buf[d, plan.slot_of.get((d, c), trash)] for c in chunks]
                    for d in range(n)])
    out[[d for d in range(n) if d not in members]] = 0
    if kind == "all_reduce":
        return out.reshape(x.shape)
    return out.reshape(n, len(members), *x.shape[1:])
