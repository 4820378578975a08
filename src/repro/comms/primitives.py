"""PCCL-backed collective primitives for JAX programs.

Drop-in collectives that run a PCCL-synthesized, topology-aware schedule via
ppermute instead of XLA's built-in all-gather/all-reduce/all-to-all. They are
meant to be called INSIDE shard_map over the axis (or flattened axes) whose
devices form the process group.

The schedule is synthesized once per (topology, group, collective, nbytes)
and cached; synthesis happens at trace time on the host, so the compiled
program embeds the static permute rounds.
"""

from __future__ import annotations

from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.comms.executor import (
    BufferPlan,
    execute_program,
    gather_slots,
    plan_buffers_cached,
    slot_rows,
)
from repro.core.engine import SynthesisEngine
from repro.core.registry import default_registry, topology_fingerprint
from repro.core.request import CollectiveRequest
from repro.core.translate import PpermuteProgram, to_ppermute_program
from repro.topology.topology import Topology
from repro.tracing import count, span


# ``chipbench/generators`` names the request type passed next to a
# ready ``program=`` by this older name; only ``.kind`` and ``.group``
# are read there
CollectiveSpec = CollectiveRequest

_EXEC_KINDS = ("all_gather", "all_to_all", "reduce_scatter", "all_reduce")


def _check_executable(req) -> None:
    if not isinstance(req, CollectiveRequest):
        raise TypeError(
            f"request must be a CollectiveRequest, got {type(req).__name__}")
    if req.kind not in _EXEC_KINDS:
        raise ValueError(
            f"collective kind {req.kind!r} is not executable "
            f"(expected one of {_EXEC_KINDS})")
    if not req.group:
        raise ValueError("executable collectives need an explicit group")


# translated programs, keyed by fingerprint (bounded LRU; BufferPlans are
# owned by the executor's plan cache, not pinned here)
_PROGRAM_CACHE: OrderedDict = OrderedDict()
_PROGRAM_CACHE_MAX = 128


def _engine_for(topo: Topology, registry) -> SynthesisEngine:
    """One engine per (topology, registry), attached to the topology object
    so distance caches persist across collectives, the whole bundle is
    garbage-collected with the topology (a topo<->engine cycle, not an
    immortal module-level dict), and graph mutation invalidates it."""
    engines = getattr(topo, "_pccl_engines", None)
    if engines is None:
        engines = topo._pccl_engines = OrderedDict()
    eng = engines.get(id(registry))
    if eng is None:
        # NB: id(registry) stays valid while the entry exists because the
        # engine references the registry strongly.
        eng = SynthesisEngine(topo, registry=registry)
        engines[id(registry)] = eng
        while len(engines) > 8:
            engines.popitem(last=False)
    return eng


def synthesize_program(
    topo: Topology,
    request: CollectiveRequest,
    *,
    device_of_npu: dict[int, int] | None = None,
    registry=None,
) -> tuple[PpermuteProgram, BufferPlan]:
    """Synthesis -> translation -> buffer planning, cached at every layer:
    the algorithm through the (shared) AlgorithmRegistry — so isomorphic
    process groups reuse one synthesized plan — the translated program here,
    and the BufferPlan through the executor's plan cache (the single owner
    of plans; every call goes through it, so its stats reflect real reuse).

    ``request`` is a :class:`~repro.core.request.CollectiveRequest` of an
    executable kind over an explicit group; it executes any engine route:
    ``hierarchy="always"``, TE gateway strategies, comm sketches, pipelined
    all-reduce."""
    with span("pccl.plan"):
        registry = registry if registry is not None else default_registry()
        _check_executable(request)
        dev_key = (None if device_of_npu is None
                   else tuple(sorted(device_of_npu.items())))
        key = (topology_fingerprint(topo), request.fingerprint(), dev_key)
        prog = _PROGRAM_CACHE.get(key)
        if prog is not None:
            _PROGRAM_CACHE.move_to_end(key)
            count("program_cache.hit")
        else:
            count("program_cache.miss")
            engine = _engine_for(topo, registry)
            alg = engine.collective(request)
            with span("pccl.validate"):
                alg.validate()
            with span("pccl.translate"):
                prog = to_ppermute_program(alg, device_of_npu)
            _PROGRAM_CACHE[key] = prog
            while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
                _PROGRAM_CACHE.popitem(last=False)
        return prog, plan_buffers_cached(prog, key)


def lower_algorithm(
    alg,
    *,
    key: object = "lowered",
    device_of_npu: dict[int, int] | None = None,
    validate: bool = False,
) -> tuple[PpermuteProgram, BufferPlan]:
    """Lower a pre-synthesized :class:`CollectiveAlgorithm` — e.g. a
    ``PlanRepairer`` repair result or a hand-stitched ``PhasePlan`` — to an
    executable (program, plan) pair that the ``pccl_*`` primitives accept
    via their ``program=`` argument. ``key`` namespaces the buffer-plan
    cache entry; the program's structural digest keeps distinct schedules
    apart even under one key."""
    with span("pccl.plan"):
        if validate:
            with span("pccl.validate"):
                alg.validate()
        with span("pccl.translate"):
            prog = to_ppermute_program(alg, device_of_npu)
        return prog, plan_buffers_cached(prog, key)


def _group_devices(prog: PpermuteProgram, spec,
                   device_of_npu: dict[int, int] | None) -> list[int]:
    if device_of_npu is None:
        return list(spec.group)
    return [device_of_npu[n] for n in spec.group]


def _member_mask(prog: PpermuteProgram, devices: list[int]) -> np.ndarray:
    mask = np.zeros(prog.num_devices, dtype=bool)
    mask[devices] = True
    return mask


def _resolve(topo, spec, device_of_npu, program, kind):
    """Shared head of the pccl_* primitives: check the kind, fetch or accept
    a (program, plan) pair, map the group onto mesh devices, and build the
    non-participant mask — devices outside the process group may forward
    traffic (that is PG-awareness executing) but must hand back exact
    zeros, never forwarded or partially-reduced payloads."""
    req_kind = spec.kind
    if req_kind != kind:
        raise ValueError(f"pccl_{kind} got a spec of kind {req_kind!r}")
    if program is not None:
        prog, plan = program
    else:
        prog, plan = synthesize_program(topo, spec, device_of_npu=device_of_npu)
    devices = _group_devices(prog, spec, device_of_npu)
    return prog, plan, devices, _member_mask(prog, devices)


def _chunks_by_src(prog: PpermuteProgram, devices: list[int]) -> dict[int, list[int]]:
    out: dict[int, list[int]] = {d: [] for d in devices}
    for chunk, src in sorted(prog.chunk_srcs.items()):
        if src in out:
            out[src].append(chunk)
    return out


# ---------------------------------------------------------------------------
# collectives (call inside shard_map)
# ---------------------------------------------------------------------------

def pccl_all_gather(
    x: jax.Array,
    axis_name,
    topo: Topology | None,
    spec,
    *,
    device_of_npu: dict[int, int] | None = None,
    program: tuple[PpermuteProgram, BufferPlan] | None = None,
    tiled: bool = False,
) -> jax.Array:
    """All-gather x (local shard, shape S) over the group -> [g, *S] stacked
    in group order (or concatenated on axis 0 when tiled=True). Devices
    outside the group return zeros."""
    prog, plan, devices, member = _resolve(
        topo, spec, device_of_npu, program, "all_gather")
    by_src = _chunks_by_src(prog, devices)
    # one chunk per group member
    my_chunk_slot = np.zeros(prog.num_devices, dtype=np.int32)
    for dev in devices:
        (chunk,) = by_src[dev]
        my_chunk_slot[dev] = plan.slot_of[(dev, chunk)]
    rows = slot_rows(x.shape, x.dtype)
    with jax.named_scope("pccl.place"):
        idx = lax.axis_index(axis_name)
        buf = jnp.zeros((plan.buffer_slots, *rows), x.dtype)
        buf = lax.dynamic_update_index_in_dim(
            buf, jnp.reshape(x, rows), jnp.asarray(my_chunk_slot)[idx], axis=0
        )
    buf = execute_program(plan, buf, axis_name)
    ordered_chunks = [by_src[d][0] for d in devices]
    with jax.named_scope("pccl.gather"):
        out = gather_slots(plan, buf, axis_name, ordered_chunks)
        # non-participants may have forwarded chunks sitting in their slots
        # — mask so their output is untouched-by-the-collective zeros
        out = jnp.where(jnp.asarray(member)[idx], out, jnp.zeros_like(out))
        out = jnp.reshape(out, (len(devices), *x.shape))
        return jnp.concatenate(list(out), axis=0) if tiled else out


def pccl_reduce_scatter(
    x: jax.Array,
    axis_name,
    topo: Topology | None,
    spec,
    *,
    device_of_npu: dict[int, int] | None = None,
    program: tuple[PpermuteProgram, BufferPlan] | None = None,
) -> jax.Array:
    """x: [g, *S] (addend g for each group member); returns this device's
    reduced shard [*S] (devices outside the group return zeros)."""
    prog, plan, devices, member = _resolve(
        topo, spec, device_of_npu, program, "reduce_scatter")
    # chunk k is owned by group member k (condition order = group order)
    chunks = sorted(prog.chunk_holders)  # ReduceCondition: dests are owners
    owner_of_chunk = {c: prog.chunk_dests[c][0] for c in chunks}
    # initial buffer: device d's contribution to chunk k sits at d's slot for k
    # — but the reversed-AG plan only allocates slots along reduction paths.
    # Every group member is a leaf (or interior) of every chunk's tree, so the
    # slot exists for group devices.
    init_slot = np.full((prog.num_devices, len(chunks)), plan.num_slots, np.int32)
    for ci, c in enumerate(chunks):
        for dev in devices:
            got = plan.slot_of.get((dev, c))
            if got is not None:
                init_slot[dev, ci] = got
    rows = slot_rows(x.shape[1:], x.dtype)
    with jax.named_scope("pccl.place"):
        xs = jnp.reshape(x, (x.shape[0], *rows))
        idx = lax.axis_index(axis_name)
        buf = jnp.zeros((plan.buffer_slots, *rows), x.dtype)
        for ci in range(len(chunks)):
            buf = lax.dynamic_update_index_in_dim(
                buf, xs[ci], jnp.asarray(init_slot[:, ci])[idx], axis=0
            )
    buf = execute_program(plan, buf, axis_name)
    # each group device extracts its own chunk
    my_chunk_table = np.zeros(prog.num_devices, dtype=np.int64)
    for ci, c in enumerate(chunks):
        my_chunk_table[owner_of_chunk[c]] = c
    out_slot = np.full(prog.num_devices, plan.num_slots, np.int32)
    for dev in devices:
        out_slot[dev] = plan.slot_of[(dev, int(my_chunk_table[dev]))]
    with jax.named_scope("pccl.gather"):
        out = lax.dynamic_index_in_dim(
            buf, jnp.asarray(out_slot)[idx], axis=0, keepdims=False
        )
        out = jnp.where(jnp.asarray(member)[idx], out, jnp.zeros_like(out))
        return jnp.reshape(out, x.shape[1:])


def pccl_all_reduce(
    x: jax.Array,
    axis_name,
    topo: Topology | None,
    spec,
    *,
    device_of_npu: dict[int, int] | None = None,
    program: tuple[PpermuteProgram, BufferPlan] | None = None,
) -> jax.Array:
    """All-reduce x (same shape everywhere) over the group. x is split along
    axis 0 (must divide) into the program's chunks, one or two per member;
    composition RS∘AG per §4.5. Devices outside the group return zeros."""
    prog, plan, devices, member = _resolve(
        topo, spec, device_of_npu, program, "all_reduce")
    # piece i of x is chunk i in id order: member by member in group order,
    # a split shard's halves next to each other (the engine's numbering)
    chunks = sorted(prog.chunk_holders)
    n = len(chunks)
    if x.shape[0] % n:
        raise ValueError(f"all_reduce of {x.shape[0]} rows does not split "
                         f"into the program's {n} chunks")
    init_slot = np.full((prog.num_devices, n), plan.num_slots, np.int32)
    for ci, c in enumerate(chunks):
        for dev in devices:
            got = plan.slot_of.get((dev, c))
            if got is not None:
                init_slot[dev, ci] = got
    rows = slot_rows((x.shape[0] // n, *x.shape[1:]), x.dtype)
    with jax.named_scope("pccl.place"):
        xs = jnp.reshape(x, (n, *rows))
        idx = lax.axis_index(axis_name)
        buf = jnp.zeros((plan.buffer_slots, *rows), x.dtype)
        for ci in range(n):
            buf = lax.dynamic_update_index_in_dim(
                buf, xs[ci], jnp.asarray(init_slot[:, ci])[idx], axis=0
            )
    buf = execute_program(plan, buf, axis_name)
    with jax.named_scope("pccl.gather"):
        out = gather_slots(plan, buf, axis_name, chunks)
        out = jnp.where(jnp.asarray(member)[idx], out, jnp.zeros_like(out))
        return jnp.reshape(out, x.shape)


def pccl_all_to_all(
    x: jax.Array,
    axis_name,
    topo: Topology | None,
    spec,
    *,
    device_of_npu: dict[int, int] | None = None,
    program: tuple[PpermuteProgram, BufferPlan] | None = None,
) -> jax.Array:
    """x: [g, *S] where row j is this device's payload for group member j.
    Returns [g, *S] where row i is the payload received from member i
    (row for self = own self-payload, which never leaves the device).
    Devices outside the group return zeros."""
    prog, plan, devices, member = _resolve(
        topo, spec, device_of_npu, program, "all_to_all")
    g = len(devices)
    rank_of_device = {d: r for r, d in enumerate(devices)}
    # chunk (i -> j): src devices[i], dest devices[j]; build per-device tables
    send_chunk_slot = np.full((prog.num_devices, g), plan.num_slots, np.int32)
    recv_chunk_slot = np.full((prog.num_devices, g), plan.num_slots, np.int32)
    self_row = np.zeros(prog.num_devices, dtype=np.int32)
    for chunk, src in prog.chunk_srcs.items():
        dst = prog.chunk_dests[chunk][0]
        i, j = rank_of_device[src], rank_of_device[dst]
        send_chunk_slot[src, j] = plan.slot_of[(src, chunk)]
        recv_chunk_slot[dst, i] = plan.slot_of[(dst, chunk)]
    for dev in devices:
        self_row[dev] = rank_of_device[dev]
    rows = slot_rows(x.shape[1:], x.dtype)
    with jax.named_scope("pccl.place"):
        xs = jnp.reshape(x, (g, *rows))
        idx = lax.axis_index(axis_name)
        buf = jnp.zeros((plan.buffer_slots, *rows), x.dtype)
        for j in range(g):
            buf = lax.dynamic_update_index_in_dim(
                buf, xs[j], jnp.asarray(send_chunk_slot[:, j])[idx], axis=0
            )
    buf = execute_program(plan, buf, axis_name)
    with jax.named_scope("pccl.gather"):
        received = []
        for i in range(g):
            received.append(
                lax.dynamic_index_in_dim(
                    buf, jnp.asarray(recv_chunk_slot[:, i])[idx], axis=0,
                    keepdims=False
                )
            )
        out = jnp.stack(received)
        # self row: take from input (never transferred)
        me = jnp.asarray(self_row)[idx]
        self_payload = lax.dynamic_index_in_dim(xs, me, axis=0, keepdims=False)
        out = lax.dynamic_update_index_in_dim(out, self_payload, me, axis=0)
        # the self-row write above lands row 0 <- x[0] on non-participants
        # (self_row defaults to 0); mask them back to zeros
        out = jnp.where(jnp.asarray(member)[idx], out, jnp.zeros_like(out))
        return jnp.reshape(out, x.shape)
