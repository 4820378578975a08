"""Execute PCCL-synthesized schedules as shard_map ppermute programs.

This is the TPU adaptation of the paper's §4.8 (MSCCL translation): each
synthesis wave becomes `jax.lax.ppermute`s over the device mesh, one per
round of the lowered program, issued together while no round reads what
an earlier one of them writes. The synthesizer emits congestion-free
neighbor-link transfers, so the permutes are ICI-neighbor permutes when
mesh device i is the chip at the fabric position of NPU i
(``chip_smoke.py --chips 4`` places a 2x2 v5e host by chip coordinates
and checks every permute pair is one hop).

Buffers are functional: every device holds a [num_slots, *chunk_shape]
array, a 1-D chunk that fills whole TPU tiles as rows of 128 (`slot_rows`).
A static *buffer plan* assigns, per device, a slot to every chunk the device
ever holds (source, in-transit forwarder — possibly outside the process
group, which is how PG-awareness executes — or destination). Slot lookups
inside the traced program use per-device constant tables indexed by
`lax.axis_index`, so one SPMD program serves every device.
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.translate import PpermuteProgram
from repro.tracing import count, span


@dataclass
class RoundTables:
    perm: list[tuple[int, int]]
    send_slot: np.ndarray  # [num_devices] slot each device sends (0 if none)
    recv_slot: np.ndarray  # [num_devices] slot each device writes (trash if none)
    is_recv: np.ndarray  # [num_devices] bool
    is_reduce: np.ndarray  # [num_devices] bool (receive-reduce vs receive-copy)


@dataclass
class BufferPlan:
    num_devices: int
    num_slots: int  # data slots; slot num_slots is the trash slot
    slot_of: dict[tuple[int, int], int]  # (device, chunk) -> slot
    rounds: list[RoundTables] = field(default_factory=list)
    # lazily-built stacked [num_rounds, num_devices] device arrays, shared by
    # every trace of this plan (see round_tables)
    _tables: tuple | None = field(default=None, repr=False, compare=False)
    # lazily-built waves of round indices (see waves)
    _waves: list[list[int]] | None = field(default=None, repr=False,
                                           compare=False)

    @property
    def buffer_slots(self) -> int:
        return self.num_slots + 1  # + trash

    def round_tables(self) -> tuple[jax.Array, jax.Array, jax.Array]:
        """(send_slot, recv_slot, is_reduce) stacked over rounds. The numpy
        stacks are built once per plan; the jnp conversion happens per call
        — memoizing the device arrays would capture the enclosing trace's
        tracers when first materialized inside shard_map, and a cached plan
        is shared across traces (tests, retraces, threads)."""
        if self._tables is None:
            n = self.num_devices
            if self.rounds:
                send = np.stack([rt.send_slot for rt in self.rounds])
                recv = np.stack([rt.recv_slot for rt in self.rounds])
                red = np.stack([rt.is_reduce for rt in self.rounds])
            else:
                send = np.zeros((0, n), np.int32)
                recv = np.zeros((0, n), np.int32)
                red = np.zeros((0, n), bool)
            self._tables = (send, recv, red)
        send, recv, red = self._tables
        return jnp.asarray(send), jnp.asarray(recv), jnp.asarray(red)

    def waves(self) -> list[list[int]]:
        """The rounds split into waves, built once per plan at first
        trace (not in ``plan_buffers``, so planning pays nothing): see
        :func:`round_waves`."""
        if self._waves is None:
            self._waves = round_waves(self)
        return self._waves


def round_waves(plan: BufferPlan) -> list[list[int]]:
    """Split a plan's rounds into maximal runs of consecutive rounds, *waves*, in
    which no round sends a slot that an earlier round of its wave writes.

    Round ``b`` joins the current wave unless some device sends in ``b``
    (is a source of its ``perm``) from the slot that it receives into in
    an earlier round of the wave. A non-sender's ``send_slot`` of 0 is no
    read, and a non-receiver's write to the trash slot is no write. So
    every send of a wave can read the buffer as the wave found it, and the
    wave's permutes carry no data dependence on each other; a plan whose
    rounds each read the last one's receive gets waves of one round."""
    waves: list[list[int]] = []
    # (device, slot) pairs that the current wave's receives write
    written = np.zeros((plan.num_devices, plan.buffer_slots), dtype=bool)
    for b, rt in enumerate(plan.rounds):
        src = np.fromiter((s for s, _ in rt.perm), np.int64, len(rt.perm))
        if waves and not written[src, rt.send_slot[src]].any():
            waves[-1].append(b)
        else:
            waves.append([b])
            written[:] = False
        written[rt.is_recv, rt.recv_slot[rt.is_recv]] = True
    return waves


def plan_buffers(prog: PpermuteProgram) -> BufferPlan:
    """Assign per-device buffer slots and build per-round permute tables.

    Array-backed: slots live in a dense ``[num_devices, num_chunks]`` int32
    matrix (-1 = unassigned) and every round's tables are filled with numpy
    scatters over the round's send arrays, instead of per-send dict probes.
    Slot numbering is identical to the historical per-transfer scan: initial
    holders first (condition order), then receivers in round order — each
    device appears at most once as a destination per round, so the
    vectorized assignment order cannot collide. ``slot_of`` is materialized
    once at the end for the primitives' lookup API.
    """
    n = prog.num_devices
    chunks = sorted(prog.chunk_holders)
    cidx = {c: k for k, c in enumerate(chunks)}
    slot = np.full((n, len(chunks)), -1, dtype=np.int32)
    next_slot = np.zeros(n, dtype=np.int32)

    # initial holders (sources; every contributor for reduced chunks)
    for chunk, holders in prog.chunk_holders.items():
        k = cidx[chunk]
        for h in holders:
            if slot[h, k] < 0:
                slot[h, k] = next_slot[h]
                next_slot[h] += 1

    rounds: list[RoundTables] = []
    for sends in prog.rounds:
        send_slot = np.zeros(n, dtype=np.int32)
        recv_slot = np.zeros(n, dtype=np.int32)
        is_recv = np.zeros(n, dtype=bool)
        is_reduce = np.zeros(n, dtype=bool)
        if not sends:
            rounds.append(RoundTables([], send_slot, recv_slot, is_recv,
                                      is_reduce))
            continue
        m = len(sends)
        src = np.fromiter((s.src for s in sends), np.int64, m)
        dst = np.fromiter((s.dst for s in sends), np.int64, m)
        red = np.fromiter((s.reduce for s in sends), bool, m)
        try:
            ck = np.fromiter((cidx[s.chunk] for s in sends), np.int64, m)
        except KeyError:
            bad = next(s for s in sends if s.chunk not in cidx)
            raise AssertionError(
                f"send of chunk {bad.chunk} from device {bad.src} "
                f"before arrival"
            ) from None
        ssl = slot[src, ck]
        if (ssl < 0).any():
            bad = sends[int(np.argmax(ssl < 0))]
            raise AssertionError(
                f"send of chunk {bad.chunk} from device {bad.src} "
                f"before arrival"
            )
        need = slot[dst, ck] < 0
        # destinations are unique within a ppermute round, so the scattered
        # slot grants cannot collide
        slot[dst[need], ck[need]] = next_slot[dst[need]]
        next_slot[dst[need]] += 1
        send_slot[src] = ssl
        recv_slot[dst] = slot[dst, ck]
        is_recv[dst] = True
        is_reduce[dst] = red
        perm = list(zip(src.tolist(), dst.tolist()))
        rounds.append(RoundTables(perm, send_slot, recv_slot, is_recv,
                                  is_reduce))

    num_slots = int(next_slot.max()) if n else 0
    devs, ks = np.nonzero(slot >= 0)
    slot_of = {
        (int(d), chunks[k]): int(slot[d, k]) for d, k in zip(devs, ks)
    }
    plan = BufferPlan(n, num_slots, slot_of, rounds)
    # route non-receivers' ppermute zeros into the trash slot
    for rt in plan.rounds:
        rt.recv_slot = np.where(rt.is_recv, rt.recv_slot, num_slots).astype(np.int32)
    return plan


# ---------------------------------------------------------------------------
# Plan cache: fingerprint -> BufferPlan. Repeated identical collectives (same
# synthesized program, e.g. the all-reduce issued every training step, or the
# same registry-canonical collective re-requested after a retrace) skip
# plan_buffers entirely and share the plan's jitted round tables.
# ---------------------------------------------------------------------------

_PLAN_CACHE: OrderedDict[object, BufferPlan] = OrderedDict()
_PLAN_CACHE_MAX = 128
_PLAN_LOCK = threading.Lock()


def plan_buffers_cached(prog: PpermuteProgram, fingerprint: object) -> BufferPlan:
    """``plan_buffers`` behind a thread-safe LRU.

    The key pairs the caller's fingerprint (registry fingerprint plus device
    mapping is the natural choice) with the program's own structural digest,
    so two distinct programs whose callers happen to hand in the same
    fingerprint can never cross-serve one buffer plan — the digest disambiguates
    while the caller fingerprint keeps lookups stable across re-translations
    of the same schedule.
    """
    key = (fingerprint, prog.digest())
    with _PLAN_LOCK:
        plan = _PLAN_CACHE.get(key)
        if plan is not None:
            _PLAN_CACHE.move_to_end(key)
            count("plan_cache.hit")
            return plan
    # plan outside the lock: duplicated work under a race is cheaper than
    # serializing every cold plan behind one mutex
    with span("pccl.buffers"):
        plan = plan_buffers(prog)
    with _PLAN_LOCK:
        existing = _PLAN_CACHE.get(key)
        if existing is not None:
            _PLAN_CACHE.move_to_end(key)
            count("plan_cache.hit")
            return existing
        count("plan_cache.miss")
        _PLAN_CACHE[key] = plan
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
    return plan


def clear_plan_cache() -> None:
    with _PLAN_LOCK:
        _PLAN_CACHE.clear()


# TPU memory holds a 2-D array in tiles of (sublanes, 128) over its two minor
# dims, 32 bytes of sublanes per lane; a 1-D array sits in runs of
# sublanes * 128 elements, which are byte for byte one such tile of rows.
_LANES = 128
_SUBLANES = {4: 8, 2: 16, 1: 32}  # by itemsize


def slot_rows(chunk_shape: tuple[int, ...], dtype) -> tuple[int, ...]:
    """The shape a chunk of ``chunk_shape`` takes in a slot buffer.

    A 1-D chunk whose length fills whole tiles is stored as rows of 128
    lanes, so the buffer ``[slots, C // 128, 128]`` keeps each slot a
    contiguous run of whole tiles and the reshape from and to the chunk is
    a bitcast. Stored flat, the slot axis would be the second-minor dim:
    each slot a strided stripe through every tile, and the chunks relaid
    at placement. Every other chunk keeps its shape, since rows would need
    padding, which is copies. Counts ``slot_layout.rows`` or
    ``slot_layout.flat`` once per call; the primitives call it once per
    traced collective."""
    sublanes = _SUBLANES.get(jnp.dtype(dtype).itemsize)
    if (len(chunk_shape) == 1 and sublanes is not None and chunk_shape[0]
            and chunk_shape[0] % (_LANES * sublanes) == 0):
        count("slot_layout.rows")
        return (chunk_shape[0] // _LANES, _LANES)
    count("slot_layout.flat")
    return tuple(chunk_shape)


def execute_program(
    plan: BufferPlan,
    buf: jax.Array,
    axis_name,
) -> jax.Array:
    """Run inside shard_map. `buf`: [plan.buffer_slots, *chunk_shape] local
    buffer with source chunks pre-placed at their planned slots. Returns the
    final buffer; callers extract destination slots via `plan.slot_of`.

    The lowering split each synthesis wave into rounds that a ``ppermute``
    can carry (one send and one receive a device); the executor re-joins
    them. It runs the plan's waves (``BufferPlan.waves``): every send of a
    wave reads the buffer as the wave found it, the wave's permutes go out
    with no data dependence between them, so XLA keeps them in flight
    together on their links, and then the receives apply in round order.
    No round of a wave sends a slot that an earlier one writes, so the
    result is bit for bit that of the rounds run one after another.
    Counts ``executor.rounds`` and ``executor.waves`` by the plan's rounds
    and waves once per call; the primitives call it once per traced
    collective."""
    waves = plan.waves()
    count("executor.rounds", len(plan.rounds))
    count("executor.waves", len(waves))
    idx = lax.axis_index(axis_name)
    send_t, recv_t, reduce_t = plan.round_tables()
    for wave in waves:
        # named scopes tag each stage's ops for the profiler; they change
        # the ops' metadata only, not the compiled program
        with jax.named_scope("pccl.send"):  # the send table's reads too
            vals = [lax.dynamic_index_in_dim(buf, send_t[r, idx], axis=0,
                                             keepdims=False) for r in wave]
            if len(vals) > 1:
                # without the barrier XLA fuses a later round's send slice
                # into the update of an earlier receive, which chains the
                # wave's permutes again
                vals = lax.optimization_barrier(vals)
        with jax.named_scope("pccl.permute"):
            gots = [lax.ppermute(val, axis_name, plan.rounds[r].perm)
                    for r, val in zip(wave, vals)]
        for r, got in zip(wave, gots):
            with jax.named_scope("pccl.receive"):
                recv_slot = recv_t[r, idx]
                reduce_here = reduce_t[r, idx]
                old = lax.dynamic_index_in_dim(buf, recv_slot, axis=0,
                                               keepdims=False)
                new = jnp.where(reduce_here, old + got, got)
            with jax.named_scope("pccl.update"):
                buf = lax.dynamic_update_index_in_dim(buf, new, recv_slot,
                                                      axis=0)
    return buf


def gather_slots(
    plan: BufferPlan, buf: jax.Array, axis_name, chunks: list[int]
) -> jax.Array:
    """Extract `chunks` (in order) from the local buffer; per-device slot
    tables again via axis_index. Missing chunks map to the trash slot."""
    idx = lax.axis_index(axis_name)
    tables = []
    for chunk in chunks:
        t = np.full(plan.num_devices, plan.num_slots, dtype=np.int32)
        for dev in range(plan.num_devices):
            got = plan.slot_of.get((dev, chunk))
            if got is not None:
                t[dev] = got
        tables.append(jnp.asarray(t)[idx])
    slots = jnp.stack(tables)
    return jnp.take(buf, slots, axis=0)
