"""Cold process-group planning, closed loop: one planner serves job after
job, each the start of a job that was given a slice of the pod.

A request is a slice shape from the traffic's ``classes`` (classes equally
likely, then shapes within a class), at an offset in the pod and a kind
from ``kinds``, drawn from the seed. The requests come in blocks that hold
every (class, kind) pair once, in a seeded order, so every seed plans the
same mix. A (shape, offset, kind) comes again only after every other
offset of its (shape, kind), thousands of requests on, so no cache of the
program serves a request twice within a window, and each request gets a fresh
``AlgorithmRegistry``, as at a job's start. The request is
``repro.comms.synthesize_program``; a planner's work is the host's alone.
Once the timed loop has ended, the receive tables of every plan it returned
are staged on the chip in blocks of one shape, which counts the receives:
the traced window's device work, outside every timed request.

The plain reference executes every returned (program, buffer plan) on
integer payloads with numpy, from the request's semantics, and counts the
outputs that differ from the collective's exact result.
"""

from __future__ import annotations

import time
from itertools import product

import numpy as np

from chipbench.harness import Window
from chipbench.units import request_mib

PLAN_SPAN = "chipbench.plan"
STAGE_SPAN = "chipbench.stage"
BLOCK_ROWS = 4096  # rounds of receive tables staged in one block


def members(rows: int, cols: int, r0: int, c0: int, pod: tuple) -> tuple:
    """NPU ids of a rows x cols slice at (r0, c0) of a pod of ``pod`` =
    (rows, cols) chips, row-major; offsets wrap around a torus."""
    return tuple(((r0 + i) % pod[0]) * pod[1] + (c0 + j) % pod[1]
                 for i in range(rows) for j in range(cols))


def requests(config: dict, traffic: dict, seed: int):
    """(warm-up requests, window requests): a list and an endless iterator
    of (kind, shape, group). Warm-up plans each (shape, kind) once at an
    offset the window reaches last. A (shape, kind) takes every offset once
    before any again, so on the 16x16 pod nothing repeats within 3,060
    requests."""
    pod = tuple(config["fabric"]["args"][:2])
    wrap = config.get("wraparound", False)
    rng = np.random.default_rng(seed)
    classes = [[tuple(s) for s in cls] for cls in traffic["classes"]]
    kinds = traffic["kinds"]

    def shuffled(shape):
        offs = [(r, c) for r in range(pod[0] if wrap else pod[0] - shape[0] + 1)
                for c in range(pod[1] if wrap else pod[1] - shape[1] + 1)]
        return [offs[i] for i in rng.permutation(len(offs))]

    shapes = sorted({s for cls in classes for s in cls})
    pools = {(shape, kind): shuffled(shape) for shape in shapes for kind in kinds}
    warm = [(kind, shape, members(*shape, *pools[(shape, kind)].pop(), pod))
            for (shape, kind) in sorted(pools)]
    pairs = list(product(range(len(classes)), kinds))

    def window():
        while True:  # blocks holding every (class, kind) once
            for i in rng.permutation(len(pairs)):
                ci, kind = pairs[i]
                shape = classes[ci][rng.integers(len(classes[ci]))]
                pool = pools[(shape, kind)]
                if not pool:
                    pool.extend(shuffled(shape))
                yield kind, shape, members(*shape, *pool.pop(), pod)

    return warm, window()


class Job:
    def __init__(self, cell, devices, coords, seed):
        import jax
        import jax.numpy as jnp

        from repro import topology

        cfg, traffic = cell.config, cell.traffic
        fab = cfg["fabric"]
        self.topo = getattr(topology, fab["generator"])(*fab["args"])
        self.payload_bytes = int(traffic["payload_mib_per_member"] * (1 << 20))
        self.seed = seed
        self.device = devices[0]
        self.width = len(self.topo.npus)
        warm, self.sequence = requests(cfg, traffic, seed)

        def receives(recv, trash):
            return jnp.sum(recv != trash[:, None], axis=1)

        self._count = jax.jit(receives)
        self.done = []  # (request, program, plan)
        self.staged = []  # receives the chip counted, per plan of done
        self.spans = {"synthesis": [0.0, 0], "lowering": [0.0, 0]}
        for req in warm:
            self._plan(req)
        self._stage()  # compiles the one block shape
        self.done.clear()
        self.context = {"counters": {}, "spans": self.spans}

    def _plan(self, req):
        import jax

        from repro.comms import synthesize_program
        from repro.core.registry import AlgorithmRegistry
        from repro.core.request import CollectiveRequest

        kind, _shape, group = req
        with jax.profiler.TraceAnnotation(PLAN_SPAN):
            creq = CollectiveRequest(
                kind, group=group, pipelined=kind == "all_reduce",
                bytes=request_mib(kind, self.payload_bytes, len(group)))
            prog, plan = synthesize_program(self.topo, creq,
                                            registry=AlgorithmRegistry())
        self.done.append((req, prog, plan))

    def _stage(self) -> None:
        """Count on the chip the receives of every plan in ``done``."""
        import jax

        with jax.profiler.TraceAnnotation(STAGE_SPAN):
            recv, trash, owner = staged_rows([p for _, _, p in self.done],
                                             self.width, BLOCK_ROWS)
            rows = np.concatenate([np.asarray(self._count(
                jax.device_put(recv[b:b + BLOCK_ROWS], self.device),
                jax.device_put(trash[b:b + BLOCK_ROWS], self.device)))
                for b in range(0, len(recv), BLOCK_ROWS)])
        self.staged = np.bincount(owner, rows[:len(owner)],
                                  minlength=len(self.done)).astype(int).tolist()

    def window(self, seconds: float, traced: bool) -> Window:
        times, failed = [], 0
        undo = _time_layers(self.spans) if traced else (lambda: None)
        try:
            start = time.perf_counter()
            deadline = start + seconds
            end = start
            for req in self.sequence:
                t = time.perf_counter()
                try:
                    self._plan(req)
                except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
                    failed += 1
                    print(f"chipbench: request {req} failed: {e!r}", flush=True)
                end = time.perf_counter()
                times.append(end - t)
                if end >= deadline:
                    break
        finally:
            undo()
        self._stage()  # after the last timed request
        n = len(times)
        self.context["counters"]["requests"] = n
        notes = [f"{n} plan requests in {end - start:.3f} s; plan_ms.p95 "
                 f"over {n} samples"]
        for k, (total, calls) in self.spans.items():
            if calls:
                notes.append(f"{k}: {total * 1e3:.1f} ms in {calls} calls")
        ms = np.asarray(times) * 1e3
        return Window(start, end, n, failed,
                      {"plan_ms": (end - start) / n * 1e3,
                       "plan_ms.p95": float(np.percentile(ms, 95))}, notes)

    def release(self) -> None:
        pass

    def check(self) -> dict:
        wrong = staged = 0
        for i, (((kind, _shape, group), prog, plan), got) in enumerate(
                zip(self.done, self.staged)):
            x = contributions(kind, len(group), self.seed, i)
            try:
                wrong += mismatches(kind, group, prog, plan, x)
            except (KeyError, ValueError, IndexError):  # malformed plan
                wrong += x.size
            staged += got != prog.num_sends
        return {"plan_mismatches": (wrong, 0), "staged_receives_wrong": (staged, 0)}


def staged_rows(plans, width: int, block: int):
    """Every round's receive table of ``plans`` as one row of a
    [rows, width] int32 array, padded with rows of no receive to whole blocks
    of ``block`` rows; with each row's trash slot (``num_slots``, written by
    a device that receives nothing) and the index of the plan it belongs
    to."""
    recv, trash, owner = [], [], []
    for i, p in enumerate(plans):
        for rt in p.rounds:
            row = np.full(width, p.num_slots, np.int32)
            row[:p.num_devices] = rt.recv_slot
            recv.append(row)
            trash.append(p.num_slots)
            owner.append(i)
    pad = -len(recv) % block if recv else block
    recv += [np.zeros(width, np.int32)] * pad
    trash += [0] * pad
    return (np.stack(recv), np.asarray(trash, np.int32),
            np.asarray(owner, np.int64))


def _time_layers(spans):
    """Time the calls into synthesis and lowering (the traced run only);
    returns the function that takes the timers out again."""
    import jax

    from repro.comms import executor, primitives
    from repro.core.engine import SynthesisEngine

    depth = {k: 0 for k in spans}

    def timed(name, fn):
        def wrapper(*a, **kw):
            depth[name] += 1
            t = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
                    return fn(*a, **kw)
            finally:
                depth[name] -= 1
                if depth[name] == 0:
                    spans[name][0] += time.perf_counter() - t
                    spans[name][1] += 1
        return wrapper

    saved = [(SynthesisEngine, "collective", "synthesis"),
             (primitives, "to_ppermute_program", "lowering"),
             (executor, "plan_buffers", "lowering")]
    originals = [getattr(obj, attr) for obj, attr, _ in saved]
    for (obj, attr, name), fn in zip(saved, originals):
        setattr(obj, attr, timed(name, fn))

    def undo():
        for (obj, attr, _), fn in zip(saved, originals):
            setattr(obj, attr, fn)
    return undo


def contributions(kind: str, g: int, seed: int, index: int) -> np.ndarray:
    """[g, pieces] exact integer inputs of request ``index``: member r's
    input to piece k (an all-gather has one piece per member)."""
    pieces = 1 if kind == "all_gather" else g
    rng = np.random.default_rng([seed, index])
    return rng.integers(-(1 << 20), 1 << 20, size=(g, pieces), dtype=np.int64)


def mismatches(kind: str, group, prog, plan, x: np.ndarray) -> int:
    """Outputs of the collective, executed from ``plan``'s round tables on
    the inputs ``x``, that differ from the exact result; an output with no
    slot counts as wrong.

    Each round, every sender's slot is read first, then each receiver
    adds the value to its slot (receive-reduce) or overwrites it."""
    g = len(group)
    rank = {d: r for r, d in enumerate(group)}
    slot = plan.slot_of
    buf = np.zeros((plan.num_devices, plan.num_slots + 1), np.int64)
    chunks = sorted(prog.chunk_holders)
    owned = {}  # (source rank, dest rank) or source rank -> chunk
    if kind == "all_gather":
        for c in chunks:
            (src,) = prog.chunk_holders[c]
            owned[rank[src]] = c
            buf[src, slot[(src, c)]] = x[rank[src], 0]
    elif kind == "all_to_all":
        for c in chunks:
            (src,), (dst,) = prog.chunk_holders[c], prog.chunk_dests[c]
            owned[(rank[src], rank[dst])] = c
            buf[src, slot[(src, c)]] = x[rank[src], rank[dst]]
    else:  # reduce_scatter, all_reduce: chunk k is piece k
        if len(chunks) != g:
            return g * (g if kind == "all_reduce" else 1)
        for k, c in enumerate(chunks):
            for r, d in enumerate(group):
                if (d, c) in slot:
                    buf[d, slot[(d, c)]] = x[r, k]

    for rt in plan.rounds:
        sent = [(dst, buf[src, rt.send_slot[src]]) for src, dst in rt.perm]
        for dst, v in sent:
            s = rt.recv_slot[dst]
            buf[dst, s] = buf[dst, s] + v if rt.is_reduce[dst] else v

    def wrong(d, c, want) -> int:
        return int(c is None or (d, c) not in slot or buf[d, slot[(d, c)]] != want)

    total = x.sum(axis=0)
    if kind == "all_gather":
        return sum(wrong(d, owned.get(r), x[r, 0])
                   for d in group for r in range(g))
    if kind == "all_to_all":
        return sum(wrong(group[j], owned.get((i, j)), x[i, j])
                   for i in range(g) for j in range(g) if i != j)
    if kind == "reduce_scatter":
        return sum(wrong(group[k], chunks[k], total[k]) for k in range(g))
    return sum(wrong(d, chunks[k], total[k]) for d in group for k in range(g))


def setup(cell, devices, coords, seed) -> Job:
    return Job(cell, devices, coords, seed)
