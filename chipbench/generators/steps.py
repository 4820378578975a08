"""Executed collectives, closed loop: a job's step of collective traffic,
issued step after step over the chips of one host.

A step is an ordered list of calls, each ``count`` all-reduces of
``elements`` values per chip over the whole group, run in order inside one
jitted program as one communication stream runs them. Each all-reduce is
``repro.comms.primitives.pccl_all_reduce`` with the program that
``MeshCollectivePlanner.program`` plans for the group; plans and compiles
happen in set-up. The traffic's ``step`` gives the calls as
``{"kind", "buckets_of": "gradient"}``: the configuration's gradient cut
into buckets of ``bucket_cap_mib``.

Payloads are made on the chips from the seed. The plain reference makes
every chip's payload again on each chip, sums them in float32 and holds
every element of every bucket on every chip of the last timed step to it:
``ar_rel_err`` is the largest ``|out - sum| / sum(|x|)``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

from chipbench import device
from chipbench.harness import Window
from chipbench.units import request_mib

AXIS = "data"
STEP_SPAN = "chipbench.step"
# the error of a float32 sum of g terms in any order is at most (g - 1)
# units of round-off times sum(|x|), 1.8e-7 for g = 4 (twice that against
# a float32 reference). On the 2x2 v5e host sound runs read 2.38e-7 on every
# seed and the bfloat16 control 1.3e-2; the limit sits between, nearer
# the control (PERF.md gives the readings)
AR_REL_ERR_LIMIT = 1e-4
DTYPES = {"float32": 4, "bfloat16": 2}


@dataclass(frozen=True)
class Call:
    kind: str
    elements: int  # per chip, per collective
    count: int


def step_calls(config: dict, traffic: dict) -> list[Call]:
    """The ordered calls of one step."""
    itemsize = DTYPES[config["dtype"]]
    calls = []
    for part in traffic["step"]:
        if part["kind"] != "all_reduce" or part.get("buckets_of") != "gradient":
            raise ValueError(f"the steps generator runs the all_reduce of a "
                             f"gradient's buckets, not {part}")
        total = config["parameters"] * itemsize
        cap = int(config["bucket_cap_mib"] * (1 << 20))
        full, rest = divmod(total, cap)
        calls.append(Call(part["kind"], cap // itemsize, full))
        if rest:
            calls.append(Call(part["kind"], rest // itemsize, 1))
    return calls


def payload(key, call: int, bucket, rank, elements: int, dtype):
    """Chip ``rank``'s input to bucket ``bucket`` of call ``call``."""
    import jax

    k = jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(key, call), bucket), rank)
    return jax.random.normal(k, (elements,), dtype)


class Job:
    def __init__(self, cell, devices, coords, seed):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from repro import topology
        from repro.comms import primitives
        from repro.launch.sharding import MeshCollectivePlanner

        cfg, traffic = cell.config, cell.traffic
        fab = cfg["fabric"]
        self.fabric = getattr(topology, fab["generator"])(*fab["args"])
        by_npu = device.npu_devices(devices, coords)
        self.mesh = Mesh(np.array(by_npu), (AXIS,))
        self.g = len(by_npu)
        self.dtype = jnp.dtype(cfg["dtype"])
        self.calls = step_calls(cfg, traffic)
        for c in self.calls:
            if c.elements % self.g:
                raise ValueError(f"{c.elements} elements do not split into "
                                 f"{self.g} shards")
        planner = MeshCollectivePlanner(self.fabric, {AXIS: self.g})
        itemsize = self.dtype.itemsize
        self.programs = [planner.program(
            c.kind, AXIS, 0,
            nbytes=request_mib(c.kind, c.elements * itemsize, self.g))
            for c in self.calls]
        self.spec = primitives.CollectiveSpec("all_reduce", tuple(range(self.g)))
        spec_out = tuple(P(AXIS) for _ in self.calls)

        def make(key):
            r = jax.lax.axis_index(AXIS)
            return tuple(jax.vmap(lambda b, i=i, c=c: payload(
                key, i, b, r, c.elements, self.dtype))(
                    jnp.arange(c.count))[None]
                for i, c in enumerate(self.calls))

        self.key = device.seed_key(seed)
        self.inputs = jax.jit(jax.shard_map(
            make, mesh=self.mesh, in_specs=P(), out_specs=spec_out))(self.key)

        def body(*xs):
            outs = []
            for x, prog in zip(xs, self.programs):
                def one(carry, xb, prog=prog):
                    with jax.named_scope("pccl_bucket_all_reduce"):
                        y = primitives.pccl_all_reduce(
                            xb, AXIS, self.fabric, self.spec, program=prog)
                    return carry, y
                _, ys = jax.lax.scan(one, None, x[0])
                outs.append(ys[None])
            return tuple(outs)

        sharded = jax.shard_map(body, mesh=self.mesh, in_specs=spec_out,
                                out_specs=spec_out)

        def ddp_step(*xs):
            return sharded(*xs)

        self.step = jax.jit(ddp_step)
        # warm-up: compile (or load from the cache) and run the one shape
        self.out = jax.block_until_ready(self.step(*self.inputs))
        payload_bytes = [c.elements * itemsize for c in self.calls]
        self.context = {
            "counters": {"rounds": self.programs[0][0].num_rounds},
            "work": [(c.kind, b, self.g, c.count)
                     for c, b in zip(self.calls, payload_bytes)],
            "step_module": "jit_ddp_step",
            "spans": {},
        }

    def window(self, seconds: float, traced: bool) -> Window:
        import jax

        start = time.perf_counter()
        deadline = start + seconds
        ends = []
        out = self.out
        while True:
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                out = jax.block_until_ready(self.step(*self.inputs))
            ends.append(time.perf_counter())
            if ends[-1] >= deadline:
                break
        self.out = out
        steps, end = len(ends), ends[-1]
        b = sum(c.count for c in self.calls)
        each = np.diff([start, *ends]) * 1e3
        return Window(start, end, steps, 0,
                      {"step_ms": (end - start) / steps * 1e3},
                      [f"{steps} steps of {b} all-reduces in "
                       f"{end - start:.3f} s",
                       "step ms min/q1/median/q3/max: " + " ".join(
                           f"{v:.3f}" for v in np.percentile(
                               each, [0, 25, 50, 75, 100]))])

    def release(self) -> None:
        self.inputs = None

    def check(self) -> dict:
        err = np.max(np.asarray(reference(self)(self.key, *self.out)))
        return {"ar_rel_err": (float(err), AR_REL_ERR_LIMIT)}


def reference(job):
    """The plain reference, jitted: on each chip, every chip's payload made
    again from the seed and summed in float32, against what the last timed
    step returned on that chip, bucket by bucket. Returns each chip's
    largest ``|out - sum| / sum(|x|)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    tiny = jnp.finfo(job.dtype).tiny

    def ref(key, *outs):
        worst = jax.lax.pcast(jnp.zeros((), jnp.float32), (AXIS,),
                              to="varying")
        for i, (c, out) in enumerate(zip(job.calls, outs)):
            def one(m, b_out, i=i, c=c):
                b, o = b_out
                s = a = None
                for r in range(job.g):
                    x = payload(key, i, b, r, c.elements, job.dtype)
                    s = x if s is None else s + x
                    a = jnp.abs(x) if a is None else a + jnp.abs(x)
                e = jnp.max(jnp.abs(o - s) / jnp.maximum(a, tiny))
                return jnp.maximum(m, e.astype(jnp.float32)), None

            worst, _ = jax.lax.scan(one, worst,
                                    (jnp.arange(c.count), out[0]))
        return worst[None]

    return jax.jit(jax.shard_map(
        ref, mesh=job.mesh, in_specs=(P(), *(P(AXIS) for _ in job.calls)),
        out_specs=P(AXIS)))


def setup(cell, devices, coords, seed) -> Job:
    return Job(cell, devices, coords, seed)
