"""Tensor-parallel decode, closed loop: the collectives of one decoded
token, issued step after step over the chips of one host.

A step is what Megatron-style TP (arXiv:1909.08053 Sec. 3) issues for one
token, read from the configuration: the vocab-parallel embedding's
all-reduce of the ``[decode_batch, hidden]`` activations, then two a layer
(the row-parallel attention output and MLP down projections) over
``layers``, run as one ``lax.scan``, then the all-gather of the
``[decode_batch, padded_vocab / tensor_parallel]`` logits shards, so every
chip can sample. Every call runs over the whole group in the
configuration's dtype as ``repro.comms.primitives.pccl_<kind>``, with the
program that ``MeshCollectivePlanner.program`` plans for the group. Each
call takes the previous call's output as a dependence, so the calls run one
after another on one stream, as a model's layers issue them. The whole step
is one jitted program, ``jit_tp_decode_step``; plans and compiles happen in
set-up.

Payloads are made on the chips from the seed, one for each call and member;
call ``0`` is the embedding's, ``1 + 2 * i + j`` layer ``i``'s ``j``-th and
``1 + 2 * layers`` the all-gather. The plain reference remakes every
member's payloads on each chip and holds what the last timed step returned
there to them:

* ``ar_rel_err``: the largest ``|out - s| / sum(|x|)`` over every element
  of every all-reduce, where ``s`` is the float32 sum of the members'
  inputs;
* ``ag_mismatches``: the gathered elements that are not bit for bit the
  members' inputs, in member order.
"""

from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass

import numpy as np

from chipbench import device
from chipbench.harness import Window
from chipbench.units import request_mib

AXIS = "model"
STEP_SPAN = "chipbench.step"
STEP_MODULE = "jit_tp_decode_step"
KINDS = ("all_reduce", "all_gather")
# an all-reduce over g members rounds g - 1 partial sums to bfloat16, each
# by at most the unit round-off 2**-8 of sum(|x|), and the output once
# more: 4 * 2**-8 = 1.5625e-2 over 4 members. A bound on round-off, not a
# fit to readings; a float32 reference sum is exact to 2**-24 beside it.
# Another dtype states its own limit.
UNIT_ROUNDOFF = {"bfloat16": 2.0**-8}


@dataclass(frozen=True)
class Call:
    kind: str
    shape: tuple[int, ...]  # each chip's input
    count: int  # times one step issues it


def step_calls(config: dict) -> list[Call]:
    """The step's two calls with their counts: the activations' all-reduce
    and the logits shards' all-gather."""
    shard, left = divmod(config["padded_vocab"], config["tensor_parallel"])
    if left:
        raise ValueError(f"padded_vocab {config['padded_vocab']} does not "
                         f"split into {config['tensor_parallel']} shards")
    batch = config["decode_batch"]
    return [Call("all_reduce", (batch, config["hidden"]),
                 1 + 2 * config["layers"]),
            Call("all_gather", (batch, shard), 1)]


def payload(key, call, rank, shape, dtype):
    """Member ``rank``'s input to call number ``call`` of the step."""
    import jax

    k = jax.random.fold_in(jax.random.fold_in(key, call), rank)
    return jax.random.normal(k, shape, dtype)


def describe(calls: list[Call], dtype) -> str:
    return "step calls: " + ", ".join(
        f"{c.count} x {c.kind} {dtype.name}{list(c.shape)} "
        f"({np.prod(c.shape) * dtype.itemsize / 2**10:.6g} KiB per chip)"
        for c in calls)


class Job:
    def __init__(self, cell, devices, coords, seed):
        import jax

        self.build(cell, device.npu_devices(devices, coords))
        self.key = device.seed_key(seed)
        self.inputs = self.make(self.key)
        # warm-up: compile (or load from the cache) and run the one shape
        self.out = jax.block_until_ready(self.step(*self.inputs))
        rounds = {c.kind: self.programs[c.kind][0].num_rounds
                  for c in self.calls}
        self.context = {
            "counters": {
                "rounds": rounds["all_reduce"],
                "rounds_per_step": sum(c.count * rounds[c.kind]
                                       for c in self.calls)},
            "work": [(c.kind, int(np.prod(c.shape)) * self.dtype.itemsize,
                      self.g, c.count) for c in self.calls],
            "step_module": STEP_MODULE,
            "spans": {},
        }

    def build(self, cell, by_npu) -> None:
        """Plan both calls for the chips ``by_npu`` (in NPU order) and
        build, without running anything, the jitted ``make(key)`` of the
        payloads and the jitted ``step(embed, attn, mlp, logits)``."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import Mesh
        from jax.sharding import PartitionSpec as P

        from repro import topology
        from repro.comms import primitives
        from repro.launch.sharding import MeshCollectivePlanner

        cfg = cell.config
        fab = cfg["fabric"]
        self.fabric = getattr(topology, fab["generator"])(*fab["args"])
        self.mesh = Mesh(np.array(by_npu), (AXIS,))
        g = self.g = len(by_npu)
        self.layers = layers = cfg["layers"]
        self.dtype = dtype = jnp.dtype(cfg["dtype"])
        if dtype.name not in UNIT_ROUNDOFF:
            raise ValueError(f"no stated all-reduce limit for {dtype}")
        self.calls = step_calls(cfg)
        act, shard = (c.shape for c in self.calls)
        if act[0] % g:
            raise ValueError(f"{act} does not split into {g} shards")
        planner = MeshCollectivePlanner(self.fabric, {AXIS: g})
        self.programs = {c.kind: planner.program(
            c.kind, AXIS, 0, nbytes=request_mib(
                c.kind, int(np.prod(c.shape)) * dtype.itemsize, g))
            for c in self.calls}
        specs = {k: primitives.CollectiveSpec(k, tuple(range(g))) for k in KINDS}
        spec_in = (P(AXIS),) * 4

        def make(key):
            r = lax.axis_index(AXIS)
            x = lambda call, shape: payload(key, call, r, shape, dtype)  # noqa: E731
            layer = lambda j: jax.vmap(  # noqa: E731
                lambda i: x(1 + 2 * i + j, act))(jnp.arange(layers))
            return tuple(v[None] for v in (x(0, act), layer(0), layer(1),
                                           x(1 + 2 * layers, shard)))

        self.make = jax.jit(jax.shard_map(
            make, mesh=self.mesh, in_specs=P(), out_specs=spec_in))

        def run(kind, x, prev):
            # the call waits for the one before: its input is ready only
            # once the previous output is
            if prev is not None:
                _, x = lax.optimization_barrier((prev, x))
            fn = getattr(primitives, f"pccl_{kind}")
            return fn(x, AXIS, self.fabric, specs[kind],
                      program=self.programs[kind])

        def body(embed, attn, mlp, logits):
            h = run("all_reduce", embed[0], None)

            def layer(carry, xs):
                a = run("all_reduce", xs[0], carry)
                m = run("all_reduce", xs[1], a)
                return m, (a, m)

            last, (a, m) = lax.scan(layer, h, (attn[0], mlp[0]))
            out = run("all_gather", logits[0], last)
            return tuple(o[None] for o in (h, a, m, out))

        sharded = jax.shard_map(body, mesh=self.mesh, in_specs=spec_in,
                                out_specs=spec_in)

        def tp_decode_step(*xs):
            return sharded(*xs)

        self.step = jax.jit(tp_decode_step)

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
        each = np.diff([start, *ends]) * 1e3
        return Window(start, end, steps, 0,
                      {"step_ms": (end - start) / steps * 1e3},
                      [describe(self.calls, self.dtype),
                       f"{steps} steps of {sum(c.count for c in self.calls)} "
                       f"calls in {end - start:.3f} s",
                       "step ms min/q1/median/q3/max: " + " ".join(
                           f"{v:.3f}" for v in np.percentile(
                               each, [0, 25, 50, 75, 100]))])

    def release(self) -> None:
        self.inputs = None

    def check(self) -> dict:
        err, bad = (np.asarray(a) for a in reference(self)(self.key, *self.out))
        return {"ar_rel_err": (float(err.max()),
                               self.g * UNIT_ROUNDOFF[self.dtype.name]),
                "ag_mismatches": (int(bad.sum()), 0)}


def reference(job):
    """The plain reference, jitted: on each chip, every member's payloads
    made again from the seed, against what the last timed step returned on
    that chip, call by call. Returns each chip's largest
    ``|out - s| / sum(|x|)`` over its all-reduces, with ``s`` the float32
    sum of the inputs, and its count of gathered elements that differ in any
    bit from the inputs in member order."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    f32, tiny = jnp.float32, jnp.finfo(jnp.float32).tiny
    fin = jnp.finfo(job.dtype)
    bits = jnp.dtype(f"uint{8 * job.dtype.itemsize}")
    act, shard = (c.shape for c in job.calls)

    def inputs(key, call, shape):
        return [payload(key, call, r, shape, job.dtype) for r in range(job.g)]

    def ar_err(key, call, out):
        # XLA may keep a payload made in this program at float32 where it is
        # used (excess precision); rounded here, it is the input the
        # collective was given
        xs = [lax.reduce_precision(x.astype(f32), fin.nexp, fin.nmant)
              for x in inputs(key, call, act)]
        s, a = sum(xs), sum(jnp.abs(x) for x in xs)
        return jnp.max(jnp.abs(out.astype(f32) - s) / jnp.maximum(a, tiny))

    def ref(key, embed, attn, mlp, gathered):
        def layer(worst, i_a_m):
            i, a, m = i_a_m
            return jnp.maximum(worst, jnp.maximum(
                ar_err(key, 1 + 2 * i, a), ar_err(key, 2 + 2 * i, m))), None

        worst, _ = lax.scan(layer, ar_err(key, 0, embed[0]),
                            (jnp.arange(job.layers), attn[0], mlp[0]))
        want = lax.bitcast_convert_type(
            jnp.stack(inputs(key, 1 + 2 * job.layers, shard)), bits)
        got = lax.bitcast_convert_type(gathered[0], bits)
        return worst[None], jnp.sum(got != want, dtype=jnp.int32)[None]

    return jax.jit(jax.shard_map(
        ref, mesh=job.mesh, in_specs=(P(),) + (P(AXIS),) * 4,
        out_specs=(P(AXIS), P(AXIS))))


@contextlib.contextmanager
def control(name: str):
    """Another collective in the program's place for the ``with`` body:

    * ``member_left_out``: every all-reduce sums all members but the last;
    * ``fp8_cast``: every all-reduce's input is rounded through
      ``float8_e4m3fn``, a lower precision than the configuration states;
    * ``xla_builtin``: ``lax.psum`` and ``lax.all_gather``, XLA's own
      collectives, to time the same step beside PCCL's (it reads correct).

    The first two are the controls that must read over ``ar_rel_err``'s
    limit; the benchmark's own runs never run any of them."""
    import jax.numpy as jnp
    from jax import lax

    from repro.comms import primitives

    real = {k: getattr(primitives, f"pccl_{k}") for k in KINDS}

    def member_left_out(x, axis_name, *a, **kw):
        last = lax.axis_index(axis_name) == lax.axis_size(axis_name) - 1
        return real["all_reduce"](jnp.where(last, 0, x), axis_name, *a, **kw)

    def fp8_cast(x, *a, **kw):
        return real["all_reduce"](
            x.astype(jnp.float8_e4m3fn).astype(x.dtype), *a, **kw)

    planted = {
        "member_left_out": {"all_reduce": member_left_out},
        "fp8_cast": {"all_reduce": fp8_cast},
        "xla_builtin": {
            "all_reduce": lambda x, axis_name, *a, **kw: lax.psum(x, axis_name),
            "all_gather": lambda x, axis_name, *a, **kw: lax.all_gather(
                x, axis_name)},
    }[name]
    for kind, fn in planted.items():
        setattr(primitives, f"pccl_{kind}", fn)
    try:
        yield
    finally:
        for kind, fn in real.items():
            setattr(primitives, f"pccl_{kind}", fn)


def setup(cell, devices, coords, seed) -> Job:
    return Job(cell, devices, coords, seed)
