"""Smoke run of PCCL's main path on TPU: plan, lower, execute.

    python chip_smoke.py             # one chip: device, plan path, train step
    python chip_smoke.py --chips 4   # the 2x2 host: PCCL collectives vs lax

The one-chip run names the device, plans every executable collective kind
for a 16x16 v5e pod through ``MeshCollectivePlanner`` (host latencies, cold
and on a registry hit), and trains mamba2-370m at its published width and
seq 4096 for a few steps through ``repro.launch.train.build_trainer``. With
``--chips 4`` the script runs only the collectives: every executable kind
over the full group, each 2-chip mesh-axis group and the diagonal group
(0, 3), at 4 KiB, 1 MiB and 32 MiB of f32 per device, each checked against
the ``jax.lax`` built-in on the same chips. Times printed here are bring-up
readings, not benchmark results.

The last line of stdout is one JSON object naming the device; it is printed
only when every phase passed. Without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.comms import primitives, synthesize_program  # noqa: E402
from repro.configs import SHAPES, get_config  # noqa: E402
from repro.core.registry import AlgorithmRegistry  # noqa: E402
from repro.core.request import CollectiveRequest  # noqa: E402
from repro.data.pipeline import DataPipeline  # noqa: E402
from repro.launch.sharding import MeshCollectivePlanner  # noqa: E402
from repro.launch.train import build_trainer, use_compile_cache  # noqa: E402
from repro.topology import mesh2d, tpu_v5e_pod  # noqa: E402

KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
REDUCTIONS = ("reduce_scatter", "all_reduce")
TRAIN_ARCH = "mamba2-370m"
# per-chip batch: the step compiled for one v5e chip needs 4.1 GiB of
# params + AdamW state and 6.2 GiB of temporaries at batch 2 (10.5 GiB at
# batch 4, too close to 16 GiB of HBM)
TRAIN_BATCH = 2
TRAIN_STEPS = 5
COLLECTIVE_BYTES = (4 << 10, 1 << 20, 32 << 20)  # f32 payload per device
# the 2x2 mesh axes, flattened row-major: flat index == mesh2d NPU id
AXES = ("data", "model")


def _request(kind: str, group=(), nbytes: float = 1.0) -> CollectiveRequest:
    """What ``MeshCollectivePlanner.program(kind, ...)`` asks for: all-reduce
    takes the pipelined flat route."""
    return CollectiveRequest(kind, group=group, bytes=nbytes,
                             pipelined=kind == "all_reduce")


def device_line(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# plan path (host)
# ---------------------------------------------------------------------------

def plan_phase(rows: int = 16, cols: int = 16) -> list[dict]:
    """Plan every executable kind on both axes of a rows x cols v5e pod.
    Group 0 is synthesized cold; group 1 is the same collective on another
    row/column, which the registry serves by relabeling. The registry lives
    in memory only, so every plan comes from this run's synthesis."""
    registry = AlgorithmRegistry()
    planner = MeshCollectivePlanner(tpu_v5e_pod(rows, cols),
                                    {"data": rows, "model": cols},
                                    registry=registry)
    out = []
    for kind in KINDS:
        req = _request(kind)
        for axis in AXES:
            times = []
            for group_index in (0, 1):
                hits = registry.stats.hits
                t0 = time.perf_counter()
                prog, plan = planner.program(req, axis, group_index)
                times.append(time.perf_counter() - t0)
                if group_index == 1 and registry.stats.hits == hits:
                    raise RuntimeError(
                        f"{kind}/{axis}: group 1 was not a registry hit")
                planner.algorithm(req, axis, group_index).validate()
            row = {"kind": kind, "axis": axis, "cold_s": times[0],
                   "hit_s": times[1], "rounds": prog.num_rounds,
                   "sends": prog.num_sends}
            out.append(row)
            print(f"plan {kind:<14} axis={axis:<5} {rows}x{cols} "
                  f"rounds={prog.num_rounds} sends={prog.num_sends} "
                  f"host_cold_ms={times[0] * 1e3:.1f} "
                  f"host_hit_ms={times[1] * 1e3:.1f} validated", flush=True)
    return out


# ---------------------------------------------------------------------------
# job step (one chip)
# ---------------------------------------------------------------------------

def train_phase(devices, cfg, *, batch: int, seq: int, steps: int,
                seed: int = 0) -> list[float]:
    """Train ``cfg`` from a seeded random init on seeded random tokens. The
    untrained model must score close to ln(vocab) on uniform tokens, and
    every loss and gradient norm must be finite."""
    trainer = build_trainer(cfg, devices, total_steps=steps)
    cfg, policy = trainer.lm.cfg, trainer.policy
    print(f"train {cfg.name} params={cfg.param_count()} "
          f"batch={batch * len(devices)} seq={seq}", flush=True)
    t0 = time.perf_counter()
    params, opt = trainer.init(seed)
    jax.block_until_ready((params, opt))
    print(f"train init_s={time.perf_counter() - t0:.2f}", flush=True)
    global_batch = batch * len(devices)
    pipe = DataPipeline(seed=seed, batch=global_batch, seq=seq,
                        vocab=cfg.vocab_size,
                        sharding=policy.named(policy.batch_spec(global_batch,
                                                                seq)))
    losses = []
    try:
        for _ in range(steps):
            step, data = next(pipe)
            t0 = time.perf_counter()
            params, opt, loss, gnorm = trainer.step(params, opt, data)
            loss, gnorm = float(loss), float(gnorm)  # waits for the step
            dt = time.perf_counter() - t0
            what = "compile+step" if step == 0 else "step"
            print(f"train step={step} loss={loss:.4f} grad_norm={gnorm:.4f} "
                  f"{what}_s={dt:.3f} tokens_per_s={global_batch * seq / dt:.0f}",
                  flush=True)
            if not (math.isfinite(loss) and math.isfinite(gnorm)):
                raise RuntimeError(f"step {step}: loss {loss}, grad norm {gnorm}")
            losses.append(loss)
    finally:
        pipe.close()
    uniform = math.log(cfg.vocab_size)
    if abs(losses[0] - uniform) > 1.0:
        raise RuntimeError(f"untrained loss {losses[0]:.4f} is not near "
                           f"ln(vocab) = {uniform:.4f}")
    return losses


# ---------------------------------------------------------------------------
# collectives (2x2)
# ---------------------------------------------------------------------------

def npu_devices(devices, coords) -> list:
    """The device at each ``mesh2d(2, 2)`` NPU: NPU r*2 + c sits at chip
    coordinates (x=c, y=r), so mesh2d's links are exactly the pairs of chips
    one ICI hop apart."""
    at = {tuple(c[:2]): d for d, c in zip(devices, coords)}
    if sorted(at) != [(0, 0), (0, 1), (1, 0), (1, 1)]:
        raise RuntimeError(f"not a 2x2 grid of chips: coords {coords}")
    return [at[(n % 2, n // 2)] for n in range(4)]


def _hops(coords_of_npu, a: int, b: int) -> int:
    return sum(abs(p - q) for p, q in zip(coords_of_npu[a], coords_of_npu[b]))


def _payload(kind: str, g: int, nbytes: int, rng) -> np.ndarray:
    """Seeded [4 NPUs, ...] f32 input of ``nbytes`` per device."""
    n = nbytes // 4
    shape = {"all_gather": (n,), "all_reduce": (n,)}.get(kind, (g, n // g))
    return rng.standard_normal((4, *shape), dtype=np.float32)


def _builtin(kind: str, axis):
    def f(xl):
        v = xl[0]
        if kind == "all_gather":
            r = lax.all_gather(v, axis)
        elif kind == "reduce_scatter":
            r = lax.psum_scatter(v, axis, scatter_dimension=0, tiled=False)
        elif kind == "all_reduce":
            r = lax.psum(v, axis)
        else:
            r = lax.all_to_all(v[:, None], axis, split_axis=0,
                               concat_axis=0)[:, 0]
        return r[None]
    return f


def _median_us(fn, x, iters: int = 10) -> float:
    fn(x).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)) * 1e6


def collectives_phase(devices, coords, sizes=COLLECTIVE_BYTES, *,
                      seed: int = 0, iters: int = 10) -> int:
    """Run every executable kind through PCCL and through the lax built-in
    on the same four chips; return the number of cases checked.

    Groups: the full 4, both groups of each 2-chip mesh axis (planned by
    ``MeshCollectivePlanner.program``) and the diagonal (0, 3), which has no
    link of its own, so NPU 1 or 2 must forward its chunks. Data movement
    must be bit-identical, reductions within 1e-5 of the largest reference
    value (summation order differs), and non-members exact zeros. Every
    permute of every lowered round must join chips one ICI hop apart."""
    by_npu = npu_devices(devices, coords)
    coords_of_npu = [tuple(coords[devices.index(d)][:2]) for d in by_npu]
    for n, d in enumerate(by_npu):
        print(f"fabric mesh2d(2,2) NPU {n} -> device id={d.id} "
              f"coords={coords_of_npu[n]}", flush=True)
    topo = mesh2d(2, 2)
    links = {(l.src, l.dst) for l in topo.links}
    for a in range(4):
        for b in range(4):
            if ((a, b) in links) != (_hops(coords_of_npu, a, b) == 1):
                raise RuntimeError(f"mesh2d link {a}-{b} disagrees with coords")

    registry = AlgorithmRegistry()
    planner = MeshCollectivePlanner(topo, dict(zip(AXES, (2, 2))),
                                    registry=registry)

    def mesh_of(layout):  # layout[i][j] = NPU at mesh position (i, j)
        return Mesh(np.array([[by_npu[n] for n in row] for row in layout]),
                    AXES)

    grid, diag = ((0, 1), (2, 3)), ((0, 3), (1, 2))
    # (label, group, reference mesh layout, reference axis, planner route)
    groups = [("all4", (0, 1, 2, 3), grid, AXES, None)]
    for axis in AXES:
        for i, group in enumerate(planner.axis_groups(axis)):
            groups.append((f"{axis}{i}", tuple(group), grid, axis, (axis, i)))
    groups.append(("diag03", (0, 3), diag, "model", None))

    pccl_mesh = mesh_of(grid)
    sharding = NamedSharding(pccl_mesh, P(AXES))
    rng = np.random.default_rng(seed)
    refs = {}
    checked = 0
    for nbytes in sizes:
        for kind in KINDS:
            fn = getattr(primitives, f"pccl_{kind}")
            x_g = {g: _payload(kind, g, nbytes, rng) for g in (2, 4)}
            for label, group, layout, ref_axis, route in groups:
                members, g = set(group), len(group)
                x = x_g[g]
                req = _request(kind, group, nbytes)
                if route is None:
                    program = synthesize_program(topo, req, registry=registry)
                else:
                    program = planner.program(_request(kind, nbytes=nbytes),
                                              *route)
                prog, plan = program
                pairs = {p for rt in plan.rounds for p in rt.perm}
                far = [p for p in pairs if _hops(coords_of_npu, *p) != 1]
                if far:
                    raise RuntimeError(f"{kind}/{label}: permutes {far} are "
                                       f"not one ICI hop")
                forwarders = {d for p in pairs for d in p} - members
                if label == "diag03" and not forwarders:
                    raise RuntimeError(f"{kind}/{label}: nothing forwarded")

                def run(xl, _fn=fn, _req=req, _program=program):
                    return _fn(xl[0], AXES, topo, _req, program=_program)[None]

                mine = jax.jit(jax.shard_map(run, mesh=pccl_mesh,
                                             in_specs=P(AXES),
                                             out_specs=P(AXES)))
                flat = [n for row in layout for n in row]
                ref_mesh = mesh_of(layout)
                key = (kind, layout, ref_axis)
                if key not in refs:  # one jit (one compile per size) per key
                    refs[key] = jax.jit(jax.shard_map(
                        _builtin(kind, ref_axis), mesh=ref_mesh,
                        in_specs=P(AXES), out_specs=P(AXES)))
                ref = refs[key]
                xd = jax.device_put(x, sharding)
                xr = jax.device_put(x[flat], NamedSharding(ref_mesh, P(AXES)))
                got = np.asarray(mine(xd))
                want = np.empty_like(got)
                want[flat] = np.asarray(ref(xr))
                for n in range(4):
                    if n not in members:
                        if np.any(got[n] != 0):
                            raise RuntimeError(f"{kind}/{label}: non-member "
                                               f"NPU {n} is not zero")
                    elif kind in REDUCTIONS:
                        err = np.max(np.abs(got[n] - want[n]))
                        bound = 1e-5 * np.max(np.abs(want[n]))
                        if not err <= bound:
                            raise RuntimeError(
                                f"{kind}/{label}/{nbytes}B: NPU {n} off by "
                                f"{err} (bound {bound})")
                    elif not np.array_equal(got[n], want[n]):
                        raise RuntimeError(f"{kind}/{label}/{nbytes}B: NPU {n} "
                                           f"differs from lax")
                t_pccl = _median_us(mine, xd, iters)
                t_ref = _median_us(ref, xr, iters)
                checked += 1
                print(f"coll {kind:<14} group={label:<6} bytes={nbytes:<9} "
                      f"rounds={prog.num_rounds} sends={prog.num_sends} "
                      f"forwarders={sorted(forwarders)} match "
                      f"pccl_us={t_pccl:.1f} lax_us={t_ref:.1f}", flush=True)
    return checked


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{platform!r} ({devices[0].device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} chips, found {len(devices)}")
    devices = devices[:args.chips]
    print(f"device platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)} (of {jax.device_count()})", flush=True)
    print(f"compile cache {use_compile_cache()}", flush=True)

    if args.chips == 4:
        n = collectives_phase(devices, [d.coords for d in devices])
        print(f"collectives ok: {n} cases match lax", flush=True)
    else:
        plan_phase()
        losses = train_phase(devices, get_config(TRAIN_ARCH),
                             batch=TRAIN_BATCH,
                             seq=SHAPES["train_4k"].seq_len,
                             steps=TRAIN_STEPS)
        print(f"train ok: losses {losses}", flush=True)
    print(json.dumps({"ok": True, "device": device_line(devices)}))


if __name__ == "__main__":
    main()
