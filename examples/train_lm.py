"""End-to-end training driver: data pipeline -> sharded train step ->
async checkpointing -> straggler monitoring -> (simulated) failure recovery.

    PYTHONPATH=src python examples/train_lm.py                 # quick (~10M)
    PYTHONPATH=src python examples/train_lm.py --model 100m --steps 300

Data-parallel training over a multi-device mesh can route the gradient
all-reduce through a PCCL-synthesized, topology-aware ppermute schedule
instead of XLA's built-in psum:

    PYTHONPATH=src python examples/train_lm.py \
        --dp 8 --host-devices 8 --collectives pccl

``--compare-collectives`` runs the same steps through both implementations
from the same initialization and prints the max loss/param divergence
(`PCCL_CONFORMANCE ...` — asserted by the mesh conformance suite).

The ~100M configuration is the deliverable's "train a ~100M model for a few
hundred steps" driver; the default is a smaller config so the example runs in
seconds on one CPU. All machinery is the production path: ShardingPolicy,
remat, AdamW + cosine schedule, deterministic restartable data.
"""

import argparse
import os
import sys
import time

# --host-devices must take effect before jax initializes its backend, so
# peek at argv ahead of the jax import
_early = argparse.ArgumentParser(add_help=False)
_early.add_argument("--host-devices", type=int, default=0)
_hd = _early.parse_known_args(sys.argv[1:])[0].host_devices
if _hd:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_hd}"
    ).strip()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.checkpoint import Checkpointer  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.data.pipeline import DataPipeline  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.sharding import ShardingPolicy  # noqa: E402
from repro.models import LM  # noqa: E402
from repro.optim import adamw_init, adamw_update, cosine_schedule  # noqa: E402
from repro.runtime import StragglerMonitor  # noqa: E402
from repro.runtime.fault_tolerance import StepTimer  # noqa: E402

MODELS = {
    # tiny: mesh-conformance subprocess tests | ~10M: d=256, 4L
    # ~100M: d=768, 12L (GPT-2-small-ish)
    "tiny": dict(num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                 head_dim=32, d_ff=256, vocab_size=512),
    "10m": dict(num_layers=4, d_model=256, num_heads=8, num_kv_heads=4,
                head_dim=32, d_ff=1024, vocab_size=8192),
    "100m": dict(num_layers=12, d_model=768, num_heads=12, num_kv_heads=12,
                 head_dim=64, d_ff=3072, vocab_size=32000),
}


def build_dp_step(lm, lr, mesh, dp: int, collectives: str):
    """Data-parallel train step: per-device loss/grads inside shard_map, a
    global gradient mean, then a replicated AdamW update.

    ``collectives="pccl"`` flattens the gradients (plus the loss scalar)
    into one vector and all-reduces it with a PCCL-synthesized ppermute
    schedule served by the PlanService — synthesized for a bidirectional
    ring fabric over the data axis, executed with the executor's static
    buffer plan. ``collectives="xla"`` is the lax.psum baseline.
    """
    program = None
    req = None
    topo = None
    if collectives == "pccl":
        from repro.core import CollectiveRequest
        from repro.core.planservice import PlanService
        from repro.topology import ring

        topo = ring(dp, bidirectional=True)
        svc = PlanService()
        program = svc.program(topo, {"data": dp}, "all_reduce", "data")
        req = CollectiveRequest("all_reduce", group=tuple(range(dp)))

    def mean_over_devices(vec):
        if collectives == "pccl":
            from repro.comms import pccl_all_reduce

            # the program splits the vector into one or two chunks a member
            pad = (-vec.size) % len(program[0].chunk_holders)
            if pad:
                vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
            vec = pccl_all_reduce(vec, "data", topo, req, program=program)
            if pad:
                vec = vec[:-pad]
        else:
            vec = lax.psum(vec, "data")
        return vec / dp

    def f(params, opt, local_batch):
        (loss, _metrics), grads = jax.value_and_grad(
            lm.loss, has_aux=True)(params, local_batch)
        flat, treedef = jax.tree_util.tree_flatten(grads)
        shapes = [g.shape for g in flat]
        sizes = [g.size for g in flat]
        vec = jnp.concatenate(
            [g.reshape(-1).astype(jnp.float32) for g in flat]
            + [loss.reshape(1).astype(jnp.float32)])
        vec = mean_over_devices(vec)
        loss_mean = vec[-1]
        vec = vec[:-1]
        out, off = [], 0
        for g, shp, size in zip(flat, shapes, sizes):
            out.append(vec[off:off + size].reshape(shp).astype(g.dtype))
            off += size
        grads = jax.tree_util.tree_unflatten(treedef, out)
        params, opt, om = adamw_update(params, grads, opt, lr=lr)
        return params, opt, loss_mean, om["grad_norm"]

    # the ppermute-built all-reduce returns values the replication
    # checker cannot infer as replicated
    step = jax.shard_map(f, mesh=mesh,
                         in_specs=(P(), P(), P("data")),
                         out_specs=(P(), P(), P(), P()), check_vma=False)
    return jax.jit(step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="10m", choices=sorted(MODELS))
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel devices (needs that many jax "
                    "devices; see --host-devices)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host-CPU devices (sets XLA_FLAGS before "
                    "jax initializes)")
    ap.add_argument("--collectives", default="xla", choices=("xla", "pccl"),
                    help="gradient all-reduce implementation when --dp > 1")
    ap.add_argument("--compare-collectives", action="store_true",
                    help="run every step through both xla and pccl "
                    "collectives and report the max divergence")
    args = ap.parse_args()

    cfg = get_config("llama3.2-1b").reduced(**MODELS[args.model])
    print(f"model: {cfg.name} reduced -> {cfg.param_count()/1e6:.1f}M params")

    dp = max(args.dp, 1)
    if dp > 1:
        if args.batch % dp:
            raise SystemExit(f"--batch {args.batch} not divisible by "
                             f"--dp {dp}")
        if jax.device_count() < dp:
            raise SystemExit(f"--dp {dp} needs {dp} jax devices (have "
                             f"{jax.device_count()}); pass --host-devices")
        mesh = make_mesh((dp,), ("data",))
        lm = LM(cfg, remat=True)  # no TP constraints inside shard_map
    else:
        mesh = make_mesh((1, 1), ("data", "model"))
        policy = ShardingPolicy(mesh, cfg)
        lm = LM(cfg, policy=policy, remat=True)

    params = lm.init(jax.random.PRNGKey(args.seed))
    opt = adamw_init(params)
    lr = cosine_schedule(3e-4, warmup=20, total=max(args.steps, 100))

    if dp > 1:
        train_step = build_dp_step(lm, lr, mesh, dp, args.collectives)
    else:
        @jax.jit
        def train_step(params, opt, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lm.loss, has_aux=True)(params, batch)
            params, opt, om = adamw_update(params, grads, opt, lr=lr)
            return params, opt, loss, om["grad_norm"]

    if args.compare_collectives:
        if dp <= 1:
            raise SystemExit("--compare-collectives needs --dp > 1")
        step_xla = build_dp_step(lm, lr, mesh, dp, "xla")
        step_pccl = build_dp_step(lm, lr, mesh, dp, "pccl")
        pipe = DataPipeline(seed=1234, batch=args.batch, seq=args.seq,
                            vocab=cfg.vocab_size, start_step=0)
        px, ox = params, opt
        pp, op_ = params, opt
        max_loss_diff = 0.0
        for _ in range(args.steps):
            _, batch = next(pipe)
            px, ox, lx, _ = step_xla(px, ox, batch)
            pp, op_, lp, _ = step_pccl(pp, op_, batch)
            d = abs(float(lx) - float(lp))
            max_loss_diff = max(max_loss_diff, d)
            print(f"step loss xla={float(lx):.6f} pccl={float(lp):.6f} "
                  f"diff={d:.3e}")
        pipe.close()
        lx_leaves = jax.tree_util.tree_leaves(px)
        lp_leaves = jax.tree_util.tree_leaves(pp)
        max_param_diff = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
            for a, b in zip(lx_leaves, lp_leaves))
        print(f"PCCL_CONFORMANCE max_loss_diff={max_loss_diff:.3e} "
              f"max_param_diff={max_param_diff:.3e}")
        return

    ck = Checkpointer(args.ckpt_dir, keep=2)
    start_step = 0
    if args.resume and ck.latest_step() is not None:
        start_step, restored = ck.restore({"params": params, "opt": opt})
        params, opt = restored["params"], restored["opt"]
        print(f"resumed from checkpoint at step {start_step}")

    pipe = DataPipeline(seed=1234, batch=args.batch, seq=args.seq,
                        vocab=cfg.vocab_size, start_step=start_step)
    monitor = StragglerMonitor()

    t_start = time.time()
    for _ in range(start_step, args.steps):
        step, batch = next(pipe)
        with StepTimer(monitor) as timer:
            params, opt, loss, gnorm = train_step(params, opt, batch)
            loss.block_until_ready()
        if timer.verdict != "ok":
            print(f"  [straggler] step {step} verdict={timer.verdict}")
        if step % 5 == 0 or step == args.steps - 1:
            tok_s = args.batch * args.seq / max(monitor.median, 1e-9)
            print(f"step {step:4d}  loss={float(loss):.4f}  "
                  f"gnorm={float(gnorm):.3f}  ~{tok_s:,.0f} tok/s")
        if step and step % args.ckpt_every == 0:
            ck.save(step, {"params": params, "opt": opt})
    ck.wait()
    pipe.close()
    print(f"done: {args.steps - start_step} steps in "
          f"{time.time() - t_start:.1f}s; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
