"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --steps 20 --reduced            # CPU-runnable smoke
    PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m \
        --steps 5 --batch 2             # full width, seq 4096, on one chip
    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --dry-run
        # lower+compile the full production cell instead of executing

The launcher wires together the production pieces: mesh + ShardingPolicy,
the jitted AdamW train step (``build_trainer``, which ``chip_smoke.py`` drives
too), deterministic DataPipeline, async Checkpointer, straggler monitor, and
(on restart) elastic recovery. ``--batch`` is per device; the global batch is
that times the local device count.
"""

from __future__ import annotations

import argparse
import os
from functools import partial
from pathlib import Path
from typing import Callable, NamedTuple

import jax
from jax.sharding import PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.launch.sharding import ShardingPolicy, pad_heads
from repro.models import LM
from repro.optim import AdamWState, adamw_init, adamw_update, cosine_schedule
from repro.runtime import StragglerMonitor
from repro.runtime.fault_tolerance import StepTimer

REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compile cache where ``JAX_COMPILATION_CACHE_DIR``
    says (JAX reads it itself), else at the fixed ``<repo>/.jax_cache``: the
    path is part of the cache key, so it must not move between runs. Entry
    points call this from ``main()``; importable modules never do, since a
    cache turned on at import would also catch the test suite's compiles
    for described (unattached) TPUs, which cannot be read back."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class Trainer(NamedTuple):
    lm: LM  # its cfg is the (head-padded) config actually trained
    policy: ShardingPolicy
    init: Callable  # seed -> (params, opt), placed by the policy
    step: Callable  # (params, opt, batch) -> (params, opt, loss, grad_norm)


def build_trainer(cfg: ModelConfig, devices, *, total_steps: int) -> Trainer:
    """Model, sharding and the jitted AdamW step on a (data=1, model=n) mesh
    over exactly ``devices``. The step donates params and optimizer state, so
    one copy of each lives on the device."""
    mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
    policy = ShardingPolicy(mesh, cfg)
    cfg = pad_heads(cfg, policy.tp_size)
    policy.cfg = cfg
    lm = LM(cfg, ep_degree=policy.tp_size, policy=policy, remat=True)
    lr = cosine_schedule(3e-4, warmup=max(total_steps // 10, 1),
                         total=max(total_steps, 100))
    # init and step emit the same shardings, so no step after the first
    # sees new input shardings and compiles again
    p_shard = policy.param_shardings(
        jax.eval_shape(lm.init, jax.random.PRNGKey(0)))
    state_shard = (p_shard, AdamWState(policy.named(P()), p_shard, p_shard))
    scalar = policy.named(P())

    @partial(jax.jit, out_shardings=state_shard)
    def init_state(key):
        params = lm.init(key)
        return params, adamw_init(params)

    @partial(jax.jit, out_shardings=(*state_shard, scalar, scalar),
             donate_argnums=(0, 1))
    def step(params, opt, batch):
        (loss, metrics), grads = jax.value_and_grad(lm.loss, has_aux=True)(
            params, batch)
        params, opt, om = adamw_update(params, grads, opt, lr=lr)
        return params, opt, loss, om["grad_norm"]

    def init(seed: int):
        return init_state(jax.random.PRNGKey(seed))

    return Trainer(lm, policy, init, step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--dry-run", action="store_true",
                    help="lower+compile the full cell (no execution)")
    ap.add_argument("--reduced", action="store_true",
                    help="run a reduced config (seq 256) on the local devices")
    ap.add_argument("--batch", type=int, default=8,
                    help="sequences per device per step")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        # delegate to the dry-run path (requires fresh process: 512 devices)
        import subprocess
        import sys

        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
               args.arch, "--shape", args.shape, "--mesh", args.mesh]
        raise SystemExit(subprocess.call(cmd, env=dict(os.environ)))

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    devices = jax.devices()
    trainer = build_trainer(cfg, devices, total_steps=args.steps)
    cfg, policy = trainer.lm.cfg, trainer.policy
    print(f"arch={cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
          f"mesh={dict(zip(policy.mesh.axis_names, policy.mesh.devices.shape))}")

    params, opt = trainer.init(0)
    ck = Checkpointer(args.ckpt_dir, keep=2)
    start = 0
    if args.resume and ck.latest_step() is not None:
        start, restored = ck.restore(
            {"params": params, "opt": opt},
            shardings={"params": policy.param_shardings(params)})
        params, opt = restored["params"], restored["opt"]
        print(f"resumed at step {start}")

    batch_size = args.batch * len(devices)
    seq = 256 if args.reduced else SHAPES[args.shape].seq_len
    pipe = DataPipeline(seed=0, batch=batch_size, seq=seq,
                        vocab=cfg.vocab_size, start_step=start,
                        sharding=policy.named(policy.batch_spec(batch_size,
                                                                seq)))
    monitor = StragglerMonitor()
    for _ in range(start, args.steps):
        step, batch = next(pipe)
        with StepTimer(monitor) as t:
            params, opt, loss, gnorm = trainer.step(params, opt, batch)
            loss.block_until_ready()
        if t.verdict != "ok":
            print(f"  [straggler] step {step}: {t.verdict}")
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss={float(loss):.4f} "
                  f"gnorm={float(gnorm):.2f}")
        if step and step % 10 == 0:
            ck.save(step, {"params": params, "opt": opt})
    ck.wait()
    pipe.close()
    print("done")


if __name__ == "__main__":
    main()
