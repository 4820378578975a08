"""Production serving launcher: batched decode against the KV-cache path.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-20b --dry-run
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.sharding import ShardingPolicy, pad_heads
from repro.models import LM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--shape", default="decode_32k",
                    choices=["decode_32k", "long_500k"])
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod"])
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=32)
    args = ap.parse_args()

    if args.dry_run:
        import os
        import subprocess
        import sys

        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
               args.arch, "--shape", args.shape, "--mesh", args.mesh]
        raise SystemExit(subprocess.call(cmd, env=dict(os.environ)))

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    n = jax.device_count()
    mesh = make_mesh((1, n), ("data", "model"))
    policy = ShardingPolicy(mesh, cfg)
    cfg = pad_heads(cfg, policy.tp_size)
    policy.cfg = cfg
    lm = LM(cfg, ep_degree=policy.tp_size, policy=policy)
    params = lm.init(jax.random.PRNGKey(0))
    print(f"serving {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"batch={args.batch}")

    max_seq = args.new_tokens + 8
    cache = lm.decode_init(args.batch, max_seq)
    step = jax.jit(lm.decode_step)
    tokens = jnp.zeros((args.batch,), jnp.int32)
    t0 = time.time()
    for t in range(args.new_tokens):
        logits, cache = step(params, cache, tokens, jnp.asarray(t))
        tokens = jnp.argmax(logits, axis=-1)
    tokens.block_until_ready()
    dt = time.time() - t0
    print(f"decoded {args.new_tokens} tokens x {args.batch} seqs in {dt:.2f}s "
          f"({args.new_tokens * args.batch / dt:,.0f} tok/s)")


if __name__ == "__main__":
    main()
