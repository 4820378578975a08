"""Readings that set a cell's correctness limits, on the chip.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 --seconds <s>

Runs the cell once per seed as the benchmark does (set-up, a window of
``--seconds``, the plain reference) in one process, then again with the
cell's control in the program's place, and prints one ``reading`` JSON line
per run with every number compared. The control is the reference computed
in the next lower precision (bfloat16 all-reduce) for the executed cells,
and a schedule cut short by its last round for the planning cells, which
state no precision: the guarantee it breaks is an exact result on every
member. The benchmark's own runs never run the control.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


@contextlib.contextmanager
def control(generator: str):
    """The cell's control in the program's place, for the ``with`` body."""
    if generator == "steps":
        import jax.numpy as jnp
        from jax import lax

        from repro.comms import primitives

        original = primitives.pccl_all_reduce

        def bf16_all_reduce(x, axis_name, *_a, **_kw):
            return lax.psum(x.astype(jnp.bfloat16), axis_name).astype(x.dtype)

        primitives.pccl_all_reduce = bf16_all_reduce
        try:
            yield
        finally:
            primitives.pccl_all_reduce = original
    elif generator == "plans":
        import repro.comms

        original = repro.comms.synthesize_program

        def cut_short(*a, **kw):
            prog, plan = original(*a, **kw)
            return prog, dataclasses.replace(plan, rounds=plan.rounds[:-1])

        repro.comms.synthesize_program = cut_short
        try:
            yield
        finally:
            repro.comms.synthesize_program = original
    else:
        raise ValueError(f"no control for generator {generator!r}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import cells

    cell = cells.load(ROOT, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from chipbench import device, harness

    devices = device.tpu_devices(cell.chips)
    device.enable_compile_cache()
    coords = [d.coords for d in devices]

    def run(seed, what):
        t = time.perf_counter()
        r = harness.run_cell(cell, devices, coords, seed=seed,
                             seconds=args.seconds, traced=False, t0=t)
        print("reading " + json.dumps({
            "what": what, "seed": seed, "correct": r["correct"],
            "checks": r["checks"], "metrics": r["metrics"],
            "device": r["device"]}), flush=True)

    for s in args.seeds.split(","):
        run(int(s), "program")
    seeds = [int(s) for s in args.control_seeds.split(",") if s]
    if seeds:
        with control(cell.traffic["generator"]):
            for s in seeds:
                run(s, "control")


if __name__ == "__main__":
    main()
