"""Run the DDP cell's generator at a tiny size on four host devices: once as
it is, once per fault planted under its timed path, and once with the
bfloat16 control. Prints one JSON object {case: result line}.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/chipbench/steps_cases.py
"""

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from chipbench import calibrate, cells, harness  # noqa: E402
from repro.comms import primitives  # noqa: E402

COORDS = [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]
REAL = primitives.pccl_all_reduce


def exchange_left_out(x, *_a, **_kw):
    return x


def half_left_out(x, axis_name, *_a, **_kw):
    """The sum over half of the members, scaled up as a mean over the rest
    would be."""
    keep = lax.axis_index(axis_name) < 2
    return lax.psum(jnp.where(keep, x, 0), axis_name) * 2


def answer_altered(x, *a, **kw):
    return REAL(x, *a, **kw).at[0].add(1.0)


@contextlib.contextmanager
def planted(fault):
    primitives.pccl_all_reduce = fault
    try:
        yield
    finally:
        primitives.pccl_all_reduce = REAL


def main():
    cell = cells.load(ROOT, "ddp-mamba2-370m.ar-2x2")
    # two small buckets: 64 KiB and the 14,464-byte rest
    cell.config = dict(cell.config, parameters=20000, bucket_cap_mib=1 / 16)
    devices = jax.devices()[:4]

    def run(seed):
        return harness.run_cell(cell, devices, COORDS, seed=seed, seconds=0.2,
                                traced=False, t0=time.perf_counter())

    out = {"sound": run(2**31 + 7), "sound_traced": harness.run_cell(
        cell, devices, COORDS, seed=11, seconds=0.2, traced=True,
        t0=time.perf_counter())}
    for name, fault in [("exchange_left_out", exchange_left_out),
                        ("half_left_out", half_left_out),
                        ("answer_altered", answer_altered)]:
        with planted(fault):
            out[name] = run(3)
    with calibrate.control("steps"):
        out["control_bf16"] = run(5)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
