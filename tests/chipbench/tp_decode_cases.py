"""Run the TP decode cell's generator at a small size on four host devices:
once as it is, once traced, once with each control in the all-reduce's
place, once with XLA's own collectives, and once with two members' shards
swapped in the all-gather's output. Prints one JSON object
{case: result line}.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python tests/chipbench/tp_decode_cases.py
"""

import contextlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[0:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import cells, harness  # noqa: E402
from chipbench.generators import tp_decode  # noqa: E402
from repro.comms import primitives  # noqa: E402

COORDS = [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)]
REAL = primitives.pccl_all_gather
# the configuration's shapes at a small size, all in whole tiles: two
# layers, [32, 256] all-reduces and a [32, 256] vocab shard
SMALL = {"layers": 2, "hidden": 256, "decode_batch": 32, "padded_vocab": 1024}


def rows_swapped(*a, **kw):
    """The gathered shards of members 0 and 1 in each other's place."""
    return REAL(*a, **kw)[np.array([1, 0, 2, 3])]


@contextlib.contextmanager
def planted(fault):
    primitives.pccl_all_gather = fault
    try:
        yield
    finally:
        primitives.pccl_all_gather = REAL


def main():
    cell = cells.load(ROOT, "tp4-internlm2-20b.decode-2x2")
    cell.config = dict(cell.config, **SMALL)
    devices = jax.devices()[:4]

    def run(seed, traced=False):
        return harness.run_cell(cell, devices, COORDS, seed=seed, seconds=0.2,
                                traced=traced, t0=time.perf_counter())

    out = {"sound": run(2**31 + 7), "sound_traced": run(11, traced=True)}
    for name in ("member_left_out", "fp8_cast", "xla_builtin"):
        with tp_decode.control(name):
            out[name] = run(3)
    with planted(rows_swapped):
        out["rows_swapped"] = run(5)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
