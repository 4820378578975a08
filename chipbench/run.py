"""Run one benchmark cell on the TPU chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress lines, then as the last line of stdout one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics with ``--trace 0``, its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number compared with the plain reference beside its limit. Exits non-zero,
printing no result, without a TPU or with fewer chips than the cell needs.
"""

import time

T0 = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout's root, not this directory, heads the path: chipbench's
# modules are imported as a package and never shadow the standard library
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import cells

    cell = cells.load(ROOT, args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from chipbench import device, harness

    devices = device.tpu_devices(cell.chips)
    from repro import comms

    if Path(comms.__file__).resolve().parents[3] != ROOT:
        raise SystemExit(f"chipbench: repro was imported from {comms.__file__}, "
                         f"not from this checkout's src/")
    harness.say(f"device platform={devices[0].platform} "
                f"kind={devices[0].device_kind} count={len(devices)}")
    harness.say(f"compile cache {device.enable_compile_cache()}")
    result = harness.run_cell(cell, devices,
                              [getattr(d, "coords", None) for d in devices],
                              seed=args.seed, seconds=args.seconds,
                              traced=bool(args.trace), t0=T0)
    harness.emit(result)


if __name__ == "__main__":
    main()
