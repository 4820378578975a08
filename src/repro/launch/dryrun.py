import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: .lower().compile() every (architecture x input-shape x
mesh) cell on the production meshes, and extract the roofline terms from the
compiled artifacts.

The lines above MUST stay the first statements of this module — jax locks
the device count at first init, and the dry-run needs 512 placeholder host
devices to build the (2, 16, 16) production mesh. The CPU platform is forced
so that on a TPU host the placeholder mesh never takes the chips. (Do not
import this module from tests/benches: they must see 1 device.)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b
  PYTHONPATH=src python -m repro.launch.dryrun --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --out results/dryrun.json

Each cell records: compile ok, per-device memory stats, per-device HLO FLOPs
and bytes (cost_analysis), and per-collective byte counts parsed from the
compiled HLO (all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute) — the inputs to EXPERIMENTS.md §Dry-run and §Roofline.
Results are cached incrementally: re-runs skip completed cells.
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402


from repro.configs import REGISTRY, SHAPES, get_config, shape_applicable  # noqa: E402
from repro.launch.hlo_cost import analyze as hlo_analyze  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_bundle  # noqa: E402

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(shape_str: str) -> int:
    """'f32[128,256]' -> byte count (scalar '[]' -> element bytes)."""
    m = _SHAPE_RE.match(shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def collective_bytes_by_kind(hlo_text: str) -> dict[str, dict]:
    """Sum result-shape bytes of every collective op in the compiled HLO.

    Works on post-SPMD-partitioning HLO, where shapes are per-device. Counts
    each op once (per-device traffic). `-start` variants are counted;
    matching `-done` ops are skipped to avoid double counting.
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLL_KINDS}
    for line in hlo_text.splitlines():
        s = line.strip()
        # e.g.:  %ag = bf16[4,128]{1,0} all-gather(...), replica_groups=...
        m = re.search(
            r"=\s+([a-z0-9]+\[[\d,]*\](?:\{[^}]*\})?|\([^)]*\))\s+"
            r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
            r"collective-permute)(-start|-done)?\(", s)
        if not m:
            continue
        shape_str, kind, phase = m.groups()
        if phase == "-done":
            continue
        if shape_str.startswith("("):  # tuple shape: sum elements
            nbytes = sum(_shape_bytes(p.strip())
                         for p in shape_str[1:-1].split(",") if "[" in p)
        else:
            nbytes = _shape_bytes(shape_str)
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return out


# ---------------------------------------------------------------------------
# per-cell dry run
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str, mesh) -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name}
    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec
    try:
        bundle = build_bundle(arch, shape_name, mesh)
        lowered = bundle.lower()
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        colls = collective_bytes_by_kind(hlo)
        # loop-aware totals (XLA's flat cost_analysis counts while bodies
        # once; scan-over-layers programs need the hierarchical model)
        deep = hlo_analyze(hlo)

        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "code_bytes": ma.generated_code_size_in_bytes,
            },
            flops=deep.flops,
            bytes_accessed=deep.bytes,
            collective_bytes=deep.collective_bytes,
            collective_counts=deep.collective_counts,
            xla_flat_flops=cost.get("flops", 0.0),
            xla_flat_bytes=cost.get("bytes accessed", 0.0),
            flat_collectives=colls,
            params=cfg.param_count(),
            active_params=cfg.active_param_count(),
            padded_heads=bundle.cfg.num_heads,
            orig_heads=cfg.num_heads,
        )
    except Exception as e:  # noqa: BLE001 — record, don't abort the sweep
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, help="one shape (default: all)")
    ap.add_argument("--mesh", default=None, choices=[None, "pod", "multipod"],
                    help="default: both")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--force", action="store_true", help="recompute cached cells")
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results: dict[str, dict] = {}
    if os.path.exists(args.out) and not args.force:
        with open(args.out) as f:
            results = json.load(f)

    meshes = {}
    if args.mesh in (None, "pod"):
        meshes["pod"] = make_production_mesh(multi_pod=False)
    if args.mesh in (None, "multipod"):
        meshes["multipod"] = make_production_mesh(multi_pod=True)

    archs = [args.arch] if args.arch else sorted(REGISTRY)
    shapes = [args.shape] if args.shape else list(SHAPES)

    failures = 0
    for mesh_name, mesh in meshes.items():
        for arch in archs:
            for shape_name in shapes:
                key = f"{arch}|{shape_name}|{mesh_name}"
                if key in results and results[key].get("status") in (
                        "ok", "skipped") and not args.force:
                    continue
                print(f"[dryrun] {key} ...", flush=True)
                rec = run_cell(arch, shape_name, mesh_name, mesh)
                results[key] = rec
                status = rec["status"]
                extra = ""
                if status == "ok":
                    extra = (f"compile={rec['compile_s']}s "
                             f"flops/dev={rec['flops']:.3g} "
                             f"temp={rec['memory']['temp_bytes']/2**30:.2f}GiB")
                elif status == "error":
                    extra = rec["error"][:160]
                    failures += 1
                print(f"[dryrun] {key}: {status} {extra}", flush=True)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    print(f"[dryrun] done; {failures} failures; results in {args.out}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
