"""Columnar plan-representation benchmarks (fig_plan_*): the ISSUE-7 gate.

Two row families on composed (three-level All-Reduce) plans:

* ``fig_plan_stitch_<n>`` — cold hierarchical synthesis wall (stitching
  through ``TransferColumns.concat`` + one lexsort) plus differential
  micro-benchmarks of the schedule kernels against the pre-columnar
  per-object implementations, rebuilt inline: Python ``sorted`` over
  ``Transfer`` objects vs ``np.lexsort``, and per-object validator
  ingestion (one ``fromiter`` per field over attribute access) vs direct
  column views. ``plan_bytes`` (peak in-memory schedule footprint) is
  deterministic and gated; ``mem_ratio`` reports the object-path multiple.
* ``fig_plan_store_<n>`` — npz persistence: save wall, on-disk bytes
  (deterministic, gated), and mmap-load vs parse-load wall. The mmap load
  reads only zip metadata, so ``load_speedup`` grows with plan size.

The 2048-NPU rows (``--full``) are the acceptance row: sort+ingest speedup
>= 5x and/or object/columnar memory ratio >= 4x.
"""

from __future__ import annotations

import os
import sys
import tempfile
from operator import attrgetter

import numpy as np

from benchmarks.common import Row, timed
from repro.core import (
    AlgorithmRegistry,
    CollectiveRequest,
    SynthesisEngine,
    Transfer,
    load_plan_npz,
    save_plan_npz,
    topology_fingerprint,
)
from repro.topology import three_level

_SORT_KEY = attrgetter("start", "chunk", "link")


def _object_path_sort(objs: list[Transfer]) -> list[Transfer]:
    """The pre-columnar canonicalization: sort Transfer objects."""
    return sorted(objs, key=_SORT_KEY)


def _object_path_ingest(objs: list[Transfer]):
    """The pre-columnar bulk-validator ingestion: one fromiter per field
    over per-object attribute access."""
    n = len(objs)
    return (
        np.fromiter((t.chunk for t in objs), np.int64, n),
        np.fromiter((t.link for t in objs), np.int64, n),
        np.fromiter((t.src for t in objs), np.int64, n),
        np.fromiter((t.dst for t in objs), np.int64, n),
        np.fromiter((t.start for t in objs), np.float64, n),
        np.fromiter((t.end for t in objs), np.float64, n),
        np.fromiter((t.reduce for t in objs), np.bool_, n),
    )


def _object_path_bytes(n: int) -> int:
    """Deterministic footprint of the pre-columnar schedule: n Transfer
    objects (plus their two uncached float payloads) and the list's
    pointer array."""
    proto = Transfer(0, 0, 0, 1, 0.0, 1.0)
    return n * (sys.getsizeof(proto) + 2 * sys.getsizeof(1.0) + 8)


def _rows_for(topo, n: int) -> list[Row]:
    reg = AlgorithmRegistry()
    eng = SynthesisEngine(topo, registry=reg)
    alg, synth_us = timed(eng.collective,
                          CollectiveRequest("all_reduce", topo.npus))
    _, val_us = timed(alg.validate, "bulk")
    cols = alg.columns
    nt = len(cols)

    # shuffle once; both sort paths canonicalize the same permuted schedule
    rng = np.random.default_rng(0)
    order = rng.permutation(nt)
    shuffled = cols.take(order)
    objs = list(alg.transfers)
    shuffled_objs = [objs[i] for i in order.tolist()]

    _, sort_cols_us = timed(shuffled.sorted_schedule)
    _, sort_objs_us = timed(_object_path_sort, shuffled_objs)
    _, ingest_cols_us = timed(
        lambda c: (c.chunk, c.link, c.src, c.dst, c.start, c.end, c.reduce),
        cols)
    _, ingest_objs_us = timed(_object_path_ingest, objs)

    plan_bytes = alg.plan_nbytes
    obj_bytes = _object_path_bytes(nt)
    rows = [Row(
        f"fig_plan_stitch_{n}", synth_us,
        f"npus={n};transfers={nt};makespan={alg.makespan};"
        f"plan_bytes={plan_bytes};mem_ratio={obj_bytes / plan_bytes:.2f};"
        f"sort_speedup={sort_objs_us / max(sort_cols_us, 1e-9):.1f};"
        f"ingest_speedup={ingest_objs_us / max(ingest_cols_us, 1e-9):.1f};"
        f"validate_s={val_us / 1e6:.2f};misses={reg.stats.misses}",
    )]

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "plan.npz")
        fp = topology_fingerprint(topo)
        _, save_us = timed(save_plan_npz, path, alg, fp)
        disk_bytes = os.path.getsize(path)
        loaded, load_mmap_us = timed(load_plan_npz, path, topo)
        _, load_parse_us = timed(load_plan_npz, path, topo, use_mmap=False)
        assert loaded.num_transfers == nt
        rows.append(Row(
            f"fig_plan_store_{n}", save_us,
            f"npus={n};transfers={nt};disk_bytes={disk_bytes};"
            f"load_mmap_us={load_mmap_us:.0f};"
            f"load_parse_us={load_parse_us:.0f};"
            f"load_speedup={load_parse_us / max(load_mmap_us, 1e-9):.1f}",
        ))
    return rows


def run(full: bool = False) -> list[Row]:
    sizes = [(4, 4, 4)]  # 64 NPUs, quick
    if full:
        sizes += [(8, 8, 8), (16, 16, 8)]  # 512, 2048 NPUs
    rows: list[Row] = []
    for pods, racks, k in sizes:
        topo = three_level(pods, racks, k, unit_links=True)
        rows.extend(_rows_for(topo, pods * racks * k))
    return rows
