"""Warm process-group planning, closed loop: the requests of the ``plans``
generator, served by one ``AlgorithmRegistry`` for the whole run.

Set-up plans every (shape, kind) once (the warm-up list of ``plans``), so
the registry holds every canonical schedule before the window, and every
window request is a registry hit at a fresh offset: canonicalize, relabel,
validate and lower. The window takes each (shape, kind)'s offsets in one
seeded cycle, which ends at the warm-up's offset and then starts again,
so an offset comes back only after all 256 of its (shape, kind): on the
16x16 pod nothing repeats within 3,060 requests, and the program cache
(128 entries) and the plan cache serve no request. Staging, the plain
reference and the check are those of ``plans``.

The window prints the registry's hits and misses and the program's
counters. In the traced run it records the program's spans
(``repro.tracing.recording``), which the ``validate_ms`` and
``plan_self_ms`` readers take per request; a program without
``repro.tracing`` records none, and those readers find nothing.
"""

from __future__ import annotations

import contextlib
from itertools import product

import numpy as np

from chipbench.generators import plans
from chipbench.harness import Window
from chipbench.units import request_mib


def requests(config: dict, traffic: dict, seed: int):
    """(warm-up requests, window requests) as ``plans.requests`` gives
    them: the same warm-up list, and blocks that hold every (class, kind)
    pair once. A (shape, kind) takes its slices in a fixed seeded cycle
    that ends at its warm-up slice, so a (shape, offset, kind) comes back
    only after every other offset of its (shape, kind)."""
    warm, _ = plans.requests(config, traffic, seed)
    pod = tuple(config["fabric"]["args"][:2])
    wrap = config.get("wraparound", False)
    rng = np.random.default_rng([seed, 1])
    classes = [[tuple(s) for s in cls] for cls in traffic["classes"]]
    cycles = {}
    for kind, shape, last in warm:
        groups = [plans.members(*shape, r, c, pod)
                  for r in range(pod[0] if wrap else pod[0] - shape[0] + 1)
                  for c in range(pod[1] if wrap else pod[1] - shape[1] + 1)]
        rest = [g for g in groups if g != last]
        cycles[(shape, kind)] = [rest[i] for i in rng.permutation(len(rest))]
        cycles[(shape, kind)].append(last)
    pairs = list(product(range(len(classes)), traffic["kinds"]))

    def window():
        taken = dict.fromkeys(cycles, 0)
        while True:  # blocks holding every (class, kind) once
            for i in rng.permutation(len(pairs)):
                ci, kind = pairs[i]
                shape = classes[ci][rng.integers(len(classes[ci]))]
                cycle = cycles[(shape, kind)]
                yield kind, shape, cycle[taken[(shape, kind)] % len(cycle)]
                taken[(shape, kind)] += 1

    return warm, window()


def _tracing():
    """``repro.tracing``, or None in a program that has none."""
    try:
        from repro import tracing
    except ImportError:
        return None
    return tracing


class Job(plans.Job):
    def __init__(self, cell, devices, coords, seed):
        from repro.core.registry import AlgorithmRegistry

        self.registry = AlgorithmRegistry()
        super().__init__(cell, devices, coords, seed)
        _, self.sequence = requests(cell.config, cell.traffic, seed)

    def _plan(self, req):
        import jax

        from repro.comms import synthesize_program
        from repro.core.request import CollectiveRequest

        kind, _shape, group = req
        with jax.profiler.TraceAnnotation(plans.PLAN_SPAN):
            creq = CollectiveRequest(
                kind, group=group, pipelined=kind == "all_reduce",
                bytes=request_mib(kind, self.payload_bytes, len(group)))
            prog, plan = synthesize_program(self.topo, creq,
                                            registry=self.registry)
        self.done.append((req, prog, plan))

    def window(self, seconds: float, traced: bool) -> Window:
        tracing = _tracing()
        stats = self.registry.stats.as_dict()
        counted = tracing.counters() if tracing else {}
        with (tracing.recording() if traced and tracing
              else contextlib.nullcontext()) as rec:
            win = super().window(seconds, traced)
        if rec is not None:
            self.context["recorder"] = rec
        now = self.registry.stats.as_dict()
        win.notes.append("registry in the window: " + ", ".join(
            f"{now[k] - stats[k]} {k}" for k in ("hits", "misses")))
        if tracing:
            win.notes.append("counters in the window: " + ", ".join(
                f"{k} {v - counted.get(k, 0)}"
                for k, v in sorted(tracing.counters().items())))
        return win


def setup(cell, devices, coords, seed) -> Job:
    return Job(cell, devices, coords, seed)
