"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

The profiler writes one plane per TPU (``/device:TPU:<n>``) whose line
``XLA Ops`` holds every operation the chip ran and whose line
``XLA Modules`` holds every program execution, and one host plane
(``/host:CPU``) holding the ``TraceAnnotation`` spans the harness opens.
Host and device events share one clock.

All the arithmetic is here, on plain ``(start_ns, end_ns)`` intervals:
busy time is the union of a chip's operation intervals inside the traced
window, idle share is one minus busy over the window, and an idle gap is
labelled with the innermost harness span open at its midpoint.
"""

from __future__ import annotations

import re
from bisect import bisect_left, bisect_right
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
NO_SPAN = "no host span"
TOP = 10


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint ones, in order."""
    out: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def length(intervals) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


@dataclass
class Op:
    name: str
    start: float
    end: float


@dataclass
class Chip:
    ops: list[Op] = field(default_factory=list)
    modules: list[Op] = field(default_factory=list)


@dataclass
class Trace:
    """Events of one traced run: per chip, and the harness's host spans."""

    chips: dict[int, Chip]
    spans: list[Op]

    @classmethod
    def from_profile(cls, profile) -> "Trace":
        chips: dict[int, Chip] = {}
        spans: list[Op] = []
        for plane in profile.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                chip = chips.setdefault(int(m.group(1)), Chip())
                for line in plane.lines:
                    into = {OPS_LINE: chip.ops,
                            MODULES_LINE: chip.modules}.get(line.name)
                    if into is not None:
                        into.extend(_ops(line.events))
            elif plane.name == HOST_PLANE:
                for line in plane.lines:
                    spans.extend(o for o in _ops(line.events)
                                 if o.name.startswith(SPAN_PREFIX))
        return cls(chips, spans)

    @classmethod
    def from_file(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_profile(ProfileData.from_file(str(path)))

    def window(self) -> tuple[float, float]:
        """The harness's ``chipbench.window`` span: the measured window."""
        w = [s for s in self.spans if s.name == WINDOW_SPAN]
        if len(w) != 1:
            raise ValueError(f"expected one {WINDOW_SPAN} span, found {len(w)}")
        return w[0].start, w[0].end


def _ops(events):
    return [Op(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
            for e in events]


def op_name(text: str) -> str:
    """An op event's name: the TPU profiler names an op by its whole HLO
    instruction, ``%fusion.3 = f32[...] fusion(%collective-permute-done.1,
    ...)``; only the instruction's own name, before ``=``, says what it
    is."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


@dataclass
class Summary:
    """What the per-layer readers and the result line take from a trace.
    Times are seconds; per-chip quantities are means over the chips."""

    window_s: float
    busy_s: float
    chips: int
    # time per op name; an op that encloses others on its line (a while
    # loop of the program) is left out, so its children count once
    op_s: dict[str, float]
    module_runs: dict[str, float]
    # time in the named module's executions, by the ops that enclose no
    # other (the rule of op_s): in collective-permute operations, in every
    # other leaf operation, and inside a loop op while no leaf op runs
    permute_s: dict[str, float]
    other_s: dict[str, float]
    loop_s: dict[str, float]
    idle_gaps: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps[:TOP]]}


def is_permute(op_name: str) -> bool:
    return "collective-permute" in op_name


def module_key(name: str) -> str:
    """``jit_ddp_step(123)`` -> ``jit_ddp_step``: a program's name without
    the run id the profiler appends."""
    return name.split("(", 1)[0]


def summarize(trace: Trace) -> Summary:
    lo, hi = trace.window()
    win = hi - lo
    if not trace.chips:
        return Summary(win * 1e-9, 0.0, 0, {}, {}, {}, {}, {}, [])
    n = len(trace.chips)
    busy = 0.0
    op_ns: dict[str, float] = {}
    runs: dict[str, float] = {}
    perm: dict[str, float] = {}
    other: dict[str, float] = {}
    loop: dict[str, float] = {}
    for chip in trace.chips.values():
        ops = [o for o in chip.ops if min(o.end, hi) > max(o.start, lo)]
        busy += length(clip([(o.start, o.end) for o in ops], lo, hi))
        starts = sorted(ops, key=lambda o: (o.start, -o.end))
        # a loop op's first child starts inside it and ends before it does
        leaf = [nxt is None or nxt.start >= o.end or nxt.end > o.end
                for o, nxt in zip(starts, starts[1:] + [None])]
        for o, is_leaf in zip(starts, leaf):
            if is_leaf:
                op_ns[o.name] = (op_ns.get(o.name, 0.0)
                                 + min(o.end, hi) - max(o.start, lo))
        mods: dict[str, list[tuple[float, float]]] = {}
        for m in chip.modules:
            if lo <= m.start and m.end <= hi:  # whole executions only
                mods.setdefault(module_key(m.name), []).append((m.start, m.end))
        keys = [o.start for o in starts]
        for key, spans in mods.items():
            runs[key] = runs.get(key, 0.0) + len(spans)
            inside = [(o, is_leaf) for a, b in spans
                      for o, is_leaf in zip(
                          starts[bisect_left(keys, a):bisect_right(keys, b)],
                          leaf[bisect_left(keys, a):bisect_right(keys, b)])
                      if o.end <= b]
            p = length([(o.start, o.end) for o, is_leaf in inside
                        if is_leaf and is_permute(o.name)])
            a = length([(o.start, o.end) for o, is_leaf in inside if is_leaf])
            whole = length([(o.start, o.end) for o, _ in inside])
            perm[key] = perm.get(key, 0.0) + p
            other[key] = other.get(key, 0.0) + a - p
            loop[key] = loop.get(key, 0.0) + whole - a
    first = trace.chips[min(trace.chips)]
    idle = sorted(gaps([(o.start, o.end) for o in first.ops], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    labelled = [(label_of(trace.spans, a, b), (b - a) * 1e-9) for a, b in idle]
    per = lambda d: {k: v / n * 1e-9 for k, v in d.items()}  # noqa: E731
    return Summary(win * 1e-9, busy / n * 1e-9, n, per(op_ns),
                   {k: v / n for k, v in runs.items()}, per(perm), per(other),
                   per(loop), labelled)


def label_of(spans: list[Op], a: float, b: float) -> str:
    """The innermost harness span, other than the window, open at the
    midpoint of [a, b]."""
    mid = (a + b) / 2
    open_ = [s for s in spans
             if s.start <= mid <= s.end and s.name != WINDOW_SPAN]
    if not open_:
        return NO_SPAN
    return max(open_, key=lambda s: s.start).name[len(SPAN_PREFIX):]
