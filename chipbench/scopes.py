"""Attribute a profiler trace to PCCL's own stages: device ops by the
``pccl.*`` named scope that issued them, and device idle time by the host
span open over it.

Each device op's event metadata in the ``.xplane.pb`` carries the op's
name stack (the ``tf_op`` stat, e.g. ``jit(ddp_step)/shard_map/while/body/
closed_call/pccl.update/dynamic_update_slice:``); a fused op carries its
root instruction's. JAX's ``ProfileData`` does not expose these stats, so
``op_scopes`` reads them from the file's protobuf wire format. An op the
compiler made without metadata (a relayout loop, a copy) takes the scope
of its neighbours in the program's ``HloModuleProto``, which the trace's
``/host:metadata`` plane carries (``hlo_scopes``), under a label of its
own: ``<scope> (inferred)``. The host plane holds the program's ``pccl.*`` spans (``repro.tracing``, inside
``recording()``) beside the harness's ``chipbench.*`` spans, on the clock
of the device planes.

    python chipbench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs one traced window of a cell on the chips with the program's spans
recorded, and prints as its last line one JSON object: the step program's
leaf-op time by scope, the idle time by host span, the per-request span
times from the in-memory recorder, and the program's counters.
"""

from __future__ import annotations

import heapq
import sys
from bisect import bisect_right
from pathlib import Path

if __name__ == "__main__":  # run as a script: import chipbench and repro
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[0] = str(_ROOT)
    sys.path.insert(1, str(_ROOT / "src"))

from chipbench.device import ROOT  # noqa: E402
from chipbench.trace import (DEVICE_PLANE, HOST_PLANE, NO_SPAN,  # noqa: E402
                             SPAN_PREFIX, WINDOW_SPAN, Op, Trace, gaps,
                             is_permute, length, module_key, op_name)

SCOPE_PREFIX = "pccl."
HOST_PREFIXES = (SCOPE_PREFIX, SPAN_PREFIX)
PERMUTE = "permute"
NO_SCOPE = "outside pccl scopes"  # a name stack with no pccl.* scope
NO_STACK = "no name stack"  # an op the compiler made without metadata
INFERRED = " (inferred)"  # suffix of a scope taken from an op's neighbours
HLO_PLANE = "/host:metadata"
# instructions whose name stack says nothing of the work around them
NOT_DONORS = frozenset({"constant", "parameter"})
# the step program's leaf ops, other than collective-permutes, by metric
METRIC_SCOPES = {"place_ms": ("pccl.place",),
                 "receive_ms": ("pccl.send", "pccl.receive"),
                 "update_ms": ("pccl.update",),
                 "gather_ms": ("pccl.gather",)}
# the host spans each per-request metric reads, and whether it takes their
# self time (their duration less their child spans') or their whole time
REQUEST_SPANS = {"search_ms": ("pccl.search", False),
                 "registry_ms": ("pccl.synthesize", True),
                 "validate_ms": ("pccl.validate", False),
                 "plan_self_ms": ("pccl.plan", True)}


def innermost_scope(stack: str | None) -> str:
    """The last ``pccl.*`` component of a name stack; ``NO_SCOPE`` for a
    stack without one, ``NO_STACK`` for none."""
    if not stack:
        return NO_STACK
    found = [c for c in stack.split("/") if c.startswith(SCOPE_PREFIX)]
    return found[-1].rstrip(":") if found else NO_SCOPE


# -- the protobuf wire format, as far as XSpace's metadata needs it ------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, memoryviews
    for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_entry(buf) -> tuple[int, memoryview]:
    key, value = 0, memoryview(b"")
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _stat(buf, stat_names: dict[int, str]):
    """(stat name id, value) of an XStat; a reference resolves to the
    referenced stat's name, as the profiler interns repeated strings."""
    mid, value = 0, None
    for f, v in _fields(buf):
        if f == 1:
            mid = v
        elif f in (3, 4):
            value = v
        elif f in (5, 6):
            value = bytes(v).decode(errors="replace")
        elif f == 7:
            value = stat_names.get(v)
    return mid, value


def op_scopes(data: bytes, module: str) -> dict[int, dict[str, str]]:
    """For each TPU plane (by chip index), every op of ``module``'s programs
    by instruction name, mapped to its ``innermost_scope``; an op without
    a name stack to the scope ``hlo_scopes`` infers for it."""
    protos, memo = module_hlo(data, module), {}

    def inferred(pid: str) -> dict[str, str]:
        if pid not in memo:
            memo[pid] = hlo_scopes(protos[pid]) if pid in protos else {}
        return memo[pid]

    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                metas.append(_map_entry(v)[1])
            elif pf == 5:
                sid, meta = _map_entry(v)
                stat_names[sid] = next((bytes(x).decode() for g, x in
                                        _fields(meta) if g == 2), "")
        m = DEVICE_PLANE.match(name)
        if not m:
            continue
        ids = {v: k for k, v in stat_names.items()}
        tf_op, program = ids.get("tf_op"), ids.get("program_id")
        ops, programs = [], set()
        for meta in metas:
            md_name, stats = "", {}
            for g, v in _fields(meta):
                if g == 2:
                    md_name = bytes(v).decode(errors="replace")
                elif g == 5:
                    sid, value = _stat(v, stat_names)
                    stats[sid] = value
            if module_key(md_name) == module and md_name != module:
                programs.add(md_name[len(module) + 1:-1])
            ops.append((op_name(md_name), stats.get(program),
                        stats.get(tf_op)))
        out[int(m.group(1))] = {
            op: innermost_scope(stack) if stack
            else inferred(str(pid)).get(op, NO_STACK)
            for op, pid, stack in ops if str(pid) in programs}
    return out


def _packed(values) -> list[int]:
    """A repeated int64 field, packed or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
            continue
        i = 0
        while i < len(v):
            x, i = _varint(v, i)
            out.append(x)
    return out


def hlo_scopes(module_proto) -> dict[str, str]:
    """Every instruction of a serialized ``HloModuleProto`` by name, mapped
    to its ``innermost_scope``. An instruction without a name stack takes,
    as ``<scope> (inferred)``, the scope of the nearest instruction that
    has one and is neither a constant nor a parameter: of its nearest
    consumer, since a copy or relayout the compiler adds is made for the op
    that reads it, else of its nearest producer. A computation's root
    feeds the instructions that call it, and they feed its parameters.
    ``NO_STACK`` where none is."""
    ins = {}  # id -> (name, opcode, stack, operands, computation)
    callers: dict[int, list[int]] = {}
    roots: dict[int, int] = {}  # computation -> its root instruction
    for f, comp in _fields(module_proto):
        if f != 3:
            continue
        fields = list(_fields(comp))
        cid = next((v for g, v in fields if g == 5), None)
        roots[cid] = next((v for g, v in fields if g == 6), None)
        for g, raw in fields:
            if g != 2:
                continue
            got: dict[int, list] = {}
            for h, v in _fields(raw):
                got.setdefault(h, []).append(v)
            stack = next((bytes(v).decode(errors="replace")
                          for h, v in _fields(got[7][0]) if h == 2),
                         None) if 7 in got else None
            iid = _packed(got.get(35, [0]))[0]
            ins[iid] = (bytes(got[1][0]).decode(), bytes(got[2][0]).decode(),
                        stack, _packed(got.get(36, [])), cid)
            for c in _packed(got.get(38, [])):
                callers.setdefault(c, []).append(iid)
    users: dict[int, list[int]] = {}
    for iid, (*_, operands, _c) in ins.items():
        for o in operands:
            users.setdefault(o, []).append(iid)

    def donor(i):
        _, opcode, stack, _, _ = ins[i]
        return bool(stack) and opcode not in NOT_DONORS

    def consumers(i):  # a computation's root flows out of its callers
        return users.get(i, []) + (callers.get(ins[i][4], [])
                                   if roots.get(ins[i][4]) == i else [])

    def producers(i):  # a parameter is fed by its computation's callers
        return ins[i][3] + (callers.get(ins[i][4], [])
                            if ins[i][1] == "parameter" else [])

    def nearest(start, step):
        seen, level = {start}, [start]
        while level:
            nxt = []
            for y in (y for x in level for y in step(x)):
                if y in ins and y not in seen:
                    seen.add(y)
                    nxt.append(y)
            found = next((y for y in nxt if donor(y)), None)
            if found is not None:
                return found
            level = nxt
        return None

    def infer(i) -> str:
        found = nearest(i, consumers)
        if found is None:
            found = nearest(i, producers)
        return NO_STACK if found is None else (
            innermost_scope(ins[found][2]) + INFERRED)

    return {name: innermost_scope(stack) if stack else infer(i)
            for i, (name, _, stack, _, _) in ins.items()}


def module_hlo(data: bytes, module: str) -> dict[str, memoryview]:
    """The serialized ``HloModuleProto`` of each program of ``module`` in
    the trace's ``HLO_PLANE``, by program id."""
    out = {}
    for f, plane in _fields(memoryview(data)):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if next((bytes(v).decode() for g, v in fields if g == 2), "") != HLO_PLANE:
            continue
        for g, v in fields:
            if g != 4:
                continue
            _, meta = _map_entry(v)
            name, blob = "", None
            for h, x in _fields(meta):
                if h == 2:
                    name = bytes(x).decode(errors="replace")
                elif h == 5:
                    blob = next((y for k, y in _fields(x) if k == 6), blob)
            if module_key(name) == module and name != module and blob is not None:
                proto = next((y for k, y in _fields(blob) if k == 1), None)
                if proto is not None:
                    out[name[len(module) + 1:-1]] = proto
    return out


# -- the reductions -------------------------------------------------------

def scope_split(trace: Trace, scopes: dict[int, dict[str, str]],
                module: str) -> dict[str, float]:
    """Seconds per run of ``module`` in its leaf ops (the rule of
    ``trace.summarize``): ``PERMUTE`` for collective-permutes, every other
    op under its ``innermost_scope`` (``NO_STACK`` for an op ``scopes``
    does not name). Whole executions inside the window only, summed over
    chips."""
    lo, hi = trace.window()
    totals: dict[str, float] = {}
    runs = 0
    for chip_id, chip in trace.chips.items():
        named = scopes.get(chip_id, {})
        ops = sorted((o for o in chip.ops if min(o.end, hi) > max(o.start, lo)),
                     key=lambda o: (o.start, -o.end))
        leaf = [o for o, nxt in zip(ops, ops[1:] + [None])
                if nxt is None or nxt.start >= o.end or nxt.end > o.end]
        execs = sorted((m.start, m.end) for m in chip.modules
                       if module_key(m.name) == module and lo <= m.start
                       and m.end <= hi)
        starts = [a for a, _ in execs]
        runs += len(execs)
        by_label: dict[str, list[tuple[float, float]]] = {}
        for o in leaf:
            i = bisect_right(starts, o.start) - 1
            if i < 0 or o.end > execs[i][1]:
                continue
            label = (PERMUTE if is_permute(o.name)
                     else named.get(o.name, NO_STACK))
            by_label.setdefault(label, []).append((o.start, o.end))
        for label, spans in by_label.items():
            totals[label] = totals.get(label, 0.0) + length(spans)
    return {k: v / runs * 1e-9 for k, v in totals.items()} if runs else {}


def scope_metrics(split: dict[str, float]) -> dict[str, float]:
    """Milliseconds per run of each ``METRIC_SCOPES`` metric (its scopes'
    ops, with those inferred for them), and as ``unscoped_ms`` the rest of
    the non-permute leaf-op time."""
    out = {m: sum(split.get(s, 0.0) + split.get(s + INFERRED, 0.0)
                  for s in names) * 1e3
           for m, names in METRIC_SCOPES.items()}
    named = {s + x for names in METRIC_SCOPES.values() for s in names
             for x in ("", INFERRED)}
    out["unscoped_ms"] = sum(v for k, v in split.items()
                             if k != PERMUTE and k not in named) * 1e3
    return out


def host_spans(profile) -> list[Op]:
    """The host plane's ``pccl.*`` and ``chipbench.*`` spans."""
    return [Op(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in profile.planes if plane.name == HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith(HOST_PREFIXES)]


def idle_by_span(trace: Trace, spans: list[Op]) -> dict[str, float]:
    """Seconds of the first chip's idle time in the window, split by time
    among the innermost host spans open over it (the latest started, as
    ``trace.label_of`` takes it), other than the window; ``NO_SPAN`` where
    none is."""
    lo, hi = trace.window()
    chip = trace.chips.get(min(trace.chips)) if trace.chips else None
    idle = gaps([(o.start, o.end) for o in chip.ops] if chip else [], lo, hi)
    inner = sorted((s for s in spans if s.name != WINDOW_SPAN
                    and s.end > lo and s.start < hi), key=lambda s: s.start)
    cuts = sorted({t for s in inner for t in (s.start, s.end)}
                  | {t for g in idle for t in g})
    out: dict[str, float] = {}
    heap: list[tuple[float, int]] = []
    k = g = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(inner) and inner[k].start <= a:
            heapq.heappush(heap, (-inner[k].start, k))
            k += 1
        while heap and inner[heap[0][1]].end <= a:
            heapq.heappop(heap)
        while g < len(idle) and idle[g][1] <= a:
            g += 1
        if g < len(idle) and idle[g][0] <= a:
            label = inner[heap[0][1]].name if heap else NO_SPAN
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return out


def request_times(recorder, requests: int) -> dict[str, float]:
    """Milliseconds per request of each span ``REQUEST_SPANS`` names."""
    out = {}
    for metric, (name, own) in REQUEST_SPANS.items():
        ns = recorder.self_ns(name) if own else recorder.total_ns(name)
        out[metric] = ns / requests * 1e-6
    return out


# -- the traced window, as a script ---------------------------------------

def main(argv=None) -> None:
    import argparse
    import json
    import shutil
    import tempfile

    import jax
    from jax.profiler import ProfileData

    from chipbench import cells, device, harness, trace
    from repro import tracing

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", help="copy the .xplane.pb to this path")
    args = ap.parse_args(argv)

    cell = cells.load(ROOT, args.workload)
    devices = device.tpu_devices(cell.chips)
    device.enable_compile_cache()
    job = cell.generator().setup(
        cell, devices, [getattr(d, "coords", None) for d in devices], args.seed)
    log_dir = tempfile.mkdtemp(prefix="chipbench-scopes-")
    try:
        jax.profiler.start_trace(log_dir,
                                 profiler_options=harness._profile_options())
        try:
            with tracing.recording() as rec, \
                    jax.profiler.TraceAnnotation(WINDOW_SPAN):
                win = job.window(args.seconds, True)
        finally:
            jax.profiler.stop_trace()
        path = trace.find_xplane(log_dir)
        if args.keep:
            shutil.copy(path, args.keep)
        data = path.read_bytes()
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    for note in win.notes:
        harness.say(note)
    profile = ProfileData.from_serialized_xspace(data)
    t = Trace.from_profile(profile)
    summary = trace.summarize(t)
    out = {"device": device.device_line(devices)["kind"],
           "attempted": win.attempted, "window_s": summary.window_s,
           "busy_s": summary.busy_s,
           "idle_by_span_s": idle_by_span(t, host_spans(profile)),
           "counters": tracing.counters()}
    module = job.context.get("step_module")
    runs = summary.module_runs.get(module)
    if runs:
        split = scope_split(t, op_scopes(data, module), module)
        out.update(scope_metrics(split),
                   scopes_ms={k: v * 1e3 for k, v in split.items()},
                   permute_ms=summary.permute_s[module] / runs * 1e3,
                   copy_ms=summary.other_s[module] / runs * 1e3)
    rec = job.context.get("recorder", rec)  # a generator's own recorder
    if rec.spans and win.attempted:
        out["request_ms"] = request_times(rec, win.attempted)
        out["request_spans"] = len({s.request_id for s in rec.spans})
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
