"""PCCL's stages in a trace (``chipbench.scopes``) and the warm-registry
plan cell: the reductions on hand-built traces with known answers and on
a small trace recorded on the 2x2 v5e host (its numbers are only inputs
here), and the cells on the host CPU."""

import json
import os
import re
import subprocess
import sys
from itertools import islice
from pathlib import Path

import pytest

from chipbench import cells, harness, scopes, trace
from chipbench.device import ROOT
from chipbench.generators import plans, shared_registry

DATA = Path(__file__).parent / "data"
NS = 1e-9
STEP = "jit_ddp_step"


def _line(lid, name, events, ids):
    body = "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {int(a * 1000)} "
        f"duration_ps: {int((b - a) * 1000)} }}\n" for n, a, b in events)
    return (f"  lines {{\n    id: {lid}\n    name: \"{name}\"\n"
            f"    timestamp_ns: 1000\n{body}  }}\n")


def _plane(pid, name, lines, stats=None, interned=()):
    """A plane whose event metadata carries ``stats[event name]``, a dict of
    stat name to a string, an int or ``("ref", text)``, a string the
    profiler interned as a stat of its own."""
    stats = stats or {}
    names = sorted({n for _, evs in lines for n, _, _ in evs} | set(stats))
    ids = {n: i + 1 for i, n in enumerate(names)}
    stat_ids = {n: i + 1 for i, n in enumerate(
        ["tf_op", "program_id", *interned])}

    def stat(key, value):
        if isinstance(value, tuple):
            return (f"stats {{ metadata_id: {stat_ids[key]} "
                    f"ref_value: {stat_ids[value[1]]} }}")
        kind = "uint64_value" if isinstance(value, int) else "str_value"
        return f"stats {{ metadata_id: {stat_ids[key]} {kind}: {json.dumps(value)} }}"

    meta = "".join(
        f"  event_metadata {{ key: {i} value {{ id: {i} name: \"{n}\" "
        + " ".join(stat(k, v) for k, v in stats.get(n, {}).items())
        + " } }\n" for n, i in ids.items())
    smeta = "".join(f"  stat_metadata {{ key: {i} value {{ id: {i} "
                    f"name: {json.dumps(n)} }} }}\n" for n, i in stat_ids.items())
    body = "".join(_line(i + 1, ln, evs, ids) for i, (ln, evs) in enumerate(lines))
    return f"planes {{\n  id: {pid}\n  name: \"{name}\"\n{body}{meta}{smeta}}}\n"


def _ddp_xspace(extra: str = ""):
    """Window [0, 100] ns, with the text-format planes ``extra`` after it.
    Chip 0 runs jit_ddp_step (program 7) over
    [10, 50] and [55, 95]: a permute, ops under pccl.update and pccl.send,
    an op with no name stack, one outside every pccl scope, a loop op
    enclosing two of them, and an op after the program's runs."""
    from jax.profiler import ProfileData

    def op(stack):
        return {"tf_op": stack, "program_id": 7}

    stats = {
        f"{STEP}(7)": {},
        "collective-permute-start.1": op("jit(ddp_step)/pccl.permute/ppermute"),
        "fusion.2": op("jit(ddp_step)/while/body/pccl.update/dynamic_update_slice:"),
        "dus.3": {"tf_op": ("ref", "jit(ddp_step)/pccl.gather/pccl.send/dynamic_slice:"),
                  "program_id": 7},
        "copy.4": {"program_id": 7},
        "slice.5": op("jit(ddp_step)/while/body/dynamic_slice"),
        "while.1": op("jit(ddp_step)/pccl.gather/while"),
        "late.6": op("jit(ddp_step)/pccl.place/add"),
        "fusion.9": {"tf_op": "jit(other)/pccl.place/add", "program_id": 8},
    }
    host = _plane(1, "/host:CPU", [("python", [("chipbench.window", 0, 100),
                                               ("chipbench.step", 10, 50)])])
    chip = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [(f"{STEP}(7)", 10, 50), (f"{STEP}(7)", 55, 95)]),
        ("XLA Ops", [("collective-permute-start.1", 12, 20), ("fusion.2", 20, 30),
                     ("dus.3", 30, 38), ("copy.4", 40, 45), ("while.1", 56, 94),
                     ("slice.5", 60, 70), ("fusion.2", 70, 90),
                     ("late.6", 96, 99)])],
        stats, interned=["jit(ddp_step)/pccl.gather/pccl.send/dynamic_slice:"])
    return ProfileData.text_proto_to_serialized_xspace(host + chip + extra)


def test_innermost_scope():
    assert scopes.innermost_scope(
        "jit(f)/shard_map/pccl.gather/closed_call/pccl.update/dus:") == "pccl.update"
    assert scopes.innermost_scope("jit(f)/while/body/dynamic_slice") == scopes.NO_SCOPE
    assert scopes.innermost_scope("") == scopes.NO_STACK
    assert scopes.innermost_scope(None) == scopes.NO_STACK


def test_op_scopes_read_the_event_metadata():
    got = scopes.op_scopes(_ddp_xspace(), STEP)
    assert got == {0: {"collective-permute-start.1": "pccl.permute",
                       "fusion.2": "pccl.update", "dus.3": "pccl.send",
                       "copy.4": scopes.NO_STACK, "slice.5": scopes.NO_SCOPE,
                       "while.1": "pccl.gather", "late.6": "pccl.place"}}
    assert scopes.op_scopes(_ddp_xspace(), "jit_other") == {0: {}}


def _pb(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) pairs."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def _hlo(name, opcode, iid, stack=None, operands=(), calls=()):
    return _pb((1, name), (2, opcode),
               *([(7, _pb((1, opcode), (2, stack)))] if stack else []),
               (35, iid), *[(36, o) for o in operands],
               *[(38, c) for c in calls])


def _module(*computations) -> bytes:
    """An HloModuleProto of (id, root id, instructions) computations."""
    return _pb((1, STEP), *[(3, _pb((1, f"c{cid}"), *[(2, i) for i in ins],
                                    (5, cid), (6, root)))
                            for cid, root, ins in computations])


def test_hlo_scopes_infer_from_neighbours():
    """A metadata-less op takes its nearest consumer's scope (through a
    loop's root and the loop's users), else its nearest producer's;
    constants and parameters lend none."""
    place = "jit(ddp_step)/while/body/pccl_all_reduce/pccl.place/slice"
    module = _module(
        (1, 5, [_hlo("param.1", "parameter", 1, "xs[0]"),
                _hlo("slice.1", "fusion", 2, "jit(ddp_step)/while/body/slice",
                     [1]),
                _hlo("while.1", "while", 3, None, [2], [2]),
                _hlo("gte.1", "get-tuple-element", 4, None, [3]),
                _hlo("fusion.2", "fusion", 5, place, [4]),
                _hlo("copy.1", "copy", 6, None, [1]),
                _hlo("constant.1", "constant", 7, "jit(ddp_step)/pccl.update/c"),
                _hlo("copy.3", "copy", 8, None, [7, 2])]),
        (2, 11, [_hlo("param.b", "parameter", 10),
                 _hlo("dynamic-update-slice.1", "dynamic-update-slice", 11,
                      None, [10])]))
    got = scopes.hlo_scopes(memoryview(module))
    inferred = "pccl.place" + scopes.INFERRED
    assert got == {"param.1": scopes.NO_SCOPE, "slice.1": scopes.NO_SCOPE,
                   "while.1": inferred, "gte.1": inferred,
                   "fusion.2": "pccl.place", "copy.1": scopes.NO_STACK,
                   "constant.1": "pccl.update",
                   "copy.3": scopes.NO_SCOPE + scopes.INFERRED,
                   "param.b": inferred, "dynamic-update-slice.1": inferred}


def test_op_scopes_infer_an_op_without_a_name_stack():
    """With the step's HLO in the trace's metadata plane, the op without a
    name stack (``copy.4``) takes the scope of the op that reads it, and
    counts in that scope's metric under a label of its own."""
    from jax.profiler import ProfileData

    module = _module((1, 2, [
        _hlo("copy.4", "copy", 1),
        _hlo("fusion.2", "fusion", 2, "jit(ddp_step)/pccl.update/dus", [1])]))
    blob = "".join(f"\\{b:03o}" for b in _pb((1, module)))
    meta = ("planes {\n  id: 3\n  name: \"/host:metadata\"\n"
            f"  event_metadata {{ key: 1 value {{ id: 1 name: \"{STEP}(7)\" "
            f"stats {{ metadata_id: 1 bytes_value: \"{blob}\" }} }} }}\n"
            "  stat_metadata { key: 1 value { id: 1 name: \"Hlo Proto\" } }\n}\n")
    data = _ddp_xspace(meta)
    got = scopes.op_scopes(data, STEP)[0]
    assert got["copy.4"] == "pccl.update" + scopes.INFERRED
    assert scopes.op_scopes(data, "jit_other") == {0: {}}
    t = trace.Trace.from_profile(ProfileData.from_serialized_xspace(data))
    m = scopes.scope_metrics(scopes.scope_split(t, {0: got}, STEP))
    assert m["update_ms"] == pytest.approx(17.5e-6)  # (10 + 20 + 5) / 2 ns
    assert m["unscoped_ms"] == pytest.approx(5e-6)


def test_scope_split_of_a_synthetic_trace():
    """Per run (2 runs): permute 8/2; pccl.update (10 + 20)/2; pccl.send
    8/2; no stack 5/2; outside 10/2. The loop op and the op after the
    runs count nowhere; the non-permute labels sum to ``other_s``."""
    from jax.profiler import ProfileData

    data = _ddp_xspace()
    t = trace.Trace.from_profile(ProfileData.from_serialized_xspace(data))
    split = scopes.scope_split(t, scopes.op_scopes(data, STEP), STEP)
    want = {scopes.PERMUTE: 4, "pccl.update": 15, "pccl.send": 4,
            scopes.NO_STACK: 2.5, scopes.NO_SCOPE: 5}
    assert split == {k: pytest.approx(v * NS) for k, v in want.items()}
    s = trace.summarize(t)
    runs = s.module_runs[STEP]
    assert sum(v for k, v in split.items() if k != scopes.PERMUTE) == \
        pytest.approx(s.other_s[STEP] / runs)
    assert split[scopes.PERMUTE] == pytest.approx(s.permute_s[STEP] / runs)
    m = scopes.scope_metrics(split)
    assert m == {"place_ms": 0.0, "receive_ms": pytest.approx(4e-6),
                 "update_ms": pytest.approx(15e-6), "gather_ms": 0.0,
                 "unscoped_ms": pytest.approx(7.5e-6)}


def test_idle_time_splits_by_the_innermost_host_span():
    """Idle [0, 10], [20, 60], [70, 100]; under nested plan spans, a stage
    span, and none: each stretch goes to the span open over it, by time."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", [("python", [
        ("chipbench.window", 0, 100), ("chipbench.plan", 5, 50),
        ("pccl.plan", 6, 45), ("pccl.search", 8, 30),
        ("chipbench.stage", 65, 95), ("unrelated", 0, 100)])])
    chip = _plane(2, "/device:TPU:0", [("XLA Ops", [("a.1", 10, 20),
                                                    ("b.2", 60, 70)])])
    profile = ProfileData.from_text_proto(host + chip)
    t = trace.Trace.from_profile(profile)
    spans = scopes.host_spans(profile)
    assert "unrelated" not in {s.name for s in spans}
    got = scopes.idle_by_span(t, spans)
    want = {trace.NO_SPAN: 20, "chipbench.plan": 6, "pccl.plan": 17,
            "pccl.search": 12, "chipbench.stage": 25}
    assert got == {k: pytest.approx(v * NS) for k, v in want.items()}
    s = trace.summarize(t)
    assert sum(got.values()) == pytest.approx(s.window_s - s.busy_s)


def test_recorded_trace_without_scopes_is_all_outside():
    """The 2x2 trace recorded before the executor had scopes: every leaf op
    of the step is a permute, outside every pccl scope, or one the compiler
    made without a name stack, which its neighbours put outside too."""
    data = (DATA / "tiny_ddp.xplane.pb").read_bytes()
    t = trace.Trace.from_file(DATA / "tiny_ddp.xplane.pb")
    split = scopes.scope_split(t, scopes.op_scopes(data, STEP), STEP)
    inferred = scopes.NO_SCOPE + scopes.INFERRED
    assert set(split) == {scopes.PERMUTE, scopes.NO_SCOPE, inferred}
    assert scopes.scope_metrics(split)["unscoped_ms"] > 0
    s = trace.summarize(t)
    assert split[scopes.NO_SCOPE] + split[inferred] == pytest.approx(
        s.other_s[STEP] / s.module_runs[STEP])


def test_recorded_trace_with_scopes():
    """A tiny DDP window recorded on the 2x2 v5e host with the executor's
    scopes: every PCCL stage shows, and the stages with the rest make up
    the step's non-permute leaf-op time."""
    path = DATA / "tiny_ddp_scopes.xplane.pb"
    data = path.read_bytes()
    t = trace.Trace.from_file(path)
    named = scopes.op_scopes(data, STEP)
    assert len(named) == 4
    split = scopes.scope_split(t, named, STEP)
    assert {"pccl.place", "pccl.send", "pccl.receive", "pccl.update",
            "pccl.gather", scopes.PERMUTE} <= set(split)
    s = trace.summarize(t)
    runs = s.module_runs[STEP]
    m = scopes.scope_metrics(split)
    assert all(m[k] > 0 for k in scopes.METRIC_SCOPES)
    assert sum(m.values()) * 1e-3 == pytest.approx(s.other_s[STEP] / runs,
                                                   rel=1e-6)
    assert split[scopes.PERMUTE] == pytest.approx(s.permute_s[STEP] / runs)
    # every collective-permute is tagged pccl.permute on every chip
    assert all(v == "pccl.permute" for ops in named.values()
               for k, v in ops.items() if trace.is_permute(k))


TINY_CONFIG = {"fabric": {"generator": "tpu_v5e_pod", "args": [4, 4]},
               "guarantee": "exact collective result on every member",
               "reduced": {}, "assumed": []}


def _tiny_reuse_cell():
    cell = cells.load(ROOT, "pg-slices-v5e-256.reuse")
    cell.config = TINY_CONFIG
    cell.traffic = dict(cell.traffic, classes=[[[2, 2]], [[2, 4], [4, 2]]])
    return cell


def _window(cell, seed, traced=True, seconds=0.5):
    """Set-up and one window of ``cell`` on the host CPU, with the
    program's caches emptied first: a (slice, offset, kind) another test
    planned would be a cache hit."""
    import jax

    from repro.comms import clear_plan_cache, primitives

    primitives._PROGRAM_CACHE.clear()
    clear_plan_cache()
    job = cell.generator().setup(cell, jax.devices()[:1], None, seed)
    return job, job.window(seconds, traced)


@pytest.mark.parametrize("traced", [0, 1])
def test_reuse_cell_runs_past_the_device_check(tmp_path, traced):
    """The entry point end to end on the host CPU, with only its look for a
    TPU stood in for: correct, and every window request a registry hit
    that no program cache serves."""
    script = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "from chipbench import device, run\n"
        "device.tpu_devices = lambda chips: jax.devices()[:chips]\n"
        "run.main(['--workload', 'pg-slices-v5e-256.reuse', '--seed',\n"
        f"          str(2**31 + 7), '--seconds', '1', '--trace', '{traced}'])\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"] and r["device"]["platform"] == "cpu"
    n = r["attempted"]
    assert n > 0
    assert f"registry in the window: {n} hits, 0 misses" in p.stdout
    counted = re.search(r"counters in the window: (.*)", p.stdout).group(1)
    assert f"program_cache.miss {n}" in counted
    assert "program_cache.hit" not in counted
    assert "compilations in the timed window: 0" in p.stdout
    if traced:
        assert set(r["metrics"]) == {"synth_ms", "lower_ms", "validate_ms",
                                     "plan_self_ms"}
        assert all(m["value"] > 0 for m in r["metrics"].values())
    else:
        assert list(r["metrics"]) == ["plan_ms", "plan_ms.p95", "setup_s"]


def test_scopes_script_reports_the_reuse_window_spans(tmp_path):
    """``chipbench/scopes.py`` on the host CPU reads the spans of the
    recorder the reuse generator opens inside its window."""
    script = (
        "import sys, jax\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        "from chipbench import device, scopes\n"
        "device.tpu_devices = lambda chips: jax.devices()[:chips]\n"
        "scopes.main(['--workload', 'pg-slices-v5e-256.reuse', '--seed',\n"
        "             str(2**31 + 3), '--seconds', '0.5'])\n")
    p = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=300, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu",
                                JAX_COMPILATION_CACHE_DIR=str(tmp_path)))
    assert p.returncode == 0, p.stderr[-4000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["request_spans"] == r["attempted"] > 0
    assert set(r["request_ms"]) == set(scopes.REQUEST_SPANS)
    assert r["request_ms"]["search_ms"] == 0
    assert all(r["request_ms"][m] > 0 for m in ("registry_ms", "validate_ms",
                                                 "plan_self_ms"))


def test_reuse_sequence_never_repeats_a_slice_within_3060_requests():
    """The warm-up list is ``plans``'s; after it no (slice, offset, kind)
    comes again within 3,060 requests, over three passes of every cycle."""
    cfg = json.loads((ROOT / "chipbench/configs/pg-slices-v5e-256.json").read_text())
    traffic = json.loads((ROOT / "chipbench/traffic/reuse.json").read_text())
    warm, window = shared_registry.requests(cfg, traffic, 2**31 + 9)
    assert warm == plans.requests(cfg, traffic, 2**31 + 9)[0]
    seq = list(islice(window, 833 * 12))
    last, nearest = {}, len(seq)
    for i, (kind, _, group) in enumerate(warm + seq):
        if (kind, group) in last:
            nearest = min(nearest, i - last[(kind, group)])
        last[(kind, group)] = i
    assert nearest > 3060
    assert len(last) == 4 * (256 + 256 + 256 + 256)  # every slice taken
    for b in range(0, len(seq), 12):  # each block holds every (class, kind)
        assert len({(k, max(s) * min(s)) for k, s, _ in seq[b:b + 12]}) == 12


def test_reuse_window_spans_hold_no_search():
    """On the real pod every window request is a registry hit that misses
    the plan cache: no search, and one request of spans each."""
    from repro import tracing

    before = tracing.counters()
    job, win = _window(cells.load(ROOT, "pg-slices-v5e-256.reuse"), 5)
    rec = job.context["recorder"]
    names = {s.name for s in rec.spans}
    assert names == {"pccl.plan", "pccl.synthesize", "pccl.validate",
                     "pccl.translate", "pccl.buffers"}
    roots = [s for s in rec.spans if s.parent_id is None]
    assert len(roots) == win.attempted
    after = tracing.counters()
    assert after["plan_cache.miss"] - before.get("plan_cache.miss", 0) == \
        win.attempted + 16  # and the 16 plans of the set-up
    assert after.get("plan_cache.hit", 0) == before.get("plan_cache.hit", 0)
    ctx = type("Ctx", (), {"recorder": rec, "spans": job.spans,
                           "counters": {"requests": win.attempted}})
    got = {m: cells.load(ROOT, "pg-slices-v5e-256.reuse").reader(m)(ctx)
           for m in ("synth_ms", "validate_ms", "plan_self_ms")}
    assert all(v > 0 for v in got.values())
    assert job.check() == {"plan_mismatches": (0, 0),
                           "staged_receives_wrong": (0, 0)}


def test_reuse_cell_runs_on_a_program_without_spans(monkeypatch):
    """The cell as a program without ``repro.tracing`` runs it: the window
    records nothing and the span readers find nothing to read."""
    monkeypatch.setattr(shared_registry, "_tracing", lambda: None)
    cell = _tiny_reuse_cell()
    job, win = _window(cell, 5)
    assert win.attempted > 0 and "recorder" not in job.context
    ctx = type("Ctx", (), {"counters": {"requests": win.attempted}})
    for m in ("validate_ms", "plan_self_ms"):
        assert cell.reader(m)(ctx) is None


def test_cold_window_counts_no_cache_hit():
    """Every cold request misses every cache: the program and plan caches
    count no hit, and each request searches (a fresh registry misses)."""
    from repro import tracing

    before = tracing.counters()
    with tracing.recording() as rec:
        _, win = _window(cells.load(ROOT, "pg-slices-v5e-256.cold"), 6,
                         traced=False)
    after = tracing.counters()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("program_cache.hit", "program_cache.miss",
                       "plan_cache.hit", "plan_cache.miss")}
    n = win.attempted + 16  # and the 16 plans of the set-up
    assert win.attempted > 0
    assert delta == {"program_cache.hit": 0, "program_cache.miss": n,
                     "plan_cache.hit": 0, "plan_cache.miss": n}
    assert sum(s.name == "pccl.search" for s in rec.spans) == n


def test_reuse_cell_reference_catches_a_broken_plan(monkeypatch):
    import dataclasses

    import repro.comms

    real = repro.comms.synthesize_program

    def broken(*a, **kw):
        prog, plan = real(*a, **kw)
        return prog, dataclasses.replace(plan, rounds=plan.rounds[::2])

    monkeypatch.setattr(repro.comms, "synthesize_program", broken)
    import jax

    r = harness.run_cell(_tiny_reuse_cell(), jax.devices()[:1], None, seed=4,
                         seconds=0.5, traced=False, t0=0.0)
    assert r["correct"] is False
    assert r["checks"]["plan_mismatches"]["value"] > 0
