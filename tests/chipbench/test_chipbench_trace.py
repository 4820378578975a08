"""The trace reduction's arithmetic, on a hand-built trace with known
answers and on a small trace recorded on one TPU v5e chip (its numbers are
only inputs here: no device metric comes from it)."""

from pathlib import Path

import pytest

from chipbench import trace

DATA = Path(__file__).parent / "data"


def _events(line_name, events, ids):
    body = "".join(
        f"    events {{ metadata_id: {ids[name]} offset_ps: {int(a * 1000)} "
        f"duration_ps: {int((b - a) * 1000)} }}\n" for name, a, b in events)
    return (f"  lines {{\n    id: {len(ids)}\n    name: \"{line_name}\"\n"
            f"    timestamp_ns: 1000\n{body}  }}\n")


def _plane(pid, name, lines):
    names = sorted({n for _, evs in lines for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f"  event_metadata {{ key: {i} value {{ id: {i} name: \"{n}\" }} }}\n"
                   for n, i in ids.items())
    body = "".join(_events(ln, evs, ids) for ln, evs in lines)
    return f"planes {{\n  id: {pid}\n  name: \"{name}\"\n{body}{meta}}}\n"


def _synthetic():
    """Window [0, 100] ns (shifted by the lines' 1000 ns timestamp). Chip 0
    runs two steps of jit_ddp_step with permutes, other ops, an op outside
    the program and one that starts before the window; chip 1 is busy
    throughout."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", [("python", [
        ("chipbench.window", 0, 100), ("chipbench.step", 10, 50),
        ("chipbench.step", 55, 95), ("unrelated", 0, 100)])])
    chip0 = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [("jit_ddp_step(1)", 10, 50), ("jit_ddp_step(1)", 55, 95)]),
        ("XLA Ops", [("early", -5, 3), ("collective-permute-start.1", 12, 20),
                     ("fusion.2", 18, 30), ("collective-permute-done.1", 40, 45),
                     ("collective-permute-start.1", 60, 70), ("fusion.2", 70, 90),
                     ("copy.3", 96, 99)])])
    chip1 = _plane(3, "/device:TPU:1", [("XLA Ops", [("fusion.9", 0, 100)])])
    return ProfileData.from_text_proto(host + chip0 + chip1)


def test_union_gaps_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.length([(0, 2), (1, 3), (10, 11)]) == 4
    assert trace.clip([(-5, 3), (4, 9), (12, 20)], 0, 10) == [(0, 3), (4, 9)]
    assert trace.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert trace.gaps([], 0, 10) == [(0, 10)]


def test_summary_of_synthetic_trace():
    s = trace.summarize(trace.Trace.from_profile(_synthetic()))
    ns = 1e-9
    assert s.chips == 2
    assert s.window_s == pytest.approx(100 * ns)
    # chip 0 busy: [0,3] [12,30] [40,45] [60,90] [96,99] = 59; chip 1: 100
    assert s.busy_s == pytest.approx(79.5 * ns)
    assert s.idle_share == pytest.approx(0.205)
    assert s.module_runs == {"jit_ddp_step": 1.0}
    # chip 0 permutes: 8 + 5 + 10 = 23; all ops in the program 23 + 30 = 53
    assert s.permute_s["jit_ddp_step"] == pytest.approx(23 / 2 * ns)
    assert s.other_s["jit_ddp_step"] == pytest.approx(30 / 2 * ns)
    assert s.op_s["fusion.9"] == pytest.approx(50 * ns)
    assert s.op_s["early"] == pytest.approx(1.5 * ns)
    gaps = [(name, round(sec / ns, 6)) for name, sec in s.idle_gaps]
    assert gaps == [("no host span", 15), ("step", 10), ("no host span", 9),
                    ("step", 6), ("no host span", 1)]
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.9", pytest.approx(50 * ns)]
    assert len(b["idle_gaps"]) == 5


def test_window_span_is_required():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(
        _plane(1, "/host:CPU", [("python", [("chipbench.step", 0, 1)])]))
    with pytest.raises(ValueError, match="chipbench.window"):
        trace.summarize(trace.Trace.from_profile(pd))


def test_recorded_tpu_trace():
    """A tiny plan-cell window (16 plans of 2x2 slices of a 4x4 pod) as the
    profiler wrote it on one v5e chip, recorded while each plan's receive
    count was staged right after it: each count is one run of
    ``jit_receives`` holding one fused op, and the host spans label the idle
    gaps between them."""
    t = trace.Trace.from_file(DATA / "tiny_plan.xplane.pb")
    s = trace.summarize(t)
    assert s.chips == 1
    stages = [x for x in t.spans if x.name == "chipbench.stage"]
    assert s.module_runs == {"jit_receives": len(stages)} and len(stages) == 16
    assert s.op_s == {"convert_reduce_fusion": pytest.approx(s.busy_s)}
    assert s.permute_s == {"jit_receives": 0.0}
    assert s.other_s["jit_receives"] == pytest.approx(s.busy_s)
    assert 0 < s.busy_s < s.window_s
    lo, hi = t.window()
    (chip,) = t.chips.values()
    idle = trace.gaps([(o.start, o.end) for o in chip.ops], lo, hi)
    assert sum(b - a for a, b in idle) * 1e-9 == pytest.approx(s.window_s - s.busy_s)
    assert len(idle) == 17  # before, between and after the 16 staged counts
    assert [name for name, _ in s.idle_gaps] == ["synthesis"] * 10


def test_loop_op_is_left_out_of_the_op_times():
    """An op that encloses others on its line (a while loop) adds to busy
    time once, and only its children are named in the op times. In a
    program's runs, its time with no leaf op running is the loops' own, and
    counts neither as permute nor as other time."""
    from jax.profiler import ProfileData

    host = _plane(1, "/host:CPU", [("python", [("chipbench.window", 0, 100)])])
    chip = _plane(2, "/device:TPU:0", [
        ("XLA Modules", [("jit_ddp_step(1)", 8, 75)]),
        ("XLA Ops", [("while.1", 10, 50), ("fusion.1", 12, 30),
                     ("collective-permute-done.1", 30, 45), ("copy.2", 60, 70)])])
    s = trace.summarize(trace.Trace.from_profile(
        ProfileData.from_text_proto(host + chip)))
    ns = 1e-9
    assert s.busy_s == pytest.approx(50 * ns)
    assert s.op_s == {"fusion.1": pytest.approx(18 * ns),
                      "collective-permute-done.1": pytest.approx(15 * ns),
                      "copy.2": pytest.approx(10 * ns)}
    # while.1 runs [10, 50]; its leaf ops cover [12, 45]
    assert s.permute_s == {"jit_ddp_step": pytest.approx(15 * ns)}
    assert s.other_s == {"jit_ddp_step": pytest.approx(28 * ns)}
    assert s.loop_s == {"jit_ddp_step": pytest.approx(7 * ns)}


def test_recorded_tpu_ddp_trace():
    """A tiny DDP window (14 steps of two small buckets) as the profiler
    wrote it on the 2x2 v5e host: every step's program splits into
    collective-permute time and the rest, within the chips' busy time."""
    t = trace.Trace.from_file(DATA / "tiny_ddp.xplane.pb")
    s = trace.summarize(t)
    steps = [x for x in t.spans if x.name == "chipbench.step"]
    assert s.chips == 4 and len(steps) == 14
    assert set(s.module_runs) == {"jit_ddp_step"}
    assert 0 < s.module_runs["jit_ddp_step"] <= len(steps)
    p, o = s.permute_s["jit_ddp_step"], s.other_s["jit_ddp_step"]
    loop = s.loop_s["jit_ddp_step"]
    assert p > 0 and o > 0 and loop >= 0
    assert p + o + loop <= s.busy_s < s.window_s
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s)
    assert any(trace.is_permute(name) for name in s.op_s)
    assert {name for name, _ in s.idle_gaps} <= {"step", trace.NO_SPAN}
