"""One run of one cell: set-up, the measured window, the reference check,
and the result line.

A generator module (``chipbench/generators/<generator>.py``) provides
``setup(cell, devices, coords, seed) -> job``. The job has

* ``window(seconds, traced) -> Window``: the closed loop of the traffic,
  with nothing compiled inside it;
* ``release()``: drops the program's state once the window is over;
* ``check() -> {name: (value, limit)}``: the plain reference, run after
  the window; the run is correct when every value is at most its limit;
* ``context``: the counts and host spans the per-layer readers take.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
from dataclasses import dataclass, field
from types import SimpleNamespace

from chipbench import device, trace


@dataclass
class Window:
    start: float  # time.perf_counter() at the first timed step
    end: float
    attempted: int
    failed: int
    metrics: dict[str, float]  # end-to-end, from the host clock
    notes: list[str] = field(default_factory=list)


def say(msg: str) -> None:
    print(f"chipbench: {msg}", flush=True)


def _profile_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # harness spans only, not every call
    opts.host_tracer_level = 2
    return opts


def run_cell(cell, devices, coords, *, seed: int, seconds: float,
             traced: bool, t0: float) -> dict:
    """Run ``cell`` once on ``devices`` and return the result line."""
    import jax

    job = cell.generator().setup(cell, devices, coords, seed)
    if traced:  # a mix may trace a shorter window, to keep the trace small
        seconds = min(seconds, cell.traffic.get("traced_seconds", seconds))
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    try:
        with device.CompileCounter() as compiles:
            if traced:
                jax.profiler.start_trace(log_dir,
                                         profiler_options=_profile_options())
            try:
                with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
                    win = job.window(seconds, traced)
            finally:
                if traced:
                    jax.profiler.stop_trace()
        for note in win.notes:
            say(note)
        say(f"compilations in the timed window: {compiles.compiles} "
            f"(tracings: {compiles.traces})")
        dev = device.device_line(devices)
        job.release()
        checks = job.check()
        summary = (trace.summarize(trace.Trace.from_file(
            trace.find_xplane(log_dir))) if traced else None)
        for key, runs in (summary.module_runs if summary else {}).items():
            say(f"device ms per run of {key}: permutes "
                f"{summary.permute_s[key] / runs * 1e3!r}, other leaf ops "
                f"{summary.other_s[key] / runs * 1e3!r}, loops alone "
                f"{summary.loop_s[key] / runs * 1e3!r}")
    finally:
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)

    result = {"correct": all(v <= lim for v, lim in checks.values())
              and win.failed == 0,
              "attempted": win.attempted, "failed": win.failed}
    if traced:
        ctx = SimpleNamespace(trace=summary, device_kind=dev["kind"],
                              **job.context)
        metrics = {}
        for m in cell.per_layer:
            value = cell.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        dev.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result.update(metrics=metrics, device=dev,
                      breakdown=summary.breakdown())
    else:
        values = dict(win.metrics, setup_s=win.start - t0)
        missing = [m["name"] for m in cell.end_to_end if m["name"] not in values]
        if missing:
            raise RuntimeError(f"{cell.name}: generator measured no {missing}")
        result.update(metrics={m["name"]: {"value": values[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=dev)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def emit(result: dict) -> None:
    """The result as the last line of stdout, and each number compared
    beside its limit as the last lines of stderr."""
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
