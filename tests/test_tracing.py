"""repro.tracing: spans of the plan path, its counters, and the named
scopes that tag the executor's device ops."""

import json
import os
import subprocess
import sys
import threading
from pathlib import Path

import pytest

from repro import tracing
from repro.comms import lower_algorithm, synthesize_program
from repro.core.engine import SynthesisEngine
from repro.core.registry import AlgorithmRegistry
from repro.core.request import CollectiveRequest
from repro.topology import torus2d

PLAN_SPANS = {"pccl.plan", "pccl.synthesize", "pccl.search", "pccl.validate",
              "pccl.translate", "pccl.buffers"}


@pytest.fixture(scope="module")
def topo():
    return torus2d(4, 4)


def _request(group, kind="all_gather"):
    return CollectiveRequest(kind, group=tuple(group), bytes=1.0)


def _by_name(rec):
    out = {}
    for s in rec.spans:
        out.setdefault(s.name, []).append(s)
    return out


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` and keeps the names
    it was opened with."""

    def __init__(self):
        self.opened = []

    def __call__(self, name):
        self.opened.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler

    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    return ann


def test_off_records_nothing_and_opens_no_annotation(topo, annotations):
    assert tracing.span("pccl.plan") is tracing.span("pccl.search")
    synthesize_program(topo, _request([0, 1, 4, 5]),
                       registry=AlgorithmRegistry())
    assert annotations.opened == []
    with tracing.recording() as rec:
        pass
    assert rec.spans == []


def test_cold_plan_is_one_request_of_nested_spans(topo, annotations):
    with tracing.recording() as rec:
        synthesize_program(topo, _request([2, 3, 6, 7]),
                           registry=AlgorithmRegistry())
    spans = _by_name(rec)
    assert set(spans) == PLAN_SPANS
    assert all(len(v) == 1 for v in spans.values())
    assert sorted(annotations.opened) == sorted(PLAN_SPANS)
    (plan,), (synth,), (search,) = (spans["pccl.plan"],
                                    spans["pccl.synthesize"],
                                    spans["pccl.search"])
    assert plan.parent_id is None
    assert synth.parent_id == plan.span_id
    assert search.parent_id == synth.span_id
    for name in ("pccl.validate", "pccl.translate", "pccl.buffers"):
        assert spans[name][0].parent_id == plan.span_id
    assert {s.request_id for s in rec.spans} == {plan.request_id}
    for s in rec.spans:
        assert plan.start_ns <= s.start_ns <= s.end_ns <= plan.end_ns


def test_self_times_and_children_add_up_to_the_root(topo):
    with tracing.recording() as rec:
        synthesize_program(topo, _request([8, 9, 12, 13], "all_reduce"),
                           registry=AlgorithmRegistry())
    (root,) = [s for s in rec.spans if s.parent_id is None]
    assert sum(rec.self_ns(n) for n in PLAN_SPANS) == root.duration_ns
    children = sum(s.duration_ns for s in rec.spans
                   if s.parent_id == root.span_id)
    assert rec.self_ns("pccl.plan") + children == root.duration_ns
    assert rec.self_ns("pccl.search") == rec.total_ns("pccl.search") > 0


def test_registry_hit_has_no_search(topo):
    registry = AlgorithmRegistry()
    synthesize_program(topo, _request([0, 1, 2, 3]), registry=registry)
    with tracing.recording() as rec:
        synthesize_program(topo, _request([8, 9, 10, 11]), registry=registry)
    assert registry.stats.hits == 1
    assert set(_by_name(rec)) == PLAN_SPANS - {"pccl.search"}


def test_counters_count_cache_hits_and_misses(topo):
    registry = AlgorithmRegistry()
    before = tracing.counters()
    req = _request([1, 2, 5, 6], "reduce_scatter")
    synthesize_program(topo, req, registry=registry)
    synthesize_program(topo, req, registry=registry)
    after = tracing.counters()
    delta = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("program_cache.hit", "program_cache.miss",
                       "plan_cache.hit", "plan_cache.miss")}
    assert delta == {"program_cache.hit": 1, "program_cache.miss": 1,
                     "plan_cache.hit": 1, "plan_cache.miss": 1}


def test_lower_algorithm_is_a_plan_request(topo):
    alg = SynthesisEngine(topo, registry=AlgorithmRegistry()).collective(
        _request([0, 1, 2, 3]))
    with tracing.recording() as rec:
        lower_algorithm(alg, key="test-lower", validate=True)
    spans = _by_name(rec)
    assert set(spans) == {"pccl.plan", "pccl.validate", "pccl.translate",
                          "pccl.buffers"}
    (plan,) = spans["pccl.plan"]
    assert all(s.parent_id == plan.span_id for s in rec.spans if s is not plan)


def test_each_outermost_span_starts_a_request():
    with tracing.recording() as rec:
        for _ in range(2):
            with tracing.span("pccl.plan"):
                with tracing.span("pccl.validate"):
                    pass

        def work():
            with tracing.span("pccl.plan"):
                pass

        with tracing.span("pccl.plan"):
            # a thread starts in a fresh context: its span is a request of
            # its own, though a span is open in the thread that started it
            t = threading.Thread(target=work)
            t.start()
            t.join(timeout=30)
    assert not t.is_alive()
    roots = [s for s in rec.spans if s.parent_id is None]
    assert len(roots) == 4 and len({s.request_id for s in roots}) == 4
    for s in rec.spans:
        if s.parent_id is not None:
            (parent,) = [p for p in rec.spans if p.span_id == s.parent_id]
            assert s.request_id == parent.request_id


def test_recording_nests_and_restores():
    with tracing.recording() as outer:
        with tracing.recording() as inner:
            with tracing.span("pccl.plan"):
                pass
        with tracing.span("pccl.plan"):
            pass
    with tracing.span("pccl.plan"):
        pass
    assert len(inner.spans) == 1 and len(outer.spans) == 1


_COMPILE_KINDS = ("all_gather", "reduce_scatter", "all_reduce", "all_to_all")
_SCOPE_SCRIPT = r"""
import json, re
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.comms import primitives
from repro.core.registry import AlgorithmRegistry
from repro.core.request import CollectiveRequest
from repro.launch.sharding import MeshCollectivePlanner
from repro.topology import mesh2d

OPS = re.compile(r"= \S+ (collective-permute(?:-start|-done)?|"
                 r"dynamic-update-slice|dynamic-slice)\(")
mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
out = {}
for kind in %(kinds)r:
    planner = MeshCollectivePlanner(mesh2d(2, 2), {"x": 4},
                                    registry=AlgorithmRegistry())
    program = planner.program(kind, "x", 0, nbytes=4.0)
    spec = CollectiveRequest(kind, group=(0, 1, 2, 3))
    fn = getattr(primitives, "pccl_" + kind)
    shape = (4, 64) if kind in ("all_gather", "all_reduce") else (4, 4, 16)

    def run(xl):
        return fn(xl[0], "x", None, spec, program=program)[None]

    x = jax.ShapeDtypeStruct(shape, jnp.float32,
                             sharding=NamedSharding(mesh, P("x")))
    hlo = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=P("x"),
                                out_specs=P("x"))).lower(x).compile().as_text()
    found = [line.strip() for line in hlo.splitlines() if OPS.search(line)]
    out[kind] = {"ops": len(found), "scopes": sorted(set(
        re.findall(r'op_name="[^"]*/(pccl\.[a-z]+)/', hlo))),
        "unscoped": [line[:160] for line in found
                     if not re.search(r'op_name="[^"]*/pccl\.[a-z]+/', line)]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def compiled_scopes():
    """Each executable kind compiled on 4 host CPU devices (a child
    process: the device count is fixed when JAX starts)."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-c",
                        _SCOPE_SCRIPT % {"kinds": _COMPILE_KINDS}],
                       capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("kind", _COMPILE_KINDS)
def test_every_slice_and_permute_carries_a_pccl_scope(kind, compiled_scopes):
    """Every collective-permute, dynamic-slice and dynamic-update-slice of
    the optimized program is tagged with its stage, so that a refactor
    cannot drop a scope unnoticed."""
    got = compiled_scopes[kind]
    assert got["ops"] > 0
    assert got["unscoped"] == []
    assert {"pccl.place", "pccl.send", "pccl.permute", "pccl.receive",
            "pccl.update", "pccl.gather"} <= set(got["scopes"])
