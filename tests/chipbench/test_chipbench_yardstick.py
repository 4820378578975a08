"""The benchmark's fixed yardstick: peaks, least bytes, the request unit,
and BENCHMARK.json's own consistency."""

import json
import re

import pytest

from chipbench import peaks, units
from chipbench.device import ROOT, npu_devices, seed_key

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_peaks_of_v5e_and_unknown_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bytes_per_s"] == 200e9  # 1,600 Gbit/s
    assert "cloud.google.com/tpu/docs/v5e" in p["source"]
    with pytest.raises(ValueError, match="no peaks"):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("kind,expect", [
    ("all_reduce", 2 * 3 / 4 * 1024), ("all_gather", 3 * 1024),
    ("reduce_scatter", 3 / 4 * 1024), ("all_to_all", 3 / 4 * 1024)])
def test_least_bytes_per_kind(kind, expect):
    assert peaks.least_bytes(kind, 1024, 4) == pytest.approx(expect)
    assert peaks.least_bytes(kind, 1024, 1) == 0.0


def test_least_seconds_of_a_25_mib_bucket():
    s = peaks.least_seconds("all_reduce", 25 << 20, 4, "TPU v5 lite")
    assert s == pytest.approx(1.5 * (25 << 20) / 200e9)
    with pytest.raises(ValueError):
        peaks.least_bytes("broadcast", 1, 4)


@pytest.mark.parametrize("kind,n,mib", [
    ("all_gather", 32, 32.0), ("all_reduce", 32, 1.0),
    ("reduce_scatter", 16, 2.0), ("all_to_all", 4, 8.0)])
def test_request_mib(kind, n, mib):
    assert units.request_mib(kind, 32 << 20, n) == mib


def test_4x8_all_reduce_of_32_mib_plans_and_validates():
    """A 4x8 slice of a 16x16 v5e pod, 32 MiB of input per member, through
    the one conversion to the request's unit."""
    from repro.core.engine import SynthesisEngine
    from repro.core.registry import AlgorithmRegistry
    from repro.core.request import CollectiveRequest
    from repro.topology import tpu_v5e_pod

    group = tuple((2 + i) * 16 + 4 + j for i in range(4) for j in range(8))
    req = CollectiveRequest("all_reduce", group=group, pipelined=True,
                            bytes=units.request_mib("all_reduce", 32 << 20, 32))
    alg = SynthesisEngine(tpu_v5e_pod(16, 16),
                          registry=AlgorithmRegistry()).collective(req)
    alg.validate()


def test_npu_placement_by_chip_coordinates():
    devs = ["d0", "d1", "d2", "d3"]
    coords = [(1, 1, 0), (0, 0, 0), (1, 0, 0), (0, 1, 0)]
    assert npu_devices(devs, coords) == ["d1", "d2", "d3", "d0"]
    with pytest.raises(RuntimeError, match="2x2"):
        npu_devices(devs, [(0, 0, 0)] * 4)


def test_seed_keeps_high_bits():
    import jax

    a, b = seed_key(5), seed_key(5 + (1 << 32))
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
    with pytest.raises(ValueError):
        seed_key(-1)


def test_benchmark_json_is_consistent():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = set()
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert all(NAME.match(k) for k in c["reduced"])
        names.add(c["name"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        mine = lambda ms: {m["name"] for m in ms  # noqa: E731
                           if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in mine(BENCH["end_to_end"])
        assert len(mine(BENCH["end_to_end"])) >= 2 and mine(BENCH["per_layer"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                               "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and NAME.match(m["name"])
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", []):
            moved = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]]
            assert w in moved[0].get("workloads", [w])
