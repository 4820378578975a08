"""chip_smoke.py's phases at tiny sizes on the CPU.

The script itself refuses any platform but TPU, so these tests call its
phase functions directly: the plan path on a small pod, the training step
on a reduced mamba2, and the four-chip collective comparison on four forced
host devices (in a child process, since the device count is fixed when JAX
starts) with chip coordinates supplied by the test.
"""

import importlib.util
import math
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert "cpu" in str(e.value.code)
    assert capsys.readouterr().out == ""  # no result line


def test_plan_phase_cold_then_registry_hit(chip_smoke):
    rows = chip_smoke.plan_phase(rows=3, cols=4)
    assert [(r["kind"], r["axis"]) for r in rows] == [
        (k, a) for k in chip_smoke.KINDS for a in chip_smoke.AXES]
    assert all(r["cold_s"] > 0 and r["hit_s"] > 0 and r["sends"] > 0
               for r in rows)


def test_train_phase_reduced_mamba2(chip_smoke):
    import jax

    from repro.configs import get_config

    cfg = get_config(chip_smoke.TRAIN_ARCH).reduced()
    losses = chip_smoke.train_phase(jax.devices()[:1], cfg, batch=1, seq=64,
                                    steps=2)
    assert len(losses) == 2 and all(math.isfinite(v) for v in losses)


@pytest.mark.parametrize("coords", [
    [(0, 0, 0), (1, 0, 0), (0, 1, 0), (1, 1, 0)],
    [(1, 1, 0), (0, 1, 0), (1, 0, 0), (0, 0, 0)],  # devices not in NPU order
])
def test_collectives_phase_on_four_host_devices(coords):
    code = (
        "import sys, jax; sys.path.insert(0, sys.argv[1]); import chip_smoke; "
        f"n = chip_smoke.collectives_phase(jax.devices(), {coords!r}, "
        "sizes=(256,), iters=1); print('cases', n)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.splitlines()[-1] == "cases 24"
    assert "group=diag03" in proc.stdout and "forwarders=[]" in proc.stdout


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_only_from_main(env_dir):
    """Importing the entry points leaves the cache off; turning it on keeps
    JAX_COMPILATION_CACHE_DIR when set, else the fixed <repo>/.jax_cache."""
    code = (
        "import os, sys, jax; sys.path.insert(0, sys.argv[1]); "
        "import chip_smoke; "
        "assert jax.config.jax_compilation_cache_dir == "
        "os.environ.get('JAX_COMPILATION_CACHE_DIR'); "
        "print(chip_smoke.use_compile_cache()); "
        "print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    proc = subprocess.run([sys.executable, "-c", code, str(ROOT)], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    want = env_dir or str(ROOT / ".jax_cache")
    assert proc.stdout.splitlines()[-2:] == [want, want]


def test_npu_devices_rejects_a_non_square_grid(chip_smoke):
    with pytest.raises(RuntimeError, match="2x2"):
        chip_smoke.npu_devices(list("abcd"), [(0, 0), (1, 0), (2, 0), (3, 0)])
