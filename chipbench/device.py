"""The chip: which devices a run has, where chips sit, and what compiles.

Importing this module touches no device. ``tpu_devices`` is the only
place a run looks for its chips, and it refuses any platform but a TPU.
"""

from __future__ import annotations

import os
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def enable_compile_cache() -> str:
    """JAX's persistent compile cache at ``JAX_COMPILATION_CACHE_DIR`` if
    set, else at the fixed ``<checkout>/.jax_cache`` (the path is part of
    the cache's key, so it never moves). Every program is cached, however
    short its compile, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; exits non-zero on any other
    platform or with fewer chips. There is no CPU fallback."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: JAX found no accelerator: {e}")
    d = devices[0]
    if d.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, but JAX found platform "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"found {len(devices)}")
    return devices[:chips]


def device_line(devices) -> dict:
    """``device`` of the result line: platform, kind and count as JAX
    reports them, and the peak bytes in use on the fullest chip."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": memory_peak(devices)}


def memory_peak(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def npu_devices(devices, coords) -> list:
    """The device at each ``mesh2d(2, 2)`` NPU: NPU r*2 + c sits at chip
    coordinates (x=c, y=r), so mesh2d's links are exactly the pairs of chips
    one ICI hop apart."""
    at = {tuple(c[:2]): d for d, c in zip(devices, coords)}
    if sorted(at) != [(0, 0), (0, 1), (1, 0), (1, 1)]:
        raise RuntimeError(f"not a 2x2 grid of chips: coords {coords}")
    return [at[(n % 2, n // 2)] for n in range(4)]


def seed_key(seed: int):
    """A JAX PRNG key that keeps every bit of a seed of up to 64 bits."""
    import jax

    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class CompileCounter:
    """Counts JAX's tracings and backend compiles while it is entered, from
    the events JAX records for each (a persistent-cache hit is a tracing
    without a backend compile)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = 0
        self.compiles = 0
        self._on = False

    def _listen(self, name, _secs, **_kw):
        if not self._on:
            return
        if name == self.TRACE:
            self.traces += 1
        elif name == self.COMPILE:
            self.compiles += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        self._on = False
        jax.monitoring.unregister_event_duration_listener(self._listen)
        return False
