"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively (tests/test_tpu_compile.py compiles
both at model widths for a described v5e chip); elsewhere they run in
interpret mode (the kernel body executes as jax ops — bit-faithful to the
TPU tiling but slow), which is how the CPU test suite validates them
against the ref.py oracles. The model layer calls these through
`use_flash`/`use_kernel` flags.
"""

from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    block_q=512, block_kv=512):
    """[B,S,H,hd] x [B,T,KV,hd]^2 -> [B,S,H,hd]."""
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, interpret=not _on_tpu())


def ssd_scan(xh, dt, A, Bm, Cm, *, chunk=128):
    """Chunked SSD: [B,S,H,P] inputs -> [B,S,H,P] outputs."""
    return _ssd.ssd_scan(xh, dt, A, Bm, Cm, chunk=chunk,
                         interpret=not _on_tpu())
