"""Mamba2 SSD chunked scan as a Pallas TPU kernel.

TPU-native adaptation of the Mamba2 CUDA scan: the sequential recurrence is
restructured into its "state-space dual" chunked form — per chunk, two MXU
matmuls (the intra-chunk quadratic term C@B^T masked by the decay kernel L,
and the inter-chunk C@state term) — with the [head_dim, state] chunk-boundary
state carried in VMEM scratch across the innermost (chunk) grid dimension.
There is no warp-shuffle analogue on TPU; the carry IS the VMEM scratch and
the grid's guaranteed sequential order plays the role of the CUDA block scan.

Grid: (batch, heads, num_chunks), chunks innermost. B/C projections are
group-shared (one group), so their BlockSpecs ignore the head index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, o_ref, state_scr, *,
                chunk):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)  # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)  # [Q, 1]
    dA = da_ref[0, 0].astype(jnp.float32)  # [Q, 1] = dt * A (A < 0)
    Bm = b_ref[0].astype(jnp.float32)  # [Q, N]
    Cm = c_ref[0].astype(jnp.float32)  # [Q, N]

    # prefix sums of dA as a column and as a row, by masked reductions over
    # [Q, Q] (no 1-D vectors, cumsum or transposes in the kernel body)
    ii = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA_row = jnp.sum(jnp.where(ii == jj, dA, 0.0), axis=0,
                     keepdims=True)  # [1, Q]
    csum_col = jnp.sum(jnp.where(jj <= ii, dA_row, 0.0), axis=1,
                       keepdims=True)  # [Q, 1]
    csum_row = jnp.sum(jnp.where(ii <= jj, dA, 0.0), axis=0,
                       keepdims=True)  # [1, Q]
    total = jnp.sum(dA_row, axis=1, keepdims=True)  # [1, 1]
    xdt = x * dt  # [Q, P]

    # intra-chunk: (C B^T ∘ L) @ (x*dt), L[i,j] = exp(csum_i - csum_j), i>=j
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # [Q,Q]
    L = jnp.where(ii >= jj, jnp.exp(csum_col - csum_row), 0.0)
    intra = jax.lax.dot_general(scores * L, xdt, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Q,P]

    # inter-chunk: C_i decay_i @ state_in^T  (state: [P, N])
    state = state_scr[...]
    decayed_C = Cm * jnp.exp(csum_col)  # [Q, N]
    inter = jax.lax.dot_general(decayed_C, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [Q,P]

    o_ref[0, 0] = (intra + inter).astype(o_ref.dtype)

    # state update: exp(total) * state + sum_j exp(total - csum_j) x_j B_j^T
    decay_to_end = jnp.exp(total - csum_col)  # [Q, 1]
    dstate = jax.lax.dot_general(
        xdt * decay_to_end, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # [P, N]
    state_scr[...] = state * jnp.exp(total) + dstate


@functools.partial(
    jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    xh: jax.Array,  # [B, S, H, P] (pre-scaled inputs)
    dt: jax.Array,  # [B, S, H] post-softplus step sizes
    A: jax.Array,  # [H] negative decay rates
    Bm: jax.Array,  # [B, S, N] (group-shared)
    Cm: jax.Array,  # [B, S, N]
    *,
    chunk: int = 128,
    interpret: bool,
) -> jax.Array:
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    while S % Q:
        Q //= 2
    nc = S // Q
    grid = (B, H, nc)

    # heads ahead of the sequence, so each block's last two dims are a
    # (chunk, width) tile: (Q, P) for x, (Q, 1) for the per-step scalars
    xr = jnp.moveaxis(xh, 2, 1)  # [B, H, S, P]
    dtr = jnp.moveaxis(dt, 2, 1)[..., None]  # [B, H, S, 1]
    dar = dtr * A[None, :, None, None]

    def head_block(width):
        return pl.BlockSpec((1, 1, Q, width), lambda b, h, c: (b, h, c, 0))

    seq_block = pl.BlockSpec((1, Q, N), lambda b, h, c: (b, c, 0))
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=Q),
        grid=grid,
        in_specs=[head_block(P), head_block(1), head_block(1), seq_block,
                  seq_block],
        out_specs=head_block(P),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), xh.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xr, dtr, dar, Bm, Cm)
    return jnp.moveaxis(out, 1, 2)
