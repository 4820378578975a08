"""Mamba2 / SSD (state-space duality) blocks [arXiv:2405.21060].

Implements the chunked SSD algorithm: the sequence is split into chunks of
length Q; within a chunk the recurrence is evaluated in its "dual" quadratic
attention-like form (MXU-friendly), and chunk-boundary states are carried by
an O(T/Q) scan. This is the TPU-native adaptation of the CUDA scan kernels:
the quadratic intra-chunk part maps onto the MXU, the inter-chunk scan is a
cheap `lax.scan` (or the Pallas kernel in repro/kernels for the fused path).

Projections are kept as separate weight matrices (z/x/B/C/dt) rather than one
fused in_proj so tensor parallelism can shard the head-parallel pieces
(z, x, dt, A, D — all per-head) on the "model" mesh axis while the
group-shared B/C projections stay replicated. SSD is embarrassingly parallel
across heads, so TP needs no collectives inside the scan itself.

Decode maintains the recurrent state [H, P, N] directly: O(1) per token,
which is why the SSM archs run the long_500k shape.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import Params, _init


def ssd_init(key, d_model: int, *, expand: int = 2, head_dim: int = 64,
             state: int = 128, conv_width: int = 4) -> Params:
    d_inner = expand * d_model
    heads = d_inner // head_dim
    kz, kx, kB, kC, kdt, kconvx, kconvB, kconvC, kout = jax.random.split(key, 9)
    return {
        "w_z": _init(kz, (d_model, d_inner)),
        "w_x": _init(kx, (d_model, d_inner)),
        "w_B": _init(kB, (d_model, state)),
        "w_C": _init(kC, (d_model, state)),
        "w_dt": _init(kdt, (d_model, heads)),
        "conv_x": _init(kconvx, (conv_width, d_inner), scale=0.5),
        "conv_B": _init(kconvB, (conv_width, state), scale=0.5),
        "conv_C": _init(kconvC, (conv_width, state), scale=0.5),
        "dt_bias": jnp.zeros((heads,), jnp.float32),
        "A_log": jnp.zeros((heads,), jnp.float32),
        "D": jnp.ones((heads,), jnp.float32),
        "out_proj": _init(kout, (d_inner, d_model)),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv1d. x: [B, S, C]; w: [K, C]."""
    K = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(K):
        out = out + pad[:, i : i + x.shape[1], :] * w[i].astype(x.dtype)
    return out


def _ssd_chunked(xh, dt, A, Bm, Cm, chunk: int, policy=None):
    """Chunked SSD scan.

    xh: [B, S, H, P] inputs; dt: [B, S, H] step sizes (post softplus);
    A: [H] negative decay rates; Bm/Cm: [B, S, N] (single group, broadcast
    over heads). Returns [B, S, H, P].
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} not divisible by ssd chunk {Q}"
    nc = S // Q

    # per-step log decay: dA = dt * A  (A < 0)
    dA = dt * A[None, None, :]  # [B, S, H]
    x_ = (xh * dt[..., None]).reshape(Bsz, nc, Q, H, P)
    dA = dA.reshape(Bsz, nc, Q, H)
    Bc = Bm.reshape(Bsz, nc, Q, N)
    Cc = Cm.reshape(Bsz, nc, Q, N)

    csum = jnp.cumsum(dA, axis=2)  # [B, nc, Q, H]
    total = csum[:, :, -1, :]  # [B, nc, H] chunk total decay

    # ---- intra-chunk (dual quadratic form) ----
    # L[i, j] = exp(csum_i - csum_j) for i >= j. Mask before the exp: above
    # the diagonal diff > 0 can overflow to inf, and the where's zero
    # cotangent times an infinite exp derivative would make the grads NaN.
    diff = csum[:, :, :, None, :] - csum[:, :, None, :, :]  # [B,nc,Q,Q,H]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(mask[None, None, :, :, None], diff, -jnp.inf))
    scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)  # [B,nc,Q,Q]
    intra = jnp.einsum("bcij,bcijh,bcjhp->bcihp", scores, L, x_)

    # ---- chunk-boundary states ----
    # state contribution of chunk c: sum_j exp(total - csum_j) * B_j x_j
    decay_to_end = jnp.exp(total[:, :, None, :] - csum)  # [B,nc,Q,H]
    chunk_state = jnp.einsum("bcjn,bcjh,bcjhp->bchpn", Bc, decay_to_end, x_)

    def _pin(t):
        """Keep the inter-chunk state carry batch/head-sharded inside the
        while body (same GSPMD-replication hazard as blockwise attention);
        non-batch dims stay UNCONSTRAINED so TP head sharding survives."""
        if policy is None or policy.dp is None:
            return t
        from jax.sharding import PartitionSpec as Pspec

        u = Pspec.UNCONSTRAINED
        h_ax = policy.tp if t.shape[1] % max(policy.tp_size, 1) == 0 else u
        return policy.constrain(
            t, Pspec(policy.dp, h_ax, *([u] * (t.ndim - 2))))

    def step(carry, inp):
        state_prev = carry  # [B, H, P, N]
        tot, st = inp  # [B,H], [B,H,P,N]
        new = state_prev * jnp.exp(tot)[..., None, None] + st
        return _pin(new), state_prev  # emit the state *entering* the chunk

    init = _pin(jnp.zeros((Bsz, H, P, N), xh.dtype))
    _, states_in = lax.scan(
        step,
        init,
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(chunk_state, 1, 0)),
    )
    states_in = jnp.moveaxis(states_in, 0, 1)  # [B, nc, H, P, N]

    # ---- inter-chunk contribution: C_i · (decay_i * state_in) ----
    decay_from_start = jnp.exp(csum)  # [B,nc,Q,H]
    inter = jnp.einsum(
        "bcin,bcih,bchpn->bcihp", Cc, decay_from_start, states_in
    )
    y = (intra + inter).reshape(Bsz, S, H, P)
    return y


def ssd_block(
    p: Params,
    x: jax.Array,  # [B, S, d_model]
    *,
    head_dim: int,
    state: int,
    chunk: int,
    conv_width: int = 4,
    use_kernel: bool = False,
    policy=None,
) -> jax.Array:
    B, S, d_model = x.shape
    d_inner = p["out_proj"].shape[0]
    H = d_inner // head_dim

    z = x @ p["w_z"].astype(x.dtype)
    xin = jax.nn.silu(_causal_conv(x @ p["w_x"].astype(x.dtype), p["conv_x"]))
    Bm = jax.nn.silu(_causal_conv(x @ p["w_B"].astype(x.dtype), p["conv_B"]))
    Cm = jax.nn.silu(_causal_conv(x @ p["w_C"].astype(x.dtype), p["conv_C"]))
    dt_raw = x @ p["w_dt"].astype(x.dtype)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,S,H]
    A = -jnp.exp(p["A_log"])  # [H] negative
    xh = xin.reshape(B, S, H, head_dim)
    if use_kernel:
        from repro.kernels import ops as kops

        y = kops.ssd_scan(xh.astype(jnp.float32), dt, A,
                          Bm.astype(jnp.float32), Cm.astype(jnp.float32),
                          chunk=chunk)
    else:
        y = _ssd_chunked(xh.astype(jnp.float32), dt, A,
                         Bm.astype(jnp.float32), Cm.astype(jnp.float32), chunk,
                         policy=policy)
    y = y + xh.astype(jnp.float32) * p["D"][None, None, :, None]
    y = y.reshape(B, S, d_inner).astype(x.dtype)
    # gated RMSNorm (Mamba2)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * lax.rsqrt(var + 1e-5) * p["norm_scale"]).astype(x.dtype)
    return y @ p["out_proj"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Recurrent decode: O(1) per token
# ---------------------------------------------------------------------------

def init_ssm_cache(batch: int, d_inner: int, head_dim: int, state: int,
                   conv_width: int, dtype=jnp.float32):
    H = d_inner // head_dim
    return {
        "state": jnp.zeros((batch, H, head_dim, state), dtype),
        "conv_x": jnp.zeros((batch, conv_width - 1, d_inner), dtype),
        "conv_B": jnp.zeros((batch, conv_width - 1, state), dtype),
        "conv_C": jnp.zeros((batch, conv_width - 1, state), dtype),
    }


def _conv_step(cache_win: jax.Array, new: jax.Array, w: jax.Array):
    """cache_win: [B, K-1, C]; new: [B, C]; w: [K, C] -> (out [B,C], new win)."""
    win = jnp.concatenate([cache_win, new[:, None, :].astype(cache_win.dtype)],
                          axis=1)
    out = (win * w[None].astype(win.dtype)).sum(1)
    return out, win[:, 1:, :]


def ssd_decode_step(
    p: Params,
    x: jax.Array,  # [B, 1, d_model]
    cache: Params,
    *,
    head_dim: int,
    state: int,
):
    B = x.shape[0]
    d_inner = p["out_proj"].shape[0]
    H = d_inner // head_dim

    xt = x[:, 0]
    z = xt @ p["w_z"].astype(x.dtype)
    cx, new_conv_x = _conv_step(cache["conv_x"], xt @ p["w_x"].astype(x.dtype),
                                p["conv_x"])
    cB, new_conv_B = _conv_step(cache["conv_B"], xt @ p["w_B"].astype(x.dtype),
                                p["conv_B"])
    cC, new_conv_C = _conv_step(cache["conv_C"], xt @ p["w_C"].astype(x.dtype),
                                p["conv_C"])
    xin = jax.nn.silu(cx)
    Bm = jax.nn.silu(cB)
    Cm = jax.nn.silu(cC)
    dt_raw = xt @ p["w_dt"].astype(x.dtype)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # [B,H]
    A = -jnp.exp(p["A_log"])
    dA = jnp.exp(dt * A[None, :])  # [B,H]
    xh = xin.reshape(B, H, head_dim).astype(jnp.float32)
    dBx = jnp.einsum("bn,bhp->bhpn", Bm.astype(jnp.float32),
                     xh * dt[..., None])
    new_state = cache["state"] * dA[..., None, None] + dBx
    y = jnp.einsum("bhpn,bn->bhp", new_state, Cm.astype(jnp.float32))
    y = y + xh * p["D"][None, :, None]
    y = y.reshape(B, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    y = (y.astype(jnp.float32) * lax.rsqrt(var + 1e-5) * p["norm_scale"]).astype(x.dtype)
    out = (y @ p["out_proj"].astype(x.dtype))[:, None, :]
    new_cache = {"state": new_state, "conv_x": new_conv_x,
                 "conv_B": new_conv_B, "conv_C": new_conv_C}
    return out, new_cache
