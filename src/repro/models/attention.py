"""GQA attention: training (full/causal/sliding-window) and decode (KV cache).

The jnp paths below are the reference implementations; on TPU the training
path dispatches to the Pallas flash-attention kernel
(`repro.kernels.ops.flash_attention`) when enabled. Decode attention is
written so that sharding the KV cache's *sequence* dimension across the
"model" mesh axis yields flash-decoding-style parallelism under GSPMD (the
softmax statistics and the PV products reduce over the sharded axis with
XLA-inserted collectives) — this sidesteps KV-head divisibility limits of
head-sharded decode entirely.
"""

from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import Params, _init, apply_rope, rope_tables


def attention_init(key, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int) -> Params:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": _init(kq, (d_model, num_heads * head_dim)),
        "wk": _init(kk, (d_model, num_kv_heads * head_dim)),
        "wv": _init(kv, (d_model, num_kv_heads * head_dim)),
        "wo": _init(ko, (num_heads * head_dim, d_model)),
    }


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def gqa_scores_mask(seq_q: int, seq_k: int, *, causal: bool,
                    window: int = 0, offset: int = 0):
    """[seq_q, seq_k] additive mask. `offset` = absolute position of query 0
    (so decode can reuse it). window > 0 = sliding-window attention."""
    qpos = jnp.arange(seq_q) + offset
    kpos = jnp.arange(seq_k)
    ok = jnp.ones((seq_q, seq_k), bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        ok &= kpos[None, :] > qpos[:, None] - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def attend(q, k, v, mask, *, softcap: float = 0.0):
    """q: [B,S,H,hd], k/v: [B,T,KV,hd]; returns [B,S,H,hd]. GQA via head
    grouping; softmax in f32."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    qg = q.reshape(B, S, KV, group, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    if softcap > 0.0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = scores + mask  # mask broadcasts [S,T]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, H, hd)


def _largest_divisor(n: int, cap: int) -> int:
    """Largest d <= cap with n % d == 0 (>= 1)."""
    d = min(cap, n)
    while n % d:
        d -= 1
    return max(d, 1)


def _block_geometry(S, T, window, block_q, block_kv):
    # the block must divide the sequence; prefer the largest divisor <= the
    # requested block so odd lengths degrade to smaller tiles, NEVER to one
    # full-sequence tile (which would materialize dense S x T scores —
    # measured 117 GiB/device on llava prefill before this guard)
    bq = _largest_divisor(S, block_q)
    nq = S // bq
    ctx = min(T, window + bq) if window > 0 else T
    bkv = _largest_divisor(ctx, block_kv)
    nkv = ctx // bkv
    return bq, nq, ctx, bkv, nkv


def _mask_block(qpos, kpos, causal, window):
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        ok &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        ok &= kpos[None, :] > qpos[:, None] - window
    return ok


def _pin_batch(t, policy):
    """Pin scan-carry batch sharding: without the constraint GSPMD may
    replicate accumulators inside while bodies, inflating per-device temp
    memory by the DP degree. Non-batch dims stay UNCONSTRAINED — pinning
    them to None would *replicate* them and strip the TP head sharding
    (measured 104 GiB/device on a 1-layer llava train step with None)."""
    if policy is None or policy.dp is None:
        return t
    from jax.sharding import PartitionSpec as P

    u = P.UNCONSTRAINED
    return policy.constrain(t, P(policy.dp, *([u] * (t.ndim - 1))))


def blockwise_attention(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: float = 0.0, block_q: int = 512,
                        block_kv: int = 1024, policy=None, offset=None):
    """Keyword-friendly wrapper over the custom-VJP flash core. `offset` is
    the global position of q's first row (sequence-parallel attention passes
    the device's seq-shard origin)."""
    if offset is None:
        offset = jnp.zeros((), jnp.int32)
    return _flash_core(q, k, v, offset, causal, window, softcap, block_q,
                       block_kv, policy)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(q, k, v, offset, causal: bool = True, window: int = 0,
                softcap: float = 0.0, block_q: int = 512,
                block_kv: int = 1024, policy=None):
    """Flash attention in pure jnp with a flash-style custom VJP.

    Forward: online-softmax over (block_q x block_kv) tiles — memory is one
    tile per head group instead of the full S x T matrix. Backward: probs are
    RECOMPUTED per tile (never stored), carrying O(T) dk/dv accumulators —
    naive autodiff through the tiled scan would otherwise stash every tile's
    probs and rebuild the full quadratic matrix (measured 69 GiB/device on
    llama3.2-1b train_4k; this path: ~4 GiB).

    This is the portable reference twin of the Pallas kernel
    (repro/kernels/flash_attention.py). Sliding-window attention slices
    exactly the window's KV (traced start, static size) so SWA costs
    O(S*window); the causal path masks at tile granularity (true tile
    skipping happens in the Pallas kernel — roofline accounting corrects
    analytically).
    """
    out, _ = _flash_fwd(q, k, v, offset, causal, window, softcap, block_q,
                        block_kv, policy)
    return out


def _flash_fwd(q, k, v, offset, causal, window, softcap, block_q, block_kv,
               policy):
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    bq, nq, ctx, bkv, nkv = _block_geometry(S, T, window, block_q, block_kv)
    scale = 1.0 / math.sqrt(hd)
    group = H // KV
    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, hd), 1, 0)
    # pe-poison (see _flash_bwd_vjp): under remat-in-scan the forward is
    # recomputed inside the backward, and its primal-independent tile masks
    # would be hoisted + stacked; tie positions to the primal to prevent it
    zero = (q.ravel()[0] * 0).astype(jnp.int32) + offset

    def q_block(args):
        i, qi = args
        qpos = i * bq + jnp.arange(bq) + zero
        start = jnp.maximum(offset + i * bq + bq - ctx, 0) if window > 0 else 0
        ks = lax.dynamic_slice(k, (0, start, 0, 0), (B, ctx, KV, hd))
        vs = lax.dynamic_slice(v, (0, start, 0, 0), (B, ctx, KV, hd))
        qg = qi.reshape(B, bq, KV, group, hd)

        def kv_block(carry, j):
            m, l, acc = carry
            kj = lax.dynamic_slice(ks, (0, j * bkv, 0, 0), (B, bkv, KV, hd))
            vj = lax.dynamic_slice(vs, (0, j * bkv, 0, 0), (B, bkv, KV, hd))
            s = jnp.einsum("bqkgh,btkh->bkgqt", qg, kj).astype(jnp.float32)
            s = s * scale
            if softcap > 0.0:
                s = softcap * jnp.tanh(s / softcap)
            kpos = start + j * bkv + jnp.arange(bkv)
            ok = _mask_block(qpos, kpos, causal, window)
            s = jnp.where(ok[None, None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(-1))
            m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
            p = jnp.exp(s - m_safe[..., None])
            p = jnp.where(jnp.isinf(m_new)[..., None], 0.0, p)
            corr = jnp.where(jnp.isinf(m), 0.0, jnp.exp(m - m_safe))
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p.astype(vj.dtype), vj
            ).astype(jnp.float32)
            return tuple(_pin_batch(t, policy)
                         for t in (m_new, l_new, acc_new)), None

        # zf: primal-derived zero — keeps the carries pe-"unknown" AND, under
        # shard_map, marks them varying on the manual axes (vma typing)
        zf = zero.astype(jnp.float32) * 0.0
        m0 = _pin_batch(
            jnp.full((B, KV, group, bq), -jnp.inf, jnp.float32) + zf, policy)
        l0 = _pin_batch(jnp.zeros((B, KV, group, bq), jnp.float32) + zf,
                        policy)
        a0 = _pin_batch(jnp.zeros((B, KV, group, bq, hd), jnp.float32) + zf,
                        policy)
        (m, l, acc), _ = lax.scan(kv_block, (m0, l0, a0), jnp.arange(nkv))
        out_i = acc / jnp.maximum(l, 1e-30)[..., None]
        lse_i = jnp.where(jnp.isinf(m), -jnp.inf,
                          m + jnp.log(jnp.maximum(l, 1e-30)))
        out_i = jnp.moveaxis(out_i, 3, 1).reshape(B, bq, H, hd).astype(q.dtype)
        return _pin_batch(out_i, policy), _pin_batch(lse_i, policy)

    outs, lses = lax.map(q_block, (jnp.arange(nq), qb))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, H, hd)
    return out, lses  # lses: [nq, B, KV, G, bq]


def _flash_fwd_vjp(q, k, v, offset, causal, window, softcap, block_q,
                   block_kv, policy):
    out, lse = _flash_fwd(q, k, v, offset, causal, window, softcap, block_q,
                          block_kv, policy)
    return out, (q, k, v, offset, out, lse)


def _flash_bwd_vjp(causal, window, softcap, block_q, block_kv, policy,
                   res, dout):
    q, k, v, offset, out, lse = res
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    bq, nq, ctx, bkv, nkv = _block_geometry(S, T, window, block_q, block_kv)
    scale = 1.0 / math.sqrt(hd)
    group = H // KV

    # Partial-eval poison: scan AD hoists primal-independent intermediates
    # (the iota-derived tile masks below) out of the backward pass and STACKS
    # them as per-tile residuals — a [nq, nkv, B, KV, G, bq, bkv] bool array
    # (64 GiB/device on llava train_4k). Tying the position bases to a
    # primal value keeps the masks "unknown", so they are recomputed tile-by-
    # tile inside the backward loops instead of being saved.
    zero = (jnp.min(lse) * 0.0).astype(jnp.int32) + offset

    qb = jnp.moveaxis(q.reshape(B, nq, bq, H, hd), 1, 0)
    dob = jnp.moveaxis(dout.reshape(B, nq, bq, H, hd), 1, 0)
    ob = jnp.moveaxis(out.reshape(B, nq, bq, H, hd), 1, 0)

    zf = zero.astype(jnp.float32) * 0.0
    dk0 = _pin_batch(jnp.zeros((B, T, KV, hd), jnp.float32) + zf, policy)
    dv0 = _pin_batch(jnp.zeros((B, T, KV, hd), jnp.float32) + zf, policy)

    def q_block(carry, args):
        dk_acc, dv_acc = carry
        i, qi, doi, oi, lse_i = args
        qpos = i * bq + jnp.arange(bq) + zero
        start = (jnp.maximum(zero + i * bq + bq - ctx, 0)
                 if window > 0 else 0)
        qg = qi.reshape(B, bq, KV, group, hd)
        dog = doi.reshape(B, bq, KV, group, hd)
        og = oi.reshape(B, bq, KV, group, hd)
        # D_i = rowsum(dout * out)  [B,KV,G,bq]
        Di = jnp.einsum("bqkgh,bqkgh->bkgq", dog.astype(jnp.float32),
                        og.astype(jnp.float32))
        lse_safe = jnp.where(jnp.isinf(lse_i), 0.0, lse_i)

        def kv_block(carry2, j):
            dq_i, dk_acc, dv_acc = carry2
            kj = lax.dynamic_slice(k, (0, start + j * bkv, 0, 0),
                                   (B, bkv, KV, hd))
            vj = lax.dynamic_slice(v, (0, start + j * bkv, 0, 0),
                                   (B, bkv, KV, hd))
            s_pre = jnp.einsum("bqkgh,btkh->bkgqt", qg, kj).astype(jnp.float32)
            s_pre = s_pre * scale
            if softcap > 0.0:
                tanh_s = jnp.tanh(s_pre / softcap)
                s = softcap * tanh_s
            else:
                s = s_pre
            kpos = start + j * bkv + jnp.arange(bkv)
            ok = _mask_block(qpos, kpos, causal, window)
            s = jnp.where(ok[None, None, None], s, -jnp.inf)
            p = jnp.exp(s - lse_safe[..., None])  # [B,KV,G,bq,t]
            p = jnp.where(jnp.isinf(lse_i)[..., None], 0.0, p)
            # dv_j += p^T dout_i (sum over q and group)
            dv_j = jnp.einsum("bkgqt,bqkgh->btkh", p,
                              dog.astype(jnp.float32))
            dp = jnp.einsum("bqkgh,btkh->bkgqt", dog,
                            vj).astype(jnp.float32)
            ds = p * (dp - Di[..., None])
            if softcap > 0.0:
                ds = ds * (1.0 - tanh_s * tanh_s)
            ds = ds * scale
            dq_i = dq_i + jnp.einsum("bkgqt,btkh->bqkgh", ds, kj)
            dk_j = jnp.einsum("bkgqt,bqkgh->btkh", ds, qg)
            dk_acc = lax.dynamic_update_slice(
                dk_acc,
                lax.dynamic_slice(dk_acc, (0, start + j * bkv, 0, 0),
                                  (B, bkv, KV, hd)) + dk_j,
                (0, start + j * bkv, 0, 0))
            dv_acc = lax.dynamic_update_slice(
                dv_acc,
                lax.dynamic_slice(dv_acc, (0, start + j * bkv, 0, 0),
                                  (B, bkv, KV, hd)) + dv_j,
                (0, start + j * bkv, 0, 0))
            return (_pin_batch(dq_i, policy), _pin_batch(dk_acc, policy),
                    _pin_batch(dv_acc, policy)), None

        dq0 = _pin_batch(jnp.zeros((B, bq, KV, group, hd), jnp.float32) + zf,
                         policy)
        (dq_i, dk_acc, dv_acc), _ = lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), jnp.arange(nkv))
        return (dk_acc, dv_acc), _pin_batch(dq_i, policy)

    (dk, dv), dqs = lax.scan(
        q_block, (dk0, dv0), (jnp.arange(nq), qb, dob, ob, lse))
    # dqs: [nq, B, bq, KV, G, hd] -> [B, S, H, hd]
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, S, H, hd).astype(q.dtype)
    d_offset = np.zeros((), jax.dtypes.float0)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype), d_offset


_flash_core.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


def _seq_parallel_attention(q, k, v, policy, *, causal, window, softcap):
    """Sequence-parallel flash attention via shard_map: queries stay
    seq-sharded on the TP axis; K/V are all-gathered ONCE per layer inside
    the shard (GQA keeps them small). Replaces GSPMD's per-tile resharding
    of the scan-tiled attention, which re-gathered K/V for EVERY
    (q-tile x kv-tile) pair — 11.7 TB/device/step of all-gather on
    llava-next-34b train_4k (§Perf iteration 1)."""
    from jax.sharding import PartitionSpec as P

    tp, dp = policy.tp, policy.dp
    S_loc = q.shape[1] // policy.tp_size

    def local(q_l, k_l, v_l):
        k_f = lax.all_gather(k_l, tp, axis=1, tiled=True)
        v_f = lax.all_gather(v_l, tp, axis=1, tiled=True)
        off = (lax.axis_index(tp) * S_loc).astype(jnp.int32)
        return blockwise_attention(q_l, k_f, v_f, causal=causal,
                                   window=window, softcap=softcap,
                                   policy=None, offset=off)

    spec = P(dp, tp, None, None)
    return jax.shard_map(local, mesh=policy.mesh, in_specs=(spec,) * 3,
                         out_specs=spec)(q, k, v)


def attention_train(
    p: Params,
    x: jax.Array,  # [B, S, d]
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    rotary_pct: float = 1.0,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    positions: jax.Array | None = None,
    use_flash: bool = False,
    policy=None,
) -> jax.Array:
    B, S, _ = x.shape
    q = _split_heads(x @ p["wq"].astype(x.dtype), num_heads, head_dim)
    k = _split_heads(x @ p["wk"].astype(x.dtype), num_kv_heads, head_dim)
    v = _split_heads(x @ p["wv"].astype(x.dtype), num_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(S)
    cos, sin, rot = rope_tables(positions, head_dim, rope_theta, rotary_pct)
    q = apply_rope(q, cos, sin, rot)
    k = apply_rope(k, cos, sin, rot)
    seq_parallel_ok = (
        policy is not None and policy.tp is not None and policy.tp_size > 1
        and S % policy.tp_size == 0
        and (S // policy.tp_size) % 8 == 0
        and B % policy.dp_size == 0
    )
    if use_flash:
        from repro.kernels import ops as kops

        out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                   softcap=softcap)
    elif seq_parallel_ok:
        out = _seq_parallel_attention(q, k, v, policy, causal=causal,
                                      window=window, softcap=softcap)
    elif S > 2048:
        # memory-bounded path for long contexts (32k prefill shapes)
        out = blockwise_attention(q, k, v, causal=causal, window=window,
                                  softcap=softcap, policy=policy)
    else:
        mask = gqa_scores_mask(S, S, causal=causal, window=window)
        out = attend(q, k, v, mask, softcap=softcap)
    return out.reshape(B, S, num_heads * head_dim) @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, max_seq: int, num_kv_heads: int, head_dim: int,
                  dtype=jnp.bfloat16):
    shape = (batch, max_seq, num_kv_heads, head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def attention_decode(
    p: Params,
    x: jax.Array,  # [B, 1, d] current-token activations
    cache: Params,  # {"k","v"}: [B, T, KV, hd]
    pos: jax.Array,  # [] current absolute position (same for the batch)
    *,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    rope_theta: float,
    rotary_pct: float = 1.0,
    window: int = 0,
    softcap: float = 0.0,
):
    """One decode step. Returns (out [B,1,d], new cache). With window > 0 the
    cache is a ring buffer of size `window` (positions wrap)."""
    B = x.shape[0]
    T = cache["k"].shape[1]
    q = _split_heads(x @ p["wq"].astype(x.dtype), num_heads, head_dim)
    k = _split_heads(x @ p["wk"].astype(x.dtype), num_kv_heads, head_dim)
    v = _split_heads(x @ p["wv"].astype(x.dtype), num_kv_heads, head_dim)
    posv = jnp.full((1,), pos)
    cos, sin, rot = rope_tables(posv, head_dim, rope_theta, rotary_pct)
    q = apply_rope(q, cos, sin, rot)
    k = apply_rope(k, cos, sin, rot)
    slot = (pos % T) if window > 0 else pos  # ring buffer under SWA
    ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                  (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                  (0, slot, 0, 0))
    # validity of cache positions: either absolute (no window) or ring-buffer
    kpos = jnp.arange(T)
    if window > 0:
        valid = (kpos <= pos % T) | (pos >= T)  # ring full -> everything valid
    else:
        valid = kpos <= pos
    mask = jnp.where(valid, 0.0, -jnp.inf).astype(jnp.float32)[None, :]
    out = attend(q, ck, cv, mask, softcap=softcap).astype(x.dtype)
    out = out.reshape(B, 1, num_heads * head_dim) @ p["wo"].astype(x.dtype)
    return out, {"k": ck, "v": cv}


# ---------------------------------------------------------------------------
# Cross attention (whisper decoder) — no cache mutation, encoder KV is static
# ---------------------------------------------------------------------------

def cross_attention(
    p: Params,
    x: jax.Array,  # [B, S, d] decoder activations
    enc_kv: tuple[jax.Array, jax.Array],  # ([B,T,KV,hd], [B,T,KV,hd])
    *,
    num_heads: int,
    head_dim: int,
    softcap: float = 0.0,
) -> jax.Array:
    B, S, _ = x.shape
    q = _split_heads(x @ p["wq"].astype(x.dtype), num_heads, head_dim)
    k, v = enc_kv
    T = k.shape[1]
    mask = jnp.zeros((S, T), jnp.float32)
    out = attend(q, k, v, mask, softcap=softcap)
    return out.reshape(B, S, num_heads * head_dim) @ p["wo"].astype(x.dtype)


def encode_cross_kv(p: Params, enc_out: jax.Array, *, num_kv_heads: int,
                    head_dim: int):
    k = _split_heads(enc_out @ p["wk"].astype(enc_out.dtype), num_kv_heads, head_dim)
    v = _split_heads(enc_out @ p["wv"].astype(enc_out.dtype), num_kv_heads, head_dim)
    return k, v
