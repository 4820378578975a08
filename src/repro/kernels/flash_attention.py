"""Flash attention as a Pallas TPU kernel.

TPU-native adaptation of FlashAttention: the (q-block x kv-block) tile walk
maps onto a sequential TPU grid (batch*heads, q_blocks, kv_blocks) with the
online-softmax state (m, l, acc) living in VMEM scratch that persists across
the innermost (kv) grid dimension. Tiles are staged HBM->VMEM by BlockSpecs;
the two tile matmuls (q@k^T and p@v) hit the MXU. Causal/sliding-window
tiles that are fully masked are skipped with `pl.when` (a real branch on
TPU — the jnp reference path cannot skip, see DESIGN.md).

GQA is expressed in the index maps: query head h reads KV head
h // (H // KV) — no KV duplication in memory.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale, causal, window, softcap, bq, bkv, nkv):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = i * bq
    k_start = j * bkv
    # tile-level skipping: causal -> tiles strictly above the diagonal;
    # window -> tiles strictly left of the window
    run = jnp.bool_(True)
    if causal:
        run &= k_start <= q_start + bq - 1
    if window > 0:
        run &= k_start + bkv - 1 > q_start - window

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # [bq, hd]
        k = k_ref[0].astype(jnp.float32)  # [bkv, hd]
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bkv]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = k_start + lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        ok = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)  # fully-masked rows
        corr = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
        l_new = l_prev * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(j == nkv - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softcap", "block_q", "block_kv",
                     "interpret"),
)
def flash_attention(
    q: jax.Array,  # [B, S, H, hd]
    k: jax.Array,  # [B, T, KV, hd]
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 512,
    block_kv: int = 512,
    interpret: bool,
) -> jax.Array:
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    group = H // KV
    bq = min(block_q, S)
    while S % bq:
        bq //= 2
    bkv = min(block_kv, T)
    while T % bkv:
        bkv //= 2
    nq, nkv = S // bq, T // bkv
    scale = 1.0 / math.sqrt(hd)

    # layout: fold batch and head into the leading grid dim
    qr = jnp.moveaxis(q, 2, 1).reshape(B * H, S, hd)
    kr = jnp.moveaxis(k, 2, 1).reshape(B * KV, T, hd)
    vr = jnp.moveaxis(v, 2, 1).reshape(B * KV, T, hd)

    grid = (B * H, nq, nkv)

    def q_index(bh, i, j):
        return (bh, i, 0)

    def kv_index(bh, i, j):
        b = bh // H
        h = bh % H
        return (b * KV + h // group, j, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=bq, bkv=bkv, nkv=nkv)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_index),
            pl.BlockSpec((1, bkv, hd), kv_index),
            pl.BlockSpec((1, bkv, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_index),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        interpret=interpret,
    )(qr, kr, vr)
    return jnp.moveaxis(out.reshape(B, H, S, hd), 1, 2)
