"""Per-kernel allclose sweeps: Pallas kernels (interpret mode on CPU) vs the
pure-jnp oracles in repro/kernels/ref.py, across shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.ref import flash_attention_ref, ssd_scan_ref

jax.config.update("jax_enable_x64", False)


def _qkv(rng, B, S, H, KV, hd, dtype):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, S, KV, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, S, KV, hd), jnp.float32).astype(dtype)
    return q, k, v


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("B,S,H,KV,hd", [
        (1, 128, 4, 4, 32),   # MHA
        (2, 128, 4, 2, 32),   # GQA 2:1
        (1, 256, 8, 1, 16),   # MQA
        (1, 192, 2, 2, 64),   # non-pow2 seq (block fallback)
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_shapes_causal(self, B, S, H, KV, hd, causal):
        q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, KV, hd, jnp.float32)
        got = ops.flash_attention(q, k, v, causal=causal, block_q=64,
                                  block_kv=64)
        want = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("window", [32, 96])
    def test_sliding_window(self, window):
        q, k, v = _qkv(jax.random.PRNGKey(1), 1, 256, 4, 4, 32, jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, window=window,
                                  block_q=64, block_kv=64)
        want = flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_softcap(self):
        q, k, v = _qkv(jax.random.PRNGKey(2), 1, 128, 2, 2, 32, jnp.float32)
        got = ops.flash_attention(q, k, v, causal=True, softcap=20.0,
                                  block_q=64, block_kv=64)
        want = flash_attention_ref(q, k, v, causal=True, softcap=20.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_bf16(self):
        q, k, v = _qkv(jax.random.PRNGKey(3), 1, 128, 4, 2, 32, jnp.bfloat16)
        got = ops.flash_attention(q, k, v, causal=True, block_q=64,
                                  block_kv=64)
        want = flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_block_shape_independence(self):
        q, k, v = _qkv(jax.random.PRNGKey(4), 1, 256, 2, 2, 32, jnp.float32)
        a = ops.flash_attention(q, k, v, causal=True, block_q=64, block_kv=128)
        b = ops.flash_attention(q, k, v, causal=True, block_q=128, block_kv=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


class TestSSDScanKernel:
    def _inputs(self, rng, B, S, H, P, N, dtype=jnp.float32):
        ks = jax.random.split(rng, 4)
        xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32).astype(dtype)
        dt = jax.nn.softplus(
            jax.random.normal(ks[1], (B, S, H), jnp.float32))
        A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
        Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32) * 0.5
        Cm = jax.random.normal(ks[0], (B, S, N), jnp.float32) * 0.5
        return xh, dt, A, Bm, Cm

    @pytest.mark.parametrize("B,S,H,P,N,chunk", [
        (1, 64, 2, 16, 8, 16),
        (2, 128, 4, 32, 16, 32),
        (1, 96, 2, 16, 8, 32),   # chunk fallback (96 % 32 == 0)
        (1, 64, 1, 64, 32, 64),  # single chunk
    ])
    def test_matches_recurrence(self, B, S, H, P, N, chunk):
        xh, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(0), B, S, H, P, N)
        got = ops.ssd_scan(xh, dt, A, Bm, Cm, chunk=chunk)
        want = ssd_scan_ref(xh, dt, A, Bm, Cm)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-4)

    def test_chunk_independence(self):
        xh, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(1), 1, 128, 2, 16, 8)
        a = ops.ssd_scan(xh, dt, A, Bm, Cm, chunk=32)
        b = ops.ssd_scan(xh, dt, A, Bm, Cm, chunk=64)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    def test_model_chunked_matches_kernel(self):
        """The model's jnp chunked SSD (_ssd_chunked) and the Pallas kernel
        agree — they implement the same algorithm with different tiling."""
        from repro.models.ssm import _ssd_chunked

        xh, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(2), 1, 128, 2, 16, 8)
        a = _ssd_chunked(xh, dt, A, Bm, Cm, chunk=32)
        b = ops.ssd_scan(xh, dt, A, Bm, Cm, chunk=32)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)

    def test_model_chunked_grads_finite_under_strong_decay(self):
        """A chunk whose summed decay passes f32's exp range (as mamba2-370m's
        256-step chunks do at initialisation) still has finite gradients."""
        from repro.models.ssm import _ssd_chunked

        xh, dt, A, Bm, Cm = self._inputs(jax.random.PRNGKey(3), 1, 128, 2, 16, 8)
        dt = dt + 1.0  # summed |dt * A| over the 128-step chunk exceeds 88

        def loss(xh, dt):
            return jnp.sum(_ssd_chunked(xh, dt, A, Bm, Cm, chunk=128) ** 2)

        grads = jax.grad(loss, argnums=(0, 1))(xh, dt)
        for g in grads:
            assert np.all(np.isfinite(np.asarray(g)))
