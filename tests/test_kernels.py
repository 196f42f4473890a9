"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU).

Per-kernel shape/dtype sweeps with assert_allclose against ref.py.  The
kernels compile only for the TPU, so every call here asks for the Pallas
interpreter explicitly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models.attention import chunked_attention, full_attention
from repro.models.ssm import ssd_chunked


KEY = jax.random.PRNGKey(42)


def _tol(dt):
    return dict(atol=2e-2, rtol=2e-2) if dt == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,sq,h,kvh,d,dt",
        [
            (1, 128, 4, 4, 64, jnp.float32),   # MHA
            (2, 256, 8, 2, 80, jnp.bfloat16),  # GQA, zamba2-like head_dim
            (1, 200, 6, 1, 128, jnp.float32),  # MQA, ragged seq (padding path)
            (1, 384, 12, 2, 96, jnp.float32),  # qwen2-like
        ],
    )
    def test_against_oracle(self, b, sq, h, kvh, d, dt):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (b, sq, h, d), dt)
        k = jax.random.normal(ks[1], (b, sq, kvh, d), dt)
        v = jax.random.normal(ks[2], (b, sq, kvh, d), dt)
        o = ops.flash_attention(q, k, v, causal=True, interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(o_ref, np.float32), **_tol(dt)
        )

    def test_non_causal(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 4, 64))
        v = jax.random.normal(ks[2], (1, 256, 4, 64))
        o = ops.flash_attention(q, k, v, causal=False, interpret=True)
        o_ref = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("block_q,block_k", [(64, 64), (128, 256)])
    def test_block_shape_sweep(self, block_q, block_k):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64))
        k = jax.random.normal(ks[1], (1, 256, 2, 64))
        v = jax.random.normal(ks[2], (1, 256, 2, 64))
        o = ops.flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k, interpret=True
        )
        o_ref = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=2e-5, rtol=2e-5)


class TestNoInterpretFallback:
    """Off the TPU a kernel runs only when the caller asks for the interpreter."""

    def test_compiled_call_off_tpu_raises(self):
        assert jax.default_backend() != "tpu"
        q = jnp.ones((1, 128, 2, 64))
        with pytest.raises(ValueError, match="interpret"):
            ops.flash_attention(q, q, q)
        la = jnp.zeros((1, 128, 2))
        bm = jnp.ones((1, 128, 16))
        with pytest.raises(ValueError, match="interpret"):
            ops.ssd_scan(q, la, bm, bm)


class TestSSDScan:
    @pytest.mark.parametrize(
        "b,s,h,p,n,dt",
        [
            (2, 256, 4, 64, 64, jnp.float32),
            (1, 300, 8, 64, 128, jnp.bfloat16),  # mamba2-780m-like, ragged seq
            (1, 128, 2, 32, 16, jnp.float32),
        ],
    )
    def test_against_oracle(self, b, s, h, p, n, dt):
        ks = jax.random.split(KEY, 4)
        xb = jax.random.normal(ks[0], (b, s, h, p), dt) * 0.2
        la = -jnp.abs(jax.random.normal(ks[1], (b, s, h), jnp.float32)) * 0.1
        bm = jax.random.normal(ks[2], (b, s, n), dt) * 0.3
        cm = jax.random.normal(ks[3], (b, s, n), dt) * 0.3
        y = ops.ssd_scan(xb, la, bm, cm, interpret=True)
        y_ref, _ = ref.ssd_ref(xb, la, bm, cm)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            atol=2e-2 if dt == jnp.bfloat16 else 2e-5, rtol=5e-2 if dt == jnp.bfloat16 else 2e-5,
        )

    def test_xla_twin_matches_oracle(self):
        """models.ssm.ssd_chunked (the XLA path) == naive recurrence."""
        ks = jax.random.split(KEY, 4)
        b, s, h, p, n = 2, 200, 4, 8, 16
        xb = jax.random.normal(ks[0], (b, s, h, p)) * 0.2
        la = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
        bm = jax.random.normal(ks[2], (b, s, n)) * 0.3
        cm = jax.random.normal(ks[3], (b, s, n)) * 0.3
        y_ref, st_ref = ref.ssd_ref(xb, la, bm, cm)
        for chunk in (16, 64, 128):
            y, st = ssd_chunked(xb, la, bm, cm, chunk)
            np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5, rtol=2e-4)
            np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref), atol=2e-5, rtol=2e-4)


class TestChunkedAttentionTwin:
    def test_chunked_matches_full(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 96, 8, 32))
        k = jax.random.normal(ks[1], (2, 96, 2, 32))
        v = jax.random.normal(ks[2], (2, 96, 2, 32))
        o_full = full_attention(q, k, v, causal=True)
        for bk in (17, 32, 128):
            o = chunked_attention(q, k, v, causal=True, block_k=bk)
            np.testing.assert_allclose(np.asarray(o), np.asarray(o_full), atol=2e-5, rtol=2e-4)

    def test_decode_offset(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (2, 1, 8, 32))
        k = jax.random.normal(ks[1], (2, 64, 2, 32))
        v = jax.random.normal(ks[2], (2, 64, 2, 32))
        o1 = full_attention(q, k, v, causal=True, q_offset=63)
        o2 = chunked_attention(q, k, v, causal=True, q_offset=63, block_k=16)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5, rtol=2e-4)
