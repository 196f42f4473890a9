"""Pallas TPU flash-attention kernel (causal, GQA-aware).

Grid layout: (batch, q_heads, num_q_blocks, num_k_blocks); the last grid axis
is sequential on TPU, so the online-softmax running state (m, l, acc) lives in
VMEM scratch that persists across the k-block iterations of one q block.

Arrays are head-major -- q (B, H, Sq, D), k/v (B, KVH, Skv, D) -- so every
block is ``(1, 1, rows, D)``: its last two dimensions are a sequence tile and
the (128-padded) head dim, which is the (8, 128) tiling the TPU compiler
requires.  BlockSpecs keep one (block_q x d) query tile, one (block_k x d) K
and V tile in VMEM; with block_q = block_k = 128 and d = 128 the MXU sees 128x128 matmuls and
the VMEM working set is ~4 tiles x 64 KiB -- far below the 128 MiB/core budget,
leaving room for double buffering of the K/V streams.

Causal blocks entirely above the diagonal are skipped via ``pl.when``.
The kv-head index for GQA is derived from the q-head grid index in the
BlockSpec index maps, so no head replication is materialised.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,  # (1, 1, block_q, d)
    k_ref,  # (1, 1, block_k, d)
    v_ref,  # (1, 1, block_k, d)
    o_ref,  # (1, 1, block_q, d)
    m_ref,  # scratch (block_q,)
    l_ref,  # scratch (block_q,)
    acc_ref,  # scratch (block_q, d)
    *,
    causal: bool,
    sm_scale: float,
    block_q: int,
    block_k: int,
    seq_kv: int,
    num_kb: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_k
    # Skip blocks strictly above the causal diagonal (never any valid key).
    run = (k_start <= q_start + block_q - 1) if causal else (ik >= 0)

    @pl.when(run)
    def _body():
        # Same precision as the XLA twin (models/attention.chunked_attention):
        # q is scaled in its own dtype, both matmuls take the input dtype on
        # the MXU with f32 accumulation, and probabilities enter P @ V in the
        # input dtype while the softmax statistics stay f32.
        q = q_ref[0, 0] * sm_scale
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (bq, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        valid = kpos < seq_kv
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            valid = valid & (kpos <= qpos)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_ref[...]  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        scale = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * scale + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * scale + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    @pl.when(ik == num_kb - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,  # (B, H, Sq, D)
    k: jax.Array,  # (B, KVH, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    b, h, sq, d = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    assert sq % block_q == 0, "pad queries before calling (see ops.py)"
    assert skv % block_k == 0, "pad keys before calling (see ops.py)"
    nq, nk = sq // block_q, skv // block_k
    if sm_scale is None:
        sm_scale = d**-0.5  # caller must pass the unpadded scale when padding d

    kernel = functools.partial(
        _kernel,
        causal=causal,
        sm_scale=sm_scale,
        block_q=block_q,
        block_k=block_k,
        seq_kv=skv,
        num_kb=nk,
    )
    grid = (b, h, nq, nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ * kvh // h, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, iq, ik: (b_, h_ * kvh // h, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
