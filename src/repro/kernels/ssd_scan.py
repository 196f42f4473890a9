"""Pallas TPU kernel for the Mamba2 chunked SSD scan.

Grid: (batch, heads, num_chunks) -- the chunk axis is sequential on TPU, so the
inter-chunk SSM state (headdim x dstate, fp32) lives in VMEM scratch and is
carried across chunk iterations, exactly like the reference ``lax.scan``.

Per chunk the kernel computes (Q = chunk length, P = headdim, N = dstate):
  intra:  Y_intra = (L . (C B^T)) Xbar           -- two MXU matmuls (QxQ, QxP)
  inter:  Y_inter = diag(exp(a_cum)) C S_prev    -- (QxN)x(NxP)
  state:  S_new   = exp(a_last) S_prev + (decay_out . B)^T Xbar

Arrays are head-major so every block's last two dimensions meet the TPU's
(8, 128) tiling: xbar/y are (B, H, S, P) in ``(1, 1, Q, P)`` blocks, and the
log decay comes twice, as a row (B, H, 1, S) and as a column (B, H, S, 1), so
the kernel can build the (Q x Q) segment-sum matrix from masked lane and
sublane reductions without an in-kernel transpose.

VMEM working set: x (Q x P), B/C (Q x N), L (Q x Q) fp32 -- with Q = 128,
P = 64..128, N = 64..128 that is < 1 MiB, leaving VMEM for pipelining.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(
    x_ref,  # (1, 1, Q, P)
    a_row_ref,  # (1, 1, 1, Q)   log decay as a row
    a_col_ref,  # (1, 1, Q, 1)   the same log decay as a column
    b_ref,  # (1, Q, N)
    c_ref,  # (1, Q, N)
    y_ref,  # (1, 1, Q, P)
    state_ref,  # scratch (P, N) fp32
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)  # (Q, P)
    a_row = a_row_ref[0, 0].astype(jnp.float32)  # (1, Q)
    a_col = a_col_ref[0, 0].astype(jnp.float32)  # (Q, 1)
    bm = b_ref[0].astype(jnp.float32)  # (Q, N)
    cm = c_ref[0].astype(jnp.float32)  # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # decay since chunk start, as a column (index i) and as a row (index j)
    cum_col = jnp.sum(jnp.where(jj <= ii, a_row, 0.0), axis=1, keepdims=True)  # (Q, 1)
    cum_row = jnp.sum(jnp.where(ii <= jj, a_col, 0.0), axis=0, keepdims=True)  # (1, Q)
    # L[i, j] = exp(a_cum_i - a_cum_j) for i >= j else 0
    lmat = jnp.where(ii >= jj, jnp.exp(cum_col - cum_row), 0.0)

    scores = jax.lax.dot_general(
        cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, Q) = C_i . B_j
    w = scores * lmat
    y_intra = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (Q, P)

    state = state_ref[...]  # (P, N)
    y_inter = (
        jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        * jnp.exp(cum_col)
    )  # (Q, P)

    a_last = jnp.sum(a_row, axis=1, keepdims=True)  # (1, 1) decay over the chunk
    decay_out = jnp.exp(a_last - cum_col)  # (Q, 1)
    state_upd = jax.lax.dot_general(
        x, bm * decay_out, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state_ref[...] = state * jnp.exp(a_last) + state_upd

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan_pallas(
    xbar: jax.Array,  # (B, H, S, P)
    log_da: jax.Array,  # (B, H, S)
    bmat: jax.Array,  # (B, S, N)
    cmat: jax.Array,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, s, p = xbar.shape
    n = bmat.shape[-1]
    assert s % chunk == 0, "pad sequence before calling (see ops.py)"
    nc = s // chunk
    grid = (b, h, nc)
    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, ic: (b_, h_, 0, ic)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, ic: (b_, h_, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ic: (b_, ic, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, ic: (b_, ic, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, ic: (b_, h_, ic, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), xbar.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xbar, log_da[:, :, None, :], log_da[..., None], bmat, cmat)
