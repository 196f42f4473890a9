"""Jit'd public wrappers around the Pallas kernels.

Take the model's sequence-major layout, move heads ahead of the sequence (the
kernels are head-major so their blocks meet the TPU tiling), pad to
hardware-aligned tile sizes (head_dim -> 128 lanes, seq -> block multiples),
and slice results back.  Zero-padding is exact for both kernels: padded
head-dim lanes contribute nothing to dot products, padded key positions are
masked by the kernels, and padded SSD timesteps have zero input (state
unaffected) and are sliced off the output.

The kernels compile for the TPU.  Off the TPU a caller asks for the Pallas
interpreter itself (``interpret=True``, as the CPU tests do); a call that
does not is refused by Pallas instead of silently running interpreted.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _heads_first(x: jax.Array, block: int) -> jax.Array:
    """(B, S, H, D) -> (B, H, S_pad, D_pad) for a head-major kernel."""
    return _pad_to(_pad_to(x.transpose(0, 2, 1, 3), 2, block), 3, 128)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention: q (B,Sq,H,D), k/v (B,Skv,KVH,D) -> (B,Sq,H,D)."""
    sq, d = q.shape[1], q.shape[3]
    # NOTE: the kernel masks padded *key* positions via seq_kv; padded *query*
    # rows compute garbage that is sliced off here.
    o = flash_attention_pallas(
        _heads_first(q, block_q), _heads_first(k, block_k), _heads_first(v, block_k),
        causal=causal, block_q=block_q, block_k=block_k,
        interpret=interpret, sm_scale=d**-0.5,
    )
    return o[:, :, :sq, :d].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    xbar: jax.Array,
    log_da: jax.Array,
    bmat: jax.Array,
    cmat: jax.Array,
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Chunked SSD scan: xbar (B,S,H,P) -> y (B,S,H,P)."""
    s, p = xbar.shape[1], xbar.shape[3]
    xp = _heads_first(xbar, chunk)
    # exp(0)=1 decay on padded steps: state kept
    ap = _pad_to(log_da.transpose(0, 2, 1), 2, chunk)
    bp = _pad_to(_pad_to(bmat, 1, chunk), 2, 128)
    cp = _pad_to(_pad_to(cmat, 1, chunk), 2, 128)
    y = ssd_scan_pallas(xp, ap, bp, cp, chunk=chunk, interpret=interpret)
    return y[:, :, :s, :p].transpose(0, 2, 1, 3)
