"""Jitted analytical timing kernels for the four built-in platforms.

Each platform's ``measure_batch`` calls its hook here first; a hook returns
``None`` whenever the jax backend is not active (``REPRO_PREDICT_BACKEND``,
see :mod:`repro.core.jax_predict`) or the request needs scalar semantics the
kernel cannot reproduce (noisy TPU mode, xla_cpu wall-clock mode) — the
caller then continues on its numpy path unchanged.
Third-party platforms never touch this module.

Parity is **bitwise** with the numpy models (asserted in
tests/test_jax_predict.py): integer tile padding (``-(-v // m) * m``) is
exact arithmetic so tile sizes stay compile-time constants, while every
*float* hardware constant (peak FLOPs, bandwidths, clock rates, overheads)
is passed as a traced scalar — XLA turns division by a literal into
multiplication by its reciprocal (a 1-ulp difference), and a traced divisor
keeps the true division.  Rows are padded to warm-shape buckets with ones
(never zeros: some models divide by a column) and sliced back.

jax is imported lazily through :func:`repro.core.jax_predict.jax_modules`;
importing this module on a jax-free box is free.
"""

from __future__ import annotations

import functools

import numpy as np

from repro.core.jax_predict import bucket_rows, jax_modules, resolve_backend, x64


def _active(platform) -> bool:
    """Whether the platform's predict backend resolves to jax."""
    return resolve_backend(getattr(platform, "predict_backend", None)) == "jax"


def _padded(col, n: int, nb: int) -> np.ndarray:
    """Bucket-pad one int column with ones (safe under ``//`` by a column)."""
    out = np.ones(nb, dtype=np.int64)
    out[:n] = col
    return out


# ------------------------------------------------------------------ TPU v5e
@functools.lru_cache(maxsize=None)
def _tpu_fn(layer_type: str, mxu: int, sublane: int, kv_page: int, ssd_chunk: int):
    jax, jnp, _ = jax_modules()

    def pad(v, m):
        return -(-v // m) * m

    if layer_type == "dense":

        def terms(cols, kv):
            m = pad(cols["tokens"], sublane)
            k = pad(cols["d_in"], mxu)
            n = pad(cols["d_out"], mxu)
            return 2.0 * m * k * n, 2.0 * (m * k + m * n + k * n)

    elif layer_type == "attention_prefill":

        def terms(cols, kv):
            b, h, dh = cols["B"], cols["H"], pad(cols["Dh"], mxu)
            kvh = jnp.maximum(1, h // kv)
            s = pad(cols["S"], mxu)
            flops = 2.0 * b * h * s * s * dh
            bytes_ = 2.0 * (b * h * s * dh + 2 * b * kvh * s * dh + b * h * s * dh)
            return flops, bytes_

    elif layer_type == "attention_decode":

        def terms(cols, kv):
            b = pad(cols["B"], sublane)
            h, dh = cols["H"], pad(cols["Dh"], mxu)
            kvh = jnp.maximum(1, h // kv)
            s = pad(cols["S_kv"], kv_page)
            flops = 4.0 * b * h * s * dh
            bytes_ = 2.0 * (2 * b * kvh * s * dh + 2 * b * h * dh)
            return flops, bytes_

    elif layer_type == "moe_gemm":

        def terms(cols, kv):
            e, topk, mats = cols["E"], cols["topk"], cols.get("mats", 3)
            per_expert = pad(-(-(cols["tokens"] * topk) // e), sublane)
            dm = pad(cols["d_model"], mxu)
            df = pad(cols["d_ff"], mxu)
            flops = 2.0 * mats * e * per_expert * dm * df
            bytes_ = 2.0 * (mats * e * dm * df + e * per_expert * (2 * dm + 2 * df))
            return flops, bytes_

    elif layer_type == "ssd_scan":

        def terms(cols, kv):
            b, h = cols["B"], pad(cols["H"], sublane)
            p = pad(cols["P"], mxu)
            n = pad(cols["N"], mxu)
            s = pad(cols["S"], ssd_chunk)
            q = ssd_chunk
            nchunks = s // q
            per_chunk = 2.0 * q * q * n + 2.0 * q * q * p + 4.0 * q * n * p
            flops = b * h * nchunks * per_chunk
            bytes_ = 2.0 * b * s * (h * p * 2 + 2 * cols.get("G", 1) * n + h)
            return flops, bytes_

    elif layer_type == "ssd_decode":

        def terms(cols, kv):
            b, h, p, n = cols["B"], cols["H"], cols["P"], cols["N"]
            state = b * h * pad(p, sublane) * pad(n, mxu)
            flops = 5.0 * state
            bytes_ = 2.0 * (2 * state + b * (h * p * 2 + 2 * cols.get("G", 1) * n + h))
            return flops, bytes_

    elif layer_type == "embed":

        def terms(cols, kv):
            t, dm = cols["tokens"], cols["d_model"]
            return jnp.zeros(t.shape, dtype=jnp.float64), 2.0 * t * dm * 2 + 4.0 * t

    else:
        raise KeyError(layer_type)

    def run(cols, kv, peak, bw, launch):
        flops, bytes_ = terms(cols, kv)
        return jnp.maximum(flops / peak, bytes_ / bw) + launch

    return jax.jit(run, donate_argnums=(0,))


def tpu_measure_batch(platform, layer_type: str, batch) -> np.ndarray | None:
    """Jitted ``TPUv5eSim.measure_batch`` (noise-free mode only)."""
    n = len(batch)
    if not _active(platform) or platform.noise > 0 or n == 0:
        return None
    c = platform.chip
    try:
        fn = _tpu_fn(layer_type, c.mxu, c.sublane, c.kv_page, c.ssd_chunk)
    except KeyError:
        return None
    nb = bucket_rows(n)
    cols = {p: _padded(batch.column(p), n, nb) for p in batch.params}
    kv = batch.get("kv_ratio", platform.kv_ratio)
    kv = _padded(kv, n, nb) if isinstance(kv, np.ndarray) else np.int64(kv)
    with x64():
        t = fn(
            cols, kv,
            np.float64(c.peak_bf16_flops),
            np.float64(c.hbm_bandwidth),
            np.float64(c.launch_overhead_s),
        )
    return np.asarray(t, dtype=np.float64)[:n]


# --------------------------------------------------------------- UltraTrail
@functools.lru_cache(maxsize=None)
def _ultratrail_fn(array: int):
    jax, jnp, _ = jax_modules()

    def run(C, K, C_w, F, s, pad_, overhead, clock):
        c_tiles = -(-C // array)
        k_tiles = -(-K // array)
        w_out = jnp.maximum(1, (C_w + 2 * pad_ - F) // s + 1)
        mac_cycles = c_tiles * k_tiles * w_out * F
        post_cycles = k_tiles * w_out
        return (mac_cycles + post_cycles + overhead) / clock

    return jax.jit(run)


def ultratrail_measure_batch(platform, layer_type: str, batch) -> np.ndarray | None:
    """Jitted ``UltraTrailSim.measure_batch``."""
    n = len(batch)
    if not _active(platform) or layer_type != "conv1d" or n == 0:
        return None
    nb = bucket_rows(n)
    fn = _ultratrail_fn(platform.ARRAY)
    with x64():
        t = fn(
            _padded(batch.column("C"), n, nb),
            _padded(batch.column("K"), n, nb),
            _padded(batch.column("C_w"), n, nb),
            _padded(batch.column("F"), n, nb),
            _padded(batch.column("s"), n, nb),
            _padded(batch.column("pad"), n, nb),
            np.float64(platform.OVERHEAD_CYCLES),
            np.float64(platform.CLOCK_HZ),
        )
    return np.asarray(t, dtype=np.float64)[:n]


# ---------------------------------------------------------------------- VTA
@functools.lru_cache(maxsize=None)
def _vta_fn(layer_type: str, tile: int):
    jax, jnp, _ = jax_modules()

    def gemm_cycles(m, k, n, io_lanes):
        kt = -(-k // tile)
        nt = -(-n // tile)
        compute = m * kt * nt
        io = (m * kt * tile + kt * nt * tile**2) / io_lanes
        return jnp.maximum(compute, io)

    if layer_type == "conv2d":

        def run(cols, pad_, s, io_lanes, overhead, clock):
            f = cols["F"]
            h_out = jnp.maximum(1, (cols["C_h"] + 2 * pad_ - f) // s + 1)
            w_out = jnp.maximum(1, (cols["C_w"] + 2 * pad_ - f) // s + 1)
            kt = -(-cols["C"] // tile) * tile
            cycles = gemm_cycles(h_out * w_out, kt * f**2, cols["K"], io_lanes)
            return (cycles + overhead) / clock

    else:

        def run(cols, pad_, s, io_lanes, overhead, clock):
            cycles = gemm_cycles(np.int64(1), cols["in"], cols["out"], io_lanes)
            return (cycles + overhead) / clock

    return jax.jit(run, donate_argnums=(0,))


def vta_measure_batch(platform, layer_type: str, batch) -> np.ndarray | None:
    """Jitted ``VTASim.measure_batch``."""
    n = len(batch)
    if not _active(platform) or n == 0 or layer_type not in ("conv2d", "fully_connected"):
        return None
    nb = bucket_rows(n)
    if layer_type == "conv2d":
        cols = {
            p: _padded(batch.column(p), n, nb) for p in ("C", "C_h", "C_w", "K", "F")
        }
        pad_ = batch.get("pad", 1)
        s = batch.get("s", 1)
        pad_ = _padded(pad_, n, nb) if isinstance(pad_, np.ndarray) else np.int64(pad_)
        s = _padded(s, n, nb) if isinstance(s, np.ndarray) else np.int64(s)
    else:
        cols = {p: _padded(batch.column(p), n, nb) for p in ("in", "out")}
        pad_ = s = np.int64(1)
    fn = _vta_fn(layer_type, platform.GEMM_TILE)
    with x64():
        t = fn(
            cols, pad_, s,
            np.float64(platform.IO_LANES),
            np.float64(platform.OVERHEAD_CYCLES),
            np.float64(platform.CLOCK_HZ),
        )
    return np.asarray(t, dtype=np.float64)[:n]


# ------------------------------------------------------------------ XLA CPU
@functools.lru_cache(maxsize=None)
def _xla_synthetic_fn(tile_m: int, tile_kn: int):
    jax = jax_modules()[0]

    def run(m, k, n, syn_flops, overhead):
        em = -(-m // tile_m) * tile_m
        ek = -(-k // tile_kn) * tile_kn
        en = -(-n // tile_kn) * tile_kn
        return 2.0 * em * ek * en / syn_flops + overhead

    return jax.jit(run)


def xla_cpu_measure_batch(platform, layer_type: str, batch) -> np.ndarray | None:
    """Jitted synthetic-mode ``XLACPUPlatform.measure_batch``.

    Wall-clock mode must actually run and time kernels — only the
    deterministic synthetic proxy compiles.  Values are identical whether or
    not they pass through ``platform._cache``, so the kernel skips it.
    """
    n = len(batch)
    if not _active(platform) or not platform.synthetic or layer_type != "dense" or n == 0:
        return None
    nb = bucket_rows(n)
    fn = _xla_synthetic_fn(platform.SYN_TILE_M, platform.SYN_TILE_KN)
    with x64():
        t = fn(
            _padded(batch.column("tokens"), n, nb),
            _padded(batch.column("d_in"), n, nb),
            _padded(batch.column("d_out"), n, nb),
            np.float64(platform.SYN_FLOPS),
            np.float64(platform.SYN_OVERHEAD_S),
        )
    return np.asarray(t, dtype=np.float64)[:n]
