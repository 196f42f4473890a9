"""XLA wall-clock platform: *real* measurements on JAX's first device.

This is the black-box platform analog of the paper's Jetson AGX Xavier: a real,
noisy computing device where nothing about tiling is documented to the
methodology.  Layers are jitted with XLA and timed on ``jax.devices()[0]`` --
the CPU where JAX has only the CPU, the chip where an accelerator is attached;
the paper's median-of-k protocol (it used 500 runs on the Jetson) mitigates
warm-up noise.  The device's platform and kind are part of the platform's
``name`` and ``cache_key()``, so measurements (and the estimators trained on
them) taken on one device are never cached, journaled or loaded as another's.
The registry name stays ``xla_cpu``.

Measurement is expensive -- keep parameter spaces small and use this platform
for the black-box evaluation path only.  With the measurement runtime
(:mod:`repro.runtime`), the cache-miss sub-batches of a campaign are sharded
across a process pool; workers rebuild the platform from :meth:`spawn_spec`.

``synthetic=True`` swaps the wall clock for a deterministic tile-quantised
analytical proxy (same parameter space, same step structure).  That mode
exists for the runtime's reproducibility guarantees — bitwise-identical
campaigns across worker counts, byte-identical resumed checkpoints — which a
noisy wall clock cannot certify, and for CI smoke runs on contended runners.
jax is imported lazily, when a wall-clock platform first needs its device
(its name, cache key or a measurement), so synthetic workers (and journal
replays) never pay the jax startup cost.
"""

from __future__ import annotations

import functools
import math
import time

import numpy as np

from repro.accelerators.base import Platform
from repro.registry import register_platform
from repro.core.batch import BlockBatch, ConfigBatch
from repro.core.prs import Config, ParamSpace
from repro.obs.metrics import metrics as obs_metrics


def _matmul(a, b):
    return a @ b


@functools.lru_cache(maxsize=1)
def _jit_dense():
    """Deferred jax import + jit: only the wall-clock path needs a device."""
    import jax

    return jax, jax.jit(_matmul)


class XLACPUPlatform(Platform):
    knowledge = "black"

    #: synthetic-mode model: row tile, contraction/output tile, GEMM rate
    SYN_TILE_M = 8
    SYN_TILE_KN = 64
    SYN_FLOPS = 5e10
    SYN_OVERHEAD_S = 2e-6

    def __init__(self, repeats: int = 5, dtype="float32", synthetic: bool = False) -> None:
        self.repeats = repeats
        self.dtype = np.dtype(dtype)  # accepts "float32", np.float32, jnp.float32
        self.synthetic = bool(synthetic)
        self._cache: dict[tuple, float] = {}

    @functools.cached_property
    def device(self):
        """The JAX device wall-clock measurements run on (imports jax)."""
        import jax

        return jax.devices()[0]

    @property
    def name(self) -> str:
        """``xla_cpu`` in synthetic mode, else ``xla_cpu[<platform>:<device kind>]``."""
        if self.synthetic:
            return "xla_cpu"
        return f"xla_cpu[{self.device.platform}:{self.device.device_kind}]"

    def measures_accelerator(self) -> bool:
        return not self.synthetic and self.device.platform != "cpu"

    def cache_key(self) -> str:
        mode = "|synthetic" if self.synthetic else ""
        return f"{self.name}|dtype={self.dtype.name}|repeats={self.repeats}{mode}"

    def spawn_spec(self) -> tuple[str, dict, str]:
        return (
            "xla_cpu",
            {
                "repeats": self.repeats,
                "dtype": self.dtype.name,  # np.dtype pickles, but the name is stabler
                "synthetic": self.synthetic,
            },
            "repro.accelerators.xla_cpu",
        )

    def layer_types(self) -> tuple[str, ...]:
        return ("dense",)

    def param_space(self, layer_type: str) -> ParamSpace:
        assert layer_type == "dense"
        return ParamSpace(ranges={"tokens": (16, 256), "d_in": (32, 768), "d_out": (32, 768)})

    def defaults(self, layer_type: str) -> Config:
        return {"tokens": 64, "d_in": 256, "d_out": 256}

    # ------------------------------------------------------------- measurement
    def measure(self, layer_type: str, cfg: Config) -> float:
        assert layer_type == "dense"
        key = (cfg["tokens"], cfg["d_in"], cfg["d_out"])
        if key in self._cache:
            return self._cache[key]
        m, k, n = key
        t = self._synthetic_time(m, k, n) if self.synthetic else self._wallclock_time(m, k, n)
        self._cache[key] = t
        return t

    def _synthetic_time(self, m: int, k: int, n: int) -> float:
        """Deterministic stand-in: tile-padded GEMM time at a fixed rate."""
        em = math.ceil(m / self.SYN_TILE_M) * self.SYN_TILE_M
        ek = math.ceil(k / self.SYN_TILE_KN) * self.SYN_TILE_KN
        en = math.ceil(n / self.SYN_TILE_KN) * self.SYN_TILE_KN
        return 2.0 * em * ek * en / self.SYN_FLOPS + self.SYN_OVERHEAD_S

    def _compile(self, key: tuple):
        """Compile ``(m, k) @ (k, n)`` for :attr:`device` (no operands needed)."""
        jax, dense = _jit_dense()
        m, k, n = key
        on = jax.sharding.SingleDeviceSharding(self.device)
        return dense.lower(
            jax.ShapeDtypeStruct((m, k), self.dtype, sharding=on),
            jax.ShapeDtypeStruct((k, n), self.dtype, sharding=on),
        ).compile()

    def _wallclock_time(self, m: int, k: int, n: int) -> float:
        """Median of ``repeats`` timed runs on :attr:`device`.

        The program is compiled ahead of time (operands are not needed for
        that) so its compile seconds are measured apart from the run;
        operands are then copied from the host and one untimed call warms
        up.  Compile and timed-loop seconds go to the ``xla_cpu.compile_s`` /
        ``xla_cpu.timed_s`` histograms; ``xla_cpu.shapes`` counts the shapes.
        """
        jax, _ = _jit_dense()
        reg = obs_metrics()
        t0 = time.perf_counter()
        dense = self._compile((m, k, n))
        reg.observe_value("xla_cpu.compile_s", time.perf_counter() - t0)
        a = jax.device_put(np.ones((m, k), self.dtype), self.device)
        b = jax.device_put(np.ones((k, n), self.dtype), self.device)
        dense(a, b).block_until_ready()  # warm up
        samples = []
        for _ in range(self.repeats):
            t0 = time.perf_counter()
            dense(a, b).block_until_ready()
            samples.append(time.perf_counter() - t0)
        reg.observe_value("xla_cpu.timed_s", sum(samples))
        reg.inc("xla_cpu.shapes")
        return float(np.median(samples))

    def measure_batch(self, layer_type: str, batch: ConfigBatch) -> np.ndarray:
        """Wall-clock timing cannot vectorize; batch-level dedup is the win.

        Unique rows are timed once each (in first-occurrence order, so the
        warm-up/measurement sequence matches the scalar loop) and duplicates
        reuse the measured value.

        Synthetic mode under the jax predict backend takes the jitted kernel
        (bitwise-identical, deterministic, so skipping ``self._cache`` cannot
        change a value); wall-clock mode always runs real timed kernels.
        """
        from repro.accelerators import jax_kernels

        t = jax_kernels.xla_cpu_measure_batch(self, layer_type, batch)
        if t is not None:
            return t
        unique, _, inverse = batch.dedup()
        y = np.array(
            [self.measure(layer_type, cfg) for cfg in unique.to_dicts()],
            dtype=np.float64,
        )
        return y[inverse]

    def measure_block_batch(self, batch: BlockBatch) -> np.ndarray:
        """Block path: sum of per-layer wall-clock times, layers deduplicated.

        Each layer group rides ``measure_batch`` (which times unique rows
        once, in first-occurrence order), so a batch of blocks sharing layer
        shapes pays one warm-up/measurement per unique shape — same values as
        the scalar ``measure_block`` loop, which hits ``self._cache``.
        """
        return self._summed_block_batch(batch)


register_platform("xla_cpu", XLACPUPlatform)
