"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: each test lowers a kernel at real widths with shapes placed on
one chip of a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which refuses what the chip would refuse (block tiling, VMEM, f64
support).  The topology is described inside a module fixture, never at import:
only the worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # Compiles for a described chip are written to a persistent cache that
    # cannot be read back without the chip; keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_qwen2_widths(one_chip):
    from repro.kernels import ops

    cfg = get_config("qwen2-1.5b")
    b, s, d = 1, 2048, cfg.head_dim
    assert (cfg.n_heads, cfg.n_kv_heads, d) == (12, 2, 128)
    q = _sds((b, s, cfg.n_heads, d), jnp.bfloat16, one_chip)
    kv = _sds((b, s, cfg.n_kv_heads, d), jnp.bfloat16, one_chip)
    compiled = ops.flash_attention.lower(
        q, kv, kv, causal=True,
        block_q=cfg.attention_block_q, block_k=cfg.attention_block_k,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_mamba2_widths(one_chip):
    from repro.kernels import ops

    cfg = get_config("mamba2-780m")
    b, s = 1, 2048
    h, p, n = cfg.ssm_heads, cfg.ssm_headdim, cfg.ssm_state
    assert (h, p, n) == (48, 64, 128)
    compiled = ops.ssd_scan.lower(
        _sds((b, s, h, p), jnp.bfloat16, one_chip),
        _sds((b, s, h), jnp.float32, one_chip),
        _sds((b, s, n), jnp.bfloat16, one_chip),
        _sds((b, s, n), jnp.bfloat16, one_chip),
        chunk=cfg.ssm_chunk,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _descent_forest(trees, nodes, sharding):
    from repro.core import jax_predict

    return jax_predict.DescentForest(
        _sds((trees, nodes), jnp.int32, sharding),
        _sds((trees, nodes), jnp.float64, sharding),
        _sds((trees, nodes), jnp.int32, sharding),
        _sds((trees, nodes), jnp.int32, sharding),
        _sds((trees, nodes), jnp.float64, sharding),
        _sds((), jnp.float64, sharding),
    )


def _dense_forest(trees, inner, leaves, feats, edges, sharding):
    from repro.core import jax_predict

    return jax_predict.DenseForest(
        _sds((feats, edges), jnp.float64, sharding),
        _sds((trees, inner), jnp.int32, sharding),
        _sds((trees, inner), jnp.int32, sharding),
        _sds((trees, inner, leaves), jnp.int8, sharding),
        _sds((trees, leaves), jnp.int32, sharding),
        _sds((trees, leaves), jnp.float64, sharding),
        _sds((), jnp.float64, sharding),
    )


def test_forest_traversal_f64(one_chip):
    from repro.core import jax_predict

    trees, nodes, rows, feats = 32, 4096, 1024, 6
    with jax.enable_x64(True):
        compiled = jax_predict._forest_fn().lower(
            _descent_forest(trees, nodes, one_chip),
            _sds((rows, feats), jnp.float64, one_chip),
        ).compile()
    assert "while" in compiled.as_text()


@pytest.mark.parametrize("rows", [64, 16384])
def test_forest_traversal_dense(one_chip, rows):
    """The dense form at the benchmark forests' widths (32 trees padded to
    1408 internal nodes and 1408 leaves, six features of up to 7040
    thresholds): its products are int8 with int32 sums, never float64."""
    import re

    from repro.core import jax_predict

    with jax.enable_x64(True):
        compiled = jax_predict._forest_fn().lower(
            _dense_forest(32, 1408, 1408, 6, 7040, one_chip),
            _sds((rows, 6), jnp.float64, one_chip),
        ).compile()
    hlo = compiled.as_text()
    products = re.findall(r"= (\S+) (?:convolution|dot)\(", hlo)
    assert products and all(p.startswith("s32[") for p in products), products
    assert compiled.out_info.shape == (rows,) and compiled.out_info.dtype == jnp.float64


def test_network_kernel_eq9_12(one_chip):
    """One log-target group in the dense form and one linear group in the
    descent through the fused Eq. 9-12 call, with its per-call tables packed
    into one int64 and one float64 buffer."""
    from repro.core import jax_predict

    trees, nodes, feats = 32, 2048, 5
    n_layers, n_blocks, n_nets = 256, 128, 64
    group_rows = (128, 128)
    layout = (n_layers, n_blocks, n_nets, tuple((rows, feats) for rows in group_rows))
    n_ints = sum(group_rows) + n_layers + 1 + 3 * n_blocks
    n_floats = 5 * n_blocks + sum(rows * feats for rows in group_rows)
    with jax.enable_x64(True):
        groups = (_dense_forest(trees, 1408, 1408, feats, 7040, one_chip),
                  _descent_forest(trees, nodes, one_chip))
        compiled = jax_predict._network_fn((True, False)).lower(
            groups,
            _sds((n_ints,), jnp.int64, one_chip),
            _sds((n_floats,), jnp.float64, one_chip),
            _sds((), jnp.float64, one_chip),
            layout,
        ).compile()
    # one float64 estimate per network slot, plus the padding dump slot
    assert compiled.out_info.shape == (n_nets + 1,)
    assert compiled.out_info.dtype == jnp.float64
