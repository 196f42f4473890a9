"""A whole run of each cell, the chip check skipped, at a size a CPU test
holds: sound, it comes out correct; with the timed path broken underneath,
it comes out not correct."""

import time

import pytest

from bench.run import execute

TINY = {
    "campaign.dense_sweep": {"n_samples": 40, "check_shapes": 4},
    "olmoe.mesh_search": {"check_steps": 3},
    "olmoe.layer_table": {"rows": 512, "check_steps": 3},
    "olmoe.serve_zipf": {"rate_per_s": 200, "keys": 4096, "prefill_keys": 512,
                         "connections": 4},
}


def answer_altered_dense(monkeypatch):
    """The dense program returns its product with one element changed."""
    import jax

    from repro.accelerators import xla_cpu

    wrong = jax.jit(lambda a, b: (a @ b).at[0, 0].add(100.0))
    monkeypatch.setattr(xla_cpu, "_jit_dense", lambda: (jax, wrong))


def repeats_left_out(monkeypatch):
    """The timed loop runs two of the five repeats its cache key names."""
    from repro.accelerators.xla_cpu import XLACPUPlatform

    timed = XLACPUPlatform._wallclock_time

    def fewer(self, m, k, n):
        full, self.repeats = self.repeats, 2
        try:
            return timed(self, m, k, n)
        finally:
            self.repeats = full

    monkeypatch.setattr(XLACPUPlatform, "_wallclock_time", fewer)


def _patch_traverse(monkeypatch, broken):
    from repro.core import jax_predict

    monkeypatch.setattr(jax_predict, "_traverse", broken(jax_predict._traverse))
    jax_predict._forest_fn.cache_clear()
    jax_predict._network_fn.cache_clear()


def answer_altered_forest(monkeypatch):
    """The compiled traversal returns one row's answer changed."""
    _patch_traverse(monkeypatch, lambda traverse: lambda jnp, *a: (
        traverse(jnp, *a).at[0].multiply(1.0 + 1e-6)))


def half_the_trees(monkeypatch):
    """The compiled traversal descends half of the trees and takes the mean
    over those."""
    def broken(traverse):
        def half(jnp, lax, feature, threshold, left, right, value, X, n_trees):
            h = feature.shape[0] // 2
            return traverse(jnp, lax, feature[:h], threshold[:h], left[:h], right[:h],
                            value[:h], X, n_trees / 2)
        return half

    _patch_traverse(monkeypatch, broken)


def half_the_rows(monkeypatch):
    """The compiled traversal descends the first half of the rows of its
    batch and answers the other half with those answers."""
    def broken(traverse):
        def half(jnp, lax, feature, threshold, left, right, value, X, n_trees):
            h = X.shape[0] // 2
            y = traverse(jnp, lax, feature, threshold, left, right, value, X[:h], n_trees)
            return jnp.concatenate([y, y])
        return half

    _patch_traverse(monkeypatch, broken)


CASES = [("campaign.dense_sweep", None), ("campaign.dense_sweep", answer_altered_dense),
         ("campaign.dense_sweep", repeats_left_out)]
CASES += [(name, fault) for name in ("olmoe.layer_table", "olmoe.mesh_search",
                                     "olmoe.serve_zipf")
          for fault in (None, answer_altered_forest, half_the_trees, half_the_rows)]


@pytest.fixture
def fresh_kernels():
    """Compiled kernels, and the matmul precision a campaign run sets, as
    they were before the test."""
    import jax

    from repro.core import jax_predict

    precision = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", precision)
    jax_predict._forest_fn.cache_clear()
    jax_predict._network_fn.cache_clear()


@pytest.mark.parametrize("name,fault", CASES,
                         ids=[f"{n}-{f.__name__ if f else 'sound'}" for n, f in CASES])
def test_run_is_correct_only_when_sound(name, fault, tiny_cell, monkeypatch, fresh_kernels):
    if fault is not None:
        fault(monkeypatch)
    cell = tiny_cell(name, **TINY[name])
    result, checks = execute(cell, 2**31 + 11, 1.0, False, require_chip=False,
                             t_start=time.perf_counter())
    assert result["correct"] is (fault is None), checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end()}
