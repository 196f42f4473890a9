"""Accelerator platforms: timing models + knowledge tiers + Algorithm 1 E2E."""

import numpy as np
import pytest

from repro.accelerators import TPUv5eSim, UltraTrailSim, VTASim, XLACPUPlatform
from repro.core import prs, steps, sweeps
from repro.core.batch import ConfigBatch


class TestUltraTrail:
    def test_white_box_widths(self):
        ut = UltraTrailSim()
        assert ut.known_step_widths("conv1d")["C"] == 8
        assert ut.known_step_widths("conv1d")["K"] == 8

    def test_sweeps_confirm_documented_widths(self):
        """Black-box treatment of the white-box sim recovers 8/8 (Fig. 2 analog)."""
        ut = UltraTrailSim()
        sw = sweeps.run_sweeps(ut, "conv1d", params=("C", "K", "C_w"), n_points=56)
        W = steps.determine_step_widths(sw)
        assert W["C"] == 8 and W["K"] == 8 and W["C_w"] == 1

    def test_same_step_same_time(self):
        """All configs within one step cost the same (paper Sec. 3.3)."""
        ut = UltraTrailSim()
        base = ut.defaults("conv1d")
        times = {ut.measure("conv1d", {**base, "C": c}) for c in (17, 20, 24)}
        assert len(times) == 1
        assert ut.measure("conv1d", {**base, "C": 25}) > next(iter(times))


class TestVTA:
    def test_gray_box_confirms_16(self):
        vta = VTASim()
        W, _, n_meas = sweeps.discover_step_widths(vta, "fully_connected")
        assert W == {"in": 16, "out": 16}
        assert n_meas > 0  # gray box had to sweep

    def test_conv2d_widths(self):
        vta = VTASim()
        W, _, _ = sweeps.discover_step_widths(vta, "conv2d")
        assert W["C"] == 16 and W["K"] == 16


class TestTPUv5e:
    def test_knowledge_tiers(self):
        white = TPUv5eSim(knowledge="white")
        gray = TPUv5eSim(knowledge="gray")
        black = TPUv5eSim(knowledge="black")
        assert white.known_step_widths("dense") == {"tokens": 8, "d_in": 128, "d_out": 128}
        assert gray.known_step_widths("dense") == {"d_in": 128, "d_out": 128}
        assert black.known_step_widths("dense") is None

    def test_white_box_needs_no_sweeps(self):
        W, sw, n = sweeps.discover_step_widths(TPUv5eSim(knowledge="white"), "dense")
        assert n == 0 and not sw and W["d_in"] == 128

    def test_dense_mxu_steps_discovered(self):
        tpu = TPUv5eSim(knowledge="black")
        W, _, _ = sweeps.discover_step_widths(tpu, "dense")
        assert W["d_in"] == 128 and W["d_out"] == 128

    def test_moe_token_step_width(self):
        """tokens step = E*sublane/topk -- only discoverable by sweeps."""
        tpu = TPUv5eSim(knowledge="black", moe_experts=64, moe_topk=8)
        W, _, _ = sweeps.discover_step_widths(tpu, "moe_gemm")
        assert W["tokens"] == 64

    def test_decode_page_quantisation(self):
        tpu = TPUv5eSim()
        base = tpu.defaults("attention_decode")
        t1 = tpu.measure("attention_decode", {**base, "S_kv": 4097})
        t2 = tpu.measure("attention_decode", {**base, "S_kv": 4224})
        assert t1 == t2  # same 128-token page

    def test_roofline_max_rule(self):
        """Single layer sits at max(flops, bytes) + overhead."""
        tpu = TPUv5eSim()
        f, m = tpu._terms("dense", {"tokens": 8, "d_in": 8192, "d_out": 8192})
        assert m > f  # tiny-batch GEMM is memory-bound
        t = tpu.measure("dense", {"tokens": 8, "d_in": 8192, "d_out": 8192})
        assert t == pytest.approx(m + tpu.chip.launch_overhead_s)

    def test_block_overlap_faster_than_sum(self):
        """Fused blocks overlap compute/DMA: t_block < sum of layer times."""
        tpu = TPUv5eSim()
        layers = [("dense", tpu.defaults("dense"))] * 3
        t_block = tpu.measure_block(layers)
        t_sum = sum(tpu.measure(lt, c) for lt, c in layers)
        assert t_block < t_sum

    def test_collective_term_eq9(self):
        tpu = TPUv5eSim()
        layers = [("dense", {"tokens": 64, "d_in": 256, "d_out": 256})]
        slow_coll = tpu.measure_block(layers, collective_bytes=1e9)
        fast_coll = tpu.measure_block(layers, collective_bytes=0.0)
        assert slow_coll > fast_coll  # ICI-bound branch of the max rule

    def test_deterministic_noise(self):
        tpu = TPUv5eSim(noise=0.01)
        cfg = tpu.defaults("dense")
        assert tpu.measure("dense", cfg) == tpu.measure("dense", cfg)


class TestXLACPU:
    def test_measures_positive_and_monotone_ish(self):
        cpu = XLACPUPlatform(repeats=3)
        t_small = cpu.measure("dense", {"tokens": 16, "d_in": 32, "d_out": 32})
        t_big = cpu.measure("dense", {"tokens": 256, "d_in": 768, "d_out": 768})
        assert t_small > 0 and t_big > t_small

    def test_wallclock_names_its_device(self):
        import jax

        dev = jax.devices()[0]
        wall = XLACPUPlatform(repeats=1)
        assert wall.name == f"xla_cpu[{dev.platform}:{dev.device_kind}]"
        assert wall.cache_key().startswith(wall.name + "|")
        assert wall.measures_accelerator() == (dev.platform != "cpu")
        # synthetic mode never touches a device and keeps its key
        syn = XLACPUPlatform(synthetic=True)
        assert syn.name == "xla_cpu"
        assert syn.cache_key() == "xla_cpu|dtype=float32|repeats=5|synthetic"
        assert not syn.measures_accelerator()

    def test_batch_times_each_unique_shape_once(self):
        from repro.obs.metrics import metrics

        def shapes():
            return metrics().snapshot()["counters"].get("xla_cpu.shapes", 0)

        wall = XLACPUPlatform(repeats=1)
        cfgs = [
            {"tokens": 16, "d_in": 32, "d_out": 64},
            {"tokens": 24, "d_in": 48, "d_out": 64},
            {"tokens": 16, "d_in": 32, "d_out": 64},
        ]
        before = shapes()
        y = wall.measure_batch("dense", ConfigBatch.from_dicts(cfgs))
        assert y.shape == (3,) and (y > 0).all() and y[0] == y[2]
        assert shapes() == before + 2
