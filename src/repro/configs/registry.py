"""Registry of the 10 assigned architectures (``--arch <id>``), and of the
architectures the oracle only estimates (``ESTIMATED``): ``get_config`` knows
both, the model zoo builds only ``ARCHS``."""

from __future__ import annotations

import importlib

ARCHS: tuple[str, ...] = (
    "zamba2-2.7b",
    "granite-20b",
    "qwen2-1.5b",
    "internlm2-1.8b",
    "granite-34b",
    "olmoe-1b-7b",
    "qwen3-moe-235b-a22b",
    "qwen2-vl-2b",
    "whisper-medium",
    "mamba2-780m",
)

#: decomposed, scored and ranked by the oracle; no forward pass in the zoo
ESTIMATED: tuple[str, ...] = ("nemotron-3-nano-30b-a3b", "deepseek-v3")

_MODULES = {
    "zamba2-2.7b": "zamba2_2p7b",
    "granite-20b": "granite_20b",
    "qwen2-1.5b": "qwen2_1p5b",
    "internlm2-1.8b": "internlm2_1p8b",
    "granite-34b": "granite_34b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "qwen3-moe-235b-a22b": "qwen3_moe_235b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "whisper-medium": "whisper_medium",
    "mamba2-780m": "mamba2_780m",
    "nemotron-3-nano-30b-a3b": "nemotron3_nano_30b_a3b",
    "deepseek-v3": "deepseek_v3",
}


def get_config(arch: str):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCHS + ESTIMATED}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.CONFIG
