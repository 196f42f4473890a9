"""The decomposition held to digests recorded from the per-step scalar plan.

``decompose``, ``decompose_batch`` and ``decompose_steps`` share one plan,
so comparing them with each other cannot catch a fault in that plan.  These
digests were recorded from the plan as it was walked once per step, before
it became columnar, and every form of the decomposition must reproduce them:

* every ``ARCHS`` and ``ESTIMATED`` model under every applicable ``SHAPES``
  entry and five ``(dp, tp)`` meshes, one step at a time, and all of them in
  one ``decompose_steps`` call whose kinds alternate;
* the benchmark's search models under their cells' shapes, every
  ``default_candidates`` list of 64, 256 and 1024 chips in one call, as the
  search makes it.

DeepSeek-V3 came after the plan became columnar: its digests were recorded
from that plan, with each step's blocks checked against the decomposition
written out by hand (``tests/test_mla.py``) and against the benchmark's
plain restatement (``tests/bench/test_bench_mla.py``).

Regenerate deliberately, on a tree whose plan is known good, with
``PYTHONPATH=src python tests/test_decompose_golden.py``.
"""

from __future__ import annotations

import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from repro.configs import ARCHS, ESTIMATED, get_config
from repro.core.advisor import candidates_block_batch, default_candidates
from repro.core.batch import BlockBatch
from repro.core.network import decompose, decompose_batch, decompose_steps
from repro.models.config import SHAPES, InputShape, ModelConfig, shape_applicable

GOLDEN = Path(__file__).parent / "data" / "decompose_golden.json"
MESHES = ((1, 1), (4, 2), (16, 16), (64, 1), (1, 64))
CHIPS = (64, 256, 1024)

#: the search cells' models and shapes, as ``bench/configs`` and
#: ``bench/traffic`` gave them when the digests were recorded
SEARCH_MODELS = {
    "olmoe.mesh_search": (
        {"name": "olmoe-1b-7b", "family": "moe", "n_layers": 16, "d_model": 2048,
         "n_heads": 16, "n_kv_heads": 16, "d_ff": 1024, "vocab": 50304,
         "moe_experts": 64, "moe_top_k": 8},
        {"train_4k": (4096, 256, "train"), "decode_32k": (32768, 128, "decode")},
    ),
    "nemotron3.hybrid_search": (
        {"name": "nemotron-3-nano-30b-a3b", "family": "hybrid", "n_layers": 52,
         "d_model": 2688, "n_heads": 32, "n_kv_heads": 2, "head_dim": 128, "d_ff": 1856,
         "vocab": 131072, "mlp": "relu2", "rope_theta": 10000.0, "moe_experts": 128,
         "moe_top_k": 6, "moe_shared_d_ff": 3712, "ssm_state": 128, "ssm_headdim": 64,
         "ssm_n_heads": 64, "ssm_groups": 8, "ssm_expand": 2, "ssm_conv": 4,
         "ssm_chunk": 128,
         "layer_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"},
        {"train_8k": (8192, 512, "train"), "decode_128k": (131072, 64, "decode")},
    ),
    "deepseek3.mla_search": (
        {"name": "deepseek-v3", "family": "moe", "n_layers": 61, "d_model": 7168,
         "n_heads": 128, "n_kv_heads": 128, "d_ff": 2048, "vocab": 129280,
         "rope_theta": 10000.0, "norm_eps": 1e-06, "moe_experts": 256, "moe_top_k": 8,
         "moe_shared_d_ff": 2048, "moe_dense_layers": 3, "moe_dense_d_ff": 18432,
         "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
         "qk_rope_head_dim": 64, "v_head_dim": 128, "mtp_layers": 1},
        {"prefill_32k": (32768, 64, "prefill"), "decode_128k": (131072, 256, "decode")},
    ),
}


def _hex(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()[:16]


def batch_digest(batch: BlockBatch) -> dict[str, str]:
    """sha256[:16] of a batch's fingerprints, per-block columns and layout."""
    payload = batch.to_payload()
    return {
        "fingerprints": _hex(repr(batch.fingerprints()).encode()),
        "collective_bytes": _hex(batch.collective_bytes.tobytes()),
        "repeat": _hex(batch.repeat.tobytes()),
        "kinds": _hex("\x1e".join(batch.kinds).encode()),
        "layout": _hex(json.dumps([payload["block_id"], payload["group_of"],
                                   payload["row_of"], payload["groups"]]).encode()),
    }


def blocks_digest(blocks) -> str:
    """sha256[:16] of ``decompose``'s blocks as JSON: it raises on numpy
    scalars, so the digest also pins plain Python values."""
    return _hex(json.dumps([(b.kind, [[lt, c] for lt, c in b.layers],
                             b.collective_bytes, b.repeat) for b in blocks]).encode())


def _arch_steps(arch):
    cfg = get_config(arch)
    shapes = [s for s in SHAPES.values() if shape_applicable(cfg, s)]
    return cfg, [(shape, dp, tp) for dp, tp in MESHES for shape in shapes]


def arch_digests(arch) -> dict:
    cfg, steps = _arch_steps(arch)
    out = {}
    for shape, dp, tp in steps:
        out[f"{shape.name}/{dp}x{tp}"] = {
            **batch_digest(decompose_batch(cfg, shape, dp, tp)),
            "blocks": blocks_digest(decompose(cfg, shape, dp, tp)),
        }
    batch, step_of = decompose_steps(cfg, steps)
    out["steps"] = {**batch_digest(batch), "step_of": _hex(step_of.tobytes())}
    return out


def _search(cell):
    model, shapes = SEARCH_MODELS[cell]
    cfg = ModelConfig(**model)
    for name, (seq_len, batch, kind) in shapes.items():
        for chips in CHIPS:
            yield f"{name}/{chips}", cfg, InputShape(name, seq_len, batch, kind), chips


def search_digests(cell) -> dict:
    out = {}
    for key, cfg, shape, chips in _search(cell):
        batch, step_of = candidates_block_batch(cfg, shape, default_candidates(chips))
        out[key] = {**batch_digest(batch), "step_of": _hex(step_of.tobytes())}
    return out


def _golden() -> dict:
    return json.loads(GOLDEN.read_text())


@pytest.mark.parametrize("arch", ARCHS + ESTIMATED)
def test_every_step_reproduces_the_scalar_plan(arch):
    want = _golden()["archs"][arch]
    got = arch_digests(arch)
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == want[key], key


@pytest.mark.parametrize("cell", sorted(SEARCH_MODELS))
def test_every_search_reproduces_the_scalar_plan(cell):
    want = _golden()["searches"][cell]
    got = search_digests(cell)
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == want[key], key


def test_the_golden_covers_every_model_shape_and_mesh():
    golden = _golden()
    assert sorted(golden["archs"]) == sorted(ARCHS + ESTIMATED)
    for arch, cases in golden["archs"].items():
        cfg = get_config(arch)
        n_shapes = sum(shape_applicable(cfg, s) for s in SHAPES.values())
        assert len(cases) == n_shapes * len(MESHES) + 1, arch
    assert sorted(golden["searches"]) == sorted(SEARCH_MODELS)
    assert all(len(c) == 2 * len(CHIPS) for c in golden["searches"].values())


def test_decompose_returns_plain_python_values():
    cfg = get_config("zamba2-2.7b")
    blocks = decompose(cfg, SHAPES["train_4k"], 4, 2)
    json.dumps([(b.kind, b.layers, b.collective_bytes, b.repeat) for b in blocks])
    for b in blocks:
        assert type(b.collective_bytes) is float and type(b.repeat) is float
        for _, c in b.layers:
            assert all(type(v) is int for v in c.values()), c
    steps = _arch_steps("zamba2-2.7b")[1]
    assert np.asarray(decompose_steps(cfg, steps)[1]).dtype == np.int64


if __name__ == "__main__":
    GOLDEN.parent.mkdir(exist_ok=True)
    GOLDEN.write_text(json.dumps({
        "archs": {arch: arch_digests(arch) for arch in ARCHS + ESTIMATED},
        "searches": {cell: search_digests(cell) for cell in sorted(SEARCH_MODELS)},
    }, indent=1, sort_keys=True) + "\n")
