"""Whole-model decomposition: ModelConfig x InputShape x mesh -> building blocks.

This is the bridge between the paper's methodology and the framework: any of
the 10 assigned architectures, any model built by a layer pattern
(``ModelConfig.layer_pattern``, NemotronH's Mamba-2 / MoE / attention), and
DeepSeek's latent attention, leading dense layers and MTP module
decomposes into per-device building-block
instances (attention block, MLP block, MoE block, SSD block, embed, LM head)
whose layer configurations live in the TPU-v5e platform's parameter spaces.
The PR-trained single-layer estimators then predict per-block times, combined
per Eq. 9-12 into a step-time estimate -- the LM-transformer analogue of the
paper's MobileNet/ResNet whole-DNN estimation.

Sharding-awareness: dims are *per-device* under the given (dp, tp) mesh
factors, and every block carries its collective payload so the Eq.-9 max rule
(compute/DMA/ICI overlap) applies on the sharded platform.
"""

from __future__ import annotations

import itertools
from typing import Sequence

import numpy as np

from repro.core.blocks import Block
from repro.models.config import InputShape, ModelConfig
from repro.obs.metrics import metrics as obs_metrics


def _decompose_plan(
    cfg: ModelConfig,
    kind: str,
    seq_len: np.ndarray,
    global_batch: np.ndarray,
    dp: np.ndarray,
    tp: np.ndarray,
    train_factor: float = 3.0,
):
    """Yield ``(kind, layers, collective_bytes, repeat)`` for the blocks of
    ``n`` steps of one shape kind at once.

    ``seq_len``, ``global_batch``, ``dp`` and ``tp`` are ``(n,)`` int64
    columns, one entry a step.  Every yielded value is either a scalar that
    holds for all ``n`` steps or an ``(n,)`` column; the block and layer
    order and the key order of each layer's config depend only on the model
    and on ``kind``.  The single source of truth behind :func:`decompose`
    (one step, materialised as :class:`Block` objects) and
    :func:`decompose_steps` (many steps in one columnar
    :class:`~repro.core.batch.BlockBatch`), so the two can never drift.
    """
    is_train = kind == "train"
    is_decode = kind == "decode"
    rep = train_factor if is_train else 1.0
    b_loc = np.maximum(1, global_batch // dp)
    s = 1 if is_decode else seq_len
    t_loc = b_loc * s
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    hd = cfg.head_dim
    # head policy: kv heads sharded where tp divides them, else the query
    # heads where it divides them, else both replicated
    kv_sharded = cfg.n_kv_heads % tp == 0
    h_loc = np.where(kv_sharded | (cfg.n_heads % tp == 0), cfg.n_heads // tp, cfg.n_heads)
    kv_loc = np.where(kv_sharded, cfg.n_kv_heads // tp, cfg.n_kv_heads)
    kv_ratio = np.maximum(1, h_loc // np.maximum(1, kv_loc))

    coll_act = t_loc * d * 2.0  # one bf16 activation all-reduce payload

    def mla_block() -> tuple:
        # q_a and kv_a (the cached latent beside the shared RoPE key) are
        # replicated over tp; each chip up-projects its own heads
        nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        lora = cfg.kv_lora_rank
        layers = [
            ("dense", {"tokens": t_loc, "d_in": d, "d_out": cfg.q_lora_rank}),
            ("dense", {"tokens": t_loc, "d_in": d, "d_out": lora + rope}),
            ("dense", {"tokens": t_loc, "d_in": cfg.q_lora_rank, "d_out": h_loc * (nope + rope)}),
        ]
        if is_decode:
            # absorbed: every head reads the whole latent of its sequence,
            # W_UK and W_UV folded into the core
            layers.append(("mla_decode", {"B": b_loc, "S_kv": seq_len, "H": h_loc, "nope": nope,
                                          "rope": rope, "dv": dv, "kv_lora": lora}))
        else:
            # unabsorbed: the latent decompressed into each head's k_nope and v
            layers.append(("dense", {"tokens": t_loc, "d_in": lora, "d_out": h_loc * (nope + dv)}))
            layers.append(("mla_prefill", {"B": b_loc, "S": s, "H": h_loc, "nope": nope,
                                           "rope": rope, "dv": dv}))
        layers.append(("dense", {"tokens": t_loc, "d_in": h_loc * dv, "d_out": d}))
        return ("attn", tuple(layers), coll_act)

    def attn_block() -> tuple:
        if cfg.mla:
            return mla_block()
        layers: list[tuple[str, dict]] = [
            ("dense", {"tokens": t_loc, "d_in": d, "d_out": (h_loc + 2 * kv_loc) * hd}),
        ]
        if is_decode:
            layers.append(
                ("attention_decode", {"B": b_loc, "S_kv": seq_len, "H": h_loc, "Dh": hd, "kv_ratio": kv_ratio})
            )
        else:
            layers.append(
                ("attention_prefill", {"B": b_loc, "S": s, "H": h_loc, "Dh": hd, "kv_ratio": kv_ratio})
            )
        layers.append(("dense", {"tokens": t_loc, "d_in": h_loc * hd, "d_out": d}))
        return ("attn", tuple(layers), coll_act)

    def mlp_layers(width: int) -> list:
        f_loc = np.maximum(1, width // tp)
        n_in = 2 if cfg.mlp == "swiglu" else 1
        layers = [("dense", {"tokens": t_loc, "d_in": d, "d_out": f_loc})] * n_in
        layers.append(("dense", {"tokens": t_loc, "d_in": f_loc, "d_out": d}))
        return layers

    def mlp_block() -> tuple:
        return ("mlp", tuple(mlp_layers(f)), coll_act)

    def moe_block() -> tuple:
        experts = {
            "tokens": np.maximum(1, t_loc // tp),
            "d_model": d,
            "d_ff": f,
            "E": np.maximum(1, cfg.moe_experts // tp),
            "topk": cfg.moe_top_k,
        }
        if cfg.mlp_mats != 3:  # the platform's default is a gated expert
            experts["mats"] = cfg.mlp_mats
        layers = [
            ("dense", {"tokens": t_loc, "d_in": d, "d_out": cfg.moe_experts}),  # router
            ("moe_gemm", experts),
        ]
        if cfg.moe_shared_d_ff:
            layers += mlp_layers(cfg.moe_shared_d_ff)
        return ("moe", tuple(layers), 2 * coll_act)

    def ssd_block() -> tuple:
        di_loc = np.maximum(1, cfg.d_inner // tp)
        h_ssm = np.maximum(1, cfg.ssm_heads // tp)
        p, n = cfg.ssm_headdim, cfg.ssm_state
        # decode advances each sequence's state by one token; it scans no chunk
        if is_decode:
            mixer = {"B": b_loc, "H": h_ssm, "P": p, "N": n}
        else:
            mixer = {"B": b_loc, "S": s, "H": h_ssm, "P": p, "N": n}
        if cfg.layer_pattern:
            # z, x, B, C and dt column-sharded: this chip's share of each
            g_loc = np.maximum(1, cfg.ssm_groups // tp)
            d_proj = 2 * di_loc + 2 * g_loc * n + h_ssm
            mixer["G"] = g_loc
        else:
            d_proj = 2 * di_loc + 2 * n + cfg.ssm_heads
        layers = [
            ("dense", {"tokens": t_loc, "d_in": d, "d_out": d_proj}),
            ("ssd_decode" if is_decode else "ssd_scan", mixer),
            ("dense", {"tokens": t_loc, "d_in": di_loc, "d_out": d}),
        ]
        return ("ssd", tuple(layers), coll_act)

    def body(plan: tuple, n: int) -> tuple:
        kind, layers, coll = plan
        return (kind, layers, coll, n * rep)

    # ---- embedding ----
    yield ("embed", (("embed", {"tokens": t_loc, "vocab": v, "d_model": d}),), 0.0, rep)

    # ---- body ----
    if cfg.layer_pattern:
        blocks = {"M": ssd_block, "E": moe_block, "*": attn_block}
        for layer_kind, n in cfg.layer_counts().items():
            yield body(blocks[layer_kind](), n)
    elif cfg.family in ("dense", "vlm"):
        yield body(attn_block(), cfg.n_layers)
        yield body(mlp_block(), cfg.n_layers)
    elif cfg.family == "moe":
        yield body(attn_block(), cfg.n_layers)
        if cfg.moe_dense_layers:
            yield body(("mlp", tuple(mlp_layers(cfg.moe_dense_d_ff)), coll_act),
                       cfg.moe_dense_layers)
        yield body(moe_block(), cfg.n_layers - cfg.moe_dense_layers)
    elif cfg.family == "ssm":
        yield body(ssd_block(), cfg.n_layers)
    elif cfg.family == "hybrid":
        n_shared = cfg.n_layers // max(1, cfg.attn_every)
        yield body(ssd_block(), cfg.n_layers)
        yield body(attn_block(), n_shared)
        yield body(mlp_block(), n_shared)
    elif cfg.family == "audio":
        if not is_decode:
            enc_t = b_loc * cfg.encoder_seq
            f_loc = np.maximum(1, f // tp)
            enc_attn = (
                "attn",
                (
                    ("dense", {"tokens": enc_t, "d_in": d, "d_out": (h_loc + 2 * kv_loc) * hd}),
                    ("attention_prefill", {"B": b_loc, "S": cfg.encoder_seq, "H": h_loc, "Dh": hd, "kv_ratio": kv_ratio}),
                    ("dense", {"tokens": enc_t, "d_in": h_loc * hd, "d_out": d}),
                ),
                enc_t * d * 2.0,
            )
            enc_mlp = (
                "mlp",
                (
                    ("dense", {"tokens": enc_t, "d_in": d, "d_out": f_loc}),
                    ("dense", {"tokens": enc_t, "d_in": f_loc, "d_out": d}),
                ),
                enc_t * d * 2.0,
            )
            yield body(enc_attn, cfg.n_encoder_layers)
            yield body(enc_mlp, cfg.n_encoder_layers)
        # decoder: self-attn + cross-attn + mlp
        cross = (
            "attn",
            (
                ("dense", {"tokens": t_loc, "d_in": d, "d_out": h_loc * hd}),
                ("attention_decode" if is_decode else "attention_prefill",
                 ({"B": b_loc, "S_kv": cfg.encoder_seq, "H": h_loc, "Dh": hd, "kv_ratio": kv_ratio}
                  if is_decode
                  else {"B": b_loc, "S": cfg.encoder_seq, "H": h_loc, "Dh": hd, "kv_ratio": kv_ratio})),
                ("dense", {"tokens": t_loc, "d_in": h_loc * hd, "d_out": d}),
            ),
            coll_act,
        )
        yield body(attn_block(), cfg.n_layers)
        yield body(cross, cfg.n_layers)
        yield body(mlp_block(), cfg.n_layers)
    else:
        raise ValueError(cfg.family)

    # ---- multi-token prediction, in training only: the hidden state and
    # the next token's embedding (the same lookup, shifted) concatenated and
    # projected, one more layer, and the shared LM head once more ----
    mtp = cfg.mtp_layers if is_train else 0
    if mtp:
        yield body(("mlp", (("dense", {"tokens": t_loc, "d_in": 2 * d, "d_out": d}),), 0.0), mtp)
        yield body(attn_block(), mtp)
        yield body(moe_block(), mtp)

    # ---- LM head ----
    yield (
        "mlp",
        (("dense", {"tokens": t_loc, "d_in": d, "d_out": np.maximum(1, v // tp)}),),
        0.0,
        rep * (1 + mtp),
    )


def _plan(cfg, kind, seq_len, global_batch, dp, tp, train_factor) -> tuple[int, list]:
    """One evaluation of the plan over the steps of one kind (each column
    an int64 column or a scalar that holds for every step): the number of
    steps and the blocks of each step."""
    columns = [np.asarray(c, dtype=np.int64) for c in (seq_len, global_batch, dp, tp)]
    n = max(c.size for c in columns)
    if n == 0:
        return 0, []
    seq_len, global_batch, dp, tp = (
        c.reshape(n) if c.size == n else np.full(n, c) for c in columns
    )
    reg = obs_metrics()
    reg.inc("network.decompose.passes")
    reg.inc("network.decompose.steps", n)
    # the module global, looked up at each call: whatever replaces
    # ``_decompose_plan`` reaches every form of the decomposition
    return n, list(_decompose_plan(cfg, kind, seq_len, global_batch, dp, tp, train_factor))


def _item(value):
    """A plan value of a one-step plan as a plain Python number."""
    return value[0].item() if isinstance(value, np.ndarray) else value


def decompose(
    cfg: ModelConfig,
    shape: InputShape,
    dp: int,
    tp: int,
    train_factor: float = 3.0,
) -> list[Block]:
    """Per-device building blocks of one step.  train_factor ~ (fwd+bwd)/fwd.

    The one-step case of the plan, its values turned back into plain Python
    ``int`` and ``float``."""
    _, plan = _plan(cfg, shape.kind, shape.seq_len, shape.global_batch, dp, tp, train_factor)
    return [
        Block(
            kind=kind,
            layers=tuple((lt, {p: _item(x) for p, x in c.items()}) for lt, c in layers),
            collective_bytes=_item(coll),
            repeat=_item(repeat),
        )
        for kind, layers, coll, repeat in plan
    ]


def decompose_batch(
    cfg: ModelConfig,
    shape: InputShape,
    dp: int,
    tp: int,
    train_factor: float = 3.0,
):
    """Columnar-native :func:`decompose`: the same plan streamed straight into
    a :class:`~repro.core.batch.BlockBatch`, skipping the per-block ``Block``
    objects and the re-grouping pass of ``BlockBatch.from_blocks``.  Field-
    for-field identical to ``BlockBatch.from_blocks(decompose(...))``.
    """
    return decompose_steps(cfg, [(shape, dp, tp)], train_factor)[0]


def decompose_steps(
    cfg: ModelConfig,
    steps: Sequence[tuple[InputShape, int, int]],
    train_factor: float = 3.0,
):
    """Many ``(shape, dp, tp)`` steps of one model in one
    :class:`~repro.core.batch.BlockBatch`, and the step index of each block.

    The plan is evaluated once for each run of consecutive steps of one
    ``shape.kind``, over columns of their sizes and meshes.  Field-for-field
    identical to ``BlockBatch.concat`` of each step's
    :func:`decompose_batch`: groups merge by first occurrence either way.
    """
    runs = []
    for kind, run in itertools.groupby(steps, key=lambda step: step[0].kind):
        shapes, dp, tp = zip(*run)
        runs.append((kind, [s.seq_len for s in shapes], [s.global_batch for s in shapes], dp, tp))
    return decompose_runs(cfg, runs, train_factor)


def decompose_runs(cfg: ModelConfig, runs, train_factor: float = 3.0):
    """:func:`decompose_steps` over runs given as columns:
    ``(kind, seq_len, global_batch, dp, tp)``, each column per step of the
    run or a scalar for all of them.  Steps are numbered across runs."""
    from repro.core.batch import BlockBatchBuilder

    builder = BlockBatchBuilder()
    step_of = [np.empty(0, dtype=np.int64)]
    first = 0
    for kind, *columns in runs:
        n, blocks = _plan(cfg, kind, *columns, train_factor)
        if n:
            builder.add_steps(blocks, n)
        step_of.append(np.repeat(np.arange(first, first + n, dtype=np.int64), len(blocks)))
        first += n
    return builder.build(), np.concatenate(step_of)


def simulate_network(platform, blocks: Sequence[Block]) -> float:
    """'Measure' the whole network on a simulated platform (Table-2 ground truth).

    The network is measured as one :class:`~repro.core.batch.BlockBatch`
    through the platform's columnar block model (cache-partitioned and
    runtime-sharded under a ``CachedPlatform``); values are bitwise identical
    to the old per-block ``measure_block`` loop.
    """
    return simulate_networks(platform, [blocks])[0]


def simulate_networks(platform, networks: Sequence[Sequence[Block]]) -> list[float]:
    """Batched :func:`simulate_network` over many networks.

    All networks' blocks flatten into one block batch (one platform call, one
    cache partition; duplicate blocks across networks are measured once under
    a caching platform), then each network's Eq.-12 sum accumulates in block
    order — the same left fold as the scalar loop, so the result is bitwise
    identical for every network.
    """
    from repro.core.blocks import measure_block_many

    networks = [list(net) for net in networks]
    flat = [b for net in networks for b in net]
    y = measure_block_many(platform, flat)
    times = y.tolist()
    out: list[float] = []
    i = 0
    for net in networks:
        t = 0.0
        for b in net:
            t += times[i] * b.repeat
            i += 1
        out.append(t)
    return out
