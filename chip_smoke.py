"""Smoke run of the system's main path on one TPU chip.

    python chip_smoke.py [--seed N]

Everything runs in this one process, which owns the chip, and is built from
``--seed`` in a temporary directory (no hub, journal or artifact is read):

1. device   -- the first JAX device must be a TPU; no CPU fallback;
2. campaign -- a 500-sample dense campaign on the wall-clock platform
               (``xla_cpu``, which times on the chip) through the serial
               runtime with a journal, plus one dense layer at
               qwen2-1.5b's MLP width;
3. oracle   -- the trained oracle reloaded from the hub predicts 4096 dense
               queries and a batch of networks on the compiled (jax) path,
               checked against the numpy reference;
4. server   -- the oracle service behind a local socket answers 16 requests
               that must equal the direct oracle answers;
5. model    -- qwen2-1.5b at its published widths (random weights) prefills
               a batch of 4 x 128 tokens and decodes 16; a prefill with the
               Pallas flash-attention kernel, compiled, is compared with the
               XLA chunked attention at full depth (printed) and, cut to the
               model's first layer, held to the kernel tests' bf16 tolerance.

Numbers go to earlier lines; any failed phase exits non-zero.  The last line
of standard output is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: qwen2-1.5b's published MLP projection: d_model 1536 -> d_ff 8960
QWEN2_MLP = {"tokens": 2048, "d_in": 1536, "d_out": 8960}
N_QUERIES = 4096
N_REQUESTS = 16
#: tests/test_kernels.py's tolerance for bf16 attention outputs
BF16_TOL = {"atol": 2e-2, "rtol": 2e-2}


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    """Fail the run (and its phase) unless ``ok``."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _hist(name: str) -> tuple[int, float]:
    from repro.obs.metrics import metrics

    h = metrics().snapshot()["histograms"].get(name, {})
    return h.get("count", 0), h.get("total", 0.0)


def _counter(name: str) -> int:
    from repro.obs.metrics import metrics

    return metrics().snapshot()["counters"].get(name, 0)


def _dense_queries(space, n: int, rng):
    from repro.core.batch import ConfigBatch

    return ConfigBatch.from_columns(
        {p: rng.integers(lo, hi + 1, size=n) for p, (lo, hi) in space.ranges.items()}
    )


# ------------------------------------------------------------------ phases
def phase_device():
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r} ({dev.device_kind})"
        )
    log(f"[device] {dev.platform} {dev.device_kind!r} x{len(devices)}; "
        f"compile cache {cache_dir}")
    return dev, len(devices)


def phase_campaign(seed: int, hub_dir: str, dev):
    import numpy as np

    from repro.api import Campaign, CampaignSpec, RuntimeSpec

    campaign = Campaign(
        CampaignSpec(
            platform="xla_cpu", layer_types=("dense",), n_samples=500,
            seed=seed, hub_dir=hub_dir,
        )
    )
    platform = campaign.platform.inner
    key = platform.cache_key()
    check(f"{dev.platform}:{dev.device_kind}" in key, f"cache key {key!r} names no device")
    t0 = time.perf_counter()
    oracle = campaign.run(runtime=RuntimeSpec(workers=1))
    wall = time.perf_counter() - t0
    stats = campaign.last_run_stats
    check(stats["measured"] > 0, "campaign measured nothing")
    check(Path(hub_dir, "measurements.jsonl").is_file(), "campaign wrote no journal")
    n_shapes = _counter("xla_cpu.shapes")
    _, compile_s = _hist("xla_cpu.compile_s")
    _, timed_s = _hist("xla_cpu.timed_s")
    log(f"[campaign] {key}: {wall:.3f} s wall, {n_shapes} unique shapes measured, "
        f"compile {compile_s:.3f} s, timed runs {timed_s:.3f} s, "
        f"{stats['measured']:.0f} measured / {stats['cached']:.0f} cached")

    rng = np.random.default_rng(seed + 1)
    held_out = _dense_queries(platform.param_space("dense"), 64, rng)
    err = oracle.evaluate(campaign.platform, "dense", held_out.to_dicts())
    log(f"[campaign] held-out MAPE {err['mape']:.6f} % over 64 random dense configs")

    t_mlp = platform.measure("dense", QWEN2_MLP)
    check(np.isfinite(t_mlp) and t_mlp > 0, f"qwen2-1.5b MLP time {t_mlp}")
    flops = 2.0 * QWEN2_MLP["tokens"] * QWEN2_MLP["d_in"] * QWEN2_MLP["d_out"]
    log(f"[campaign] qwen2-1.5b MLP dense {QWEN2_MLP}: {t_mlp * 1e6:.3f} us "
        f"({flops / t_mlp / 1e12:.3f} TFLOP/s, {platform.dtype.name})")
    return platform.name


def phase_oracle(seed: int, hub_dir: str, platform_name: str, dev):
    import jax
    import numpy as np

    from repro.api import EstimatorHub, PerfOracle
    from repro.core.blocks import Block
    from repro.core.jax_predict import DEVICE_RTOL

    check(jax.default_backend() == dev.platform, "jax default backend is not the device")
    oracle = PerfOracle.load(EstimatorHub(hub_dir), platform_name, ("dense",))
    rng = np.random.default_rng(seed + 2)
    space = oracle.estimators["dense"].space
    queries = _dense_queries(space, N_QUERIES, rng)

    calls = _counter("jax.forest.calls")
    t0 = time.perf_counter()
    y_jax = oracle.predict("dense", queries, backend="jax")
    dt = time.perf_counter() - t0
    check(_counter("jax.forest.calls") == calls + 1, "compiled traversal did not run")
    y_np = oracle.predict("dense", queries, backend="numpy")
    check(y_jax.shape == y_np.shape == (N_QUERIES,), f"prediction shape {y_jax.shape}")
    check(bool(np.all(np.isfinite(y_jax))), "non-finite predictions")
    rel = float(np.max(np.abs(y_jax - y_np) / np.abs(y_np)))
    bitwise = bool(np.array_equal(y_jax, y_np))
    log(f"[oracle] {N_QUERIES} queries on the chip in {dt:.3f} s (first call, "
        f"compile included); bitwise equal to numpy: {bitwise}, "
        f"max rel diff {rel:.3e}")
    check(bitwise or rel <= DEVICE_RTOL, f"max rel diff {rel} > {DEVICE_RTOL}")

    nets = [
        [
            Block(kind="mlp", layers=(("dense", cfg), ("dense", dict(cfg, d_in=cfg["d_out"]))),
                  repeat=int(rng.integers(1, 29)))
            for cfg in _dense_queries(space, 3, rng).to_dicts()
        ]
        for _ in range(8)
    ]
    calls = _counter("jax.network.calls")
    p_jax = oracle.predict_networks(nets, backend="jax")
    check(_counter("jax.network.calls") == calls + 1, "compiled network path did not run")
    p_np = oracle.predict_networks(nets, backend="numpy")
    net_rel = float(np.max(np.abs(p_jax - p_np) / np.abs(p_np)))
    log(f"[oracle] predict_networks over {len(nets)} networks: max rel diff "
        f"{net_rel:.3e} to numpy")
    check(net_rel <= max(DEVICE_RTOL, 1e-12), f"network max rel diff {net_rel}")
    return oracle, queries, nets


def phase_server(hub_dir: str, platform_name: str, oracle, queries, nets):
    import numpy as np

    from repro.serving import OracleClient, OracleServer, OracleSocketServer, ServeSpec

    server = OracleServer(spec=ServeSpec(hub_dir=hub_dir, predict_backend="jax"))
    sock = OracleSocketServer(server, host="127.0.0.1", port=0).start()
    try:
        client = OracleClient(address=sock.address)
        try:
            check(client.ping(), "no pong")
            rows = queries.to_dicts()
            per = len(rows) // (N_REQUESTS - 1)
            for i in range(N_REQUESTS - 1):
                part = rows[i * per:(i + 1) * per]
                served = np.asarray(client.predict(platform_name, "dense", part))
                direct = oracle.predict("dense", part, backend="jax")
                check(np.array_equal(served, direct), f"served request {i} differs")
            served_n = np.asarray(client.predict_networks(platform_name, nets))
            direct_n = oracle.predict_networks(nets, backend="jax")
            check(np.array_equal(served_n, direct_n), "served networks differ")
            stats = client.stats()
        finally:
            client.close()
    finally:
        sock.close()
    endpoints = stats["metrics"]["endpoints"]
    errors = sum(ep["errors"] for ep in endpoints.values())
    requests = sum(ep["requests"] for ep in endpoints.values())
    check(errors == 0, f"{errors} server errors")
    log(f"[server] {N_REQUESTS} requests over 127.0.0.1 equal the direct answers; "
        f"{requests} served, {errors} errors")


def phase_model(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.distributed import single_device_rules, use_rules
    from repro.launch.serve import generate
    from repro.models import transformer as T
    from repro.models.kvcache import init_cache

    cfg = get_config("qwen2-1.5b")
    batch, prompt_len, gen = 4, 128, 16
    with use_rules(single_device_rules()):
        t0 = time.perf_counter()
        params = jax.jit(T.init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))
        n_params = sum(x.size for x in jax.tree.leaves(params))
        jax.block_until_ready(params)
        log(f"[model] qwen2-1.5b {n_params} params (f32) initialised in "
            f"{time.perf_counter() - t0:.3f} s")
        prompts = np.random.default_rng(seed).integers(
            1, cfg.vocab, size=(batch, prompt_len)
        ).astype(np.int32)

        for run in ("first (compile included)", "second"):
            n_p, s_p = _hist("serve.prefill_s")
            n_d, s_d = _hist("serve.decode_step_s")
            tokens = np.asarray(generate(cfg, params, prompts, gen))
            n_p2, s_p2 = _hist("serve.prefill_s")
            n_d2, s_d2 = _hist("serve.decode_step_s")
            check(tokens.shape == (batch, gen), f"generated shape {tokens.shape}")
            check(bool(((tokens >= 0) & (tokens < cfg.vocab)).all()), "token out of vocab")
            log(f"[model] generate {run}: prefill {batch}x{prompt_len} "
                f"{s_p2 - s_p:.6f} s, {n_d2 - n_d} decode steps "
                f"{(s_d2 - s_d) / max(1, n_d2 - n_d):.6f} s/step")

        tok = {"tokens": jnp.asarray(prompts)}
        cache = init_cache(cfg, batch, prompt_len + gen)
        ref, plain = prefill_logits(params, cfg, "xla_chunked", tok, cache)
        got, kernel = prefill_logits(params, cfg, "flash_pallas", tok, cache)
        check(kernel and not plain, "flash_pallas must run the compiled kernel")
        log(f"[model] {cfg.n_layers} layers: {logits_diff(got, ref)} (information: "
            "bf16 rounding differences compound over the layers)")
        del ref, got
        # The kernel tests' tolerance is for one attention op: hold one
        # full-width layer (the model's first) to it.
        one = dataclasses.replace(cfg, n_layers=1)
        first = dict(params, layers=jax.tree.map(lambda a: a[:1], params["layers"]))
        cache = init_cache(one, batch, prompt_len + gen)
        ref, _ = prefill_logits(first, one, "xla_chunked", tok, cache)
        got, kernel = prefill_logits(first, one, "flash_pallas", tok, cache)
        check(kernel, "flash_pallas must run the compiled kernel")
    log(f"[model] 1 layer: {logits_diff(got, ref)}")
    check_logits(got, ref)


def prefill_logits(params, cfg, impl: str, tokens: dict, cache):
    """One prefill's logits with ``impl`` attention, and whether a kernel ran."""
    import jax

    from repro.models import transformer as T

    c = dataclasses.replace(cfg, attention_impl=impl)
    compiled = jax.jit(lambda p, b, k: T.forward(p, c, b, k)[0]).lower(
        params, tokens, cache
    ).compile()
    return compiled(params, tokens, cache), "tpu_custom_call" in compiled.as_text()


def logits_diff(got, ref) -> str:
    import jax.numpy as jnp

    return (f"flash_pallas prefill vs xla_chunked max |diff| "
            f"{float(jnp.max(jnp.abs(got - ref))):.6f} over logits of max |x| "
            f"{float(jnp.max(jnp.abs(ref))):.6f}")


def check_logits(got, ref) -> None:
    """Hold the logits to tests/test_kernels.py's bf16 tolerance."""
    import jax.numpy as jnp

    excess = float(jnp.max(jnp.abs(got - ref) - BF16_TOL["rtol"] * jnp.abs(ref)))
    check(bool(jnp.all(jnp.isfinite(got))), "non-finite flash_pallas logits")
    check(excess <= BF16_TOL["atol"],
          f"flash_pallas logits off by {excess} beyond rtol {BF16_TOL['rtol']}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    t_start = time.perf_counter()
    dev, count = phase_device()
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as tmp:
        hub_dir = str(Path(tmp) / "hub")
        t0 = time.perf_counter()
        name = phase_campaign(args.seed, hub_dir, dev)
        log(f"[phase] campaign {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        oracle, queries, nets = phase_oracle(args.seed, hub_dir, name, dev)
        log(f"[phase] oracle {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        phase_server(hub_dir, name, oracle, queries, nets)
        log(f"[phase] server {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    phase_model(args.seed)
    log(f"[phase] model {time.perf_counter() - t0:.3f} s")
    log(f"[total] {time.perf_counter() - t_start:.3f} s")
    print(json.dumps(
        {"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                "count": count}}
    ), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
