"""Serving launcher: batched prefill + decode with KV/SSM caches.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
      --batch 4 --prompt-len 32 --gen 16

``--estimate`` additionally prints a PR-oracle prediction of the per-token
decode step time on the TPU-v5e platform *before* anything is compiled —
the serving analogue of the advisor use-case.  ``--hub-dir`` reloads a
persisted oracle (see repro.api.EstimatorHub) instead of training one
in-process; ``--estimate-only`` skips the real run entirely.

``--serve-oracle`` turns the launcher into the estimation *service*: it
loads the hub once and serves predict / predict_networks / autotune / stats
over line-delimited JSON (``--port`` for TCP, ``--unix-socket`` for a local
socket; see :mod:`repro.serving`).  This mode is jax-free — forests are
numpy — so the server starts in milliseconds and runs anywhere:

  PYTHONPATH=src python -m repro.launch.serve --serve-oracle \
      --hub-dir runs/hub --port 7070 --warm-platforms tpu_v5e_gray
"""

from __future__ import annotations

import argparse
import functools
import time

import numpy as np

from repro.configs import get_config

# jax (and the model stack built on it) is imported lazily inside the paths
# that compile/run a real model; the oracle paths (--estimate-only,
# --serve-oracle) stay importable on a jax-free box.


@functools.lru_cache(maxsize=None)
def _serve_fns(cfg):
    """Jitted (prefill, decode step) for one config, shared across calls."""
    import jax

    from repro.models import transformer as T
    from repro.train.steps import make_serve_step

    prefill = jax.jit(lambda p, batch, c: T.forward(p, cfg, batch, c))
    return prefill, jax.jit(make_serve_step(cfg))


def generate(cfg, params, prompts: np.ndarray, gen_len: int, extras: dict | None = None):
    """Greedy generation: prefill via forward-with-cache, then decode steps.

    The prefill and every decode step end in ``block_until_ready``; their
    wall seconds (compilation included on a config's first call) go to the
    ``serve.prefill_s`` and ``serve.decode_step_s`` histograms.
    """
    import jax.numpy as jnp

    from repro.models.kvcache import init_cache
    from repro.obs.metrics import metrics as obs_metrics

    b, s = prompts.shape
    cache = init_cache(cfg, b, s + gen_len)
    if cfg.family == "audio":
        cache.pop("enc_kv")  # computed at prefill
    reg = obs_metrics()
    prefill, serve_step = _serve_fns(cfg)

    batch = {"tokens": jnp.asarray(prompts)}
    if extras:
        batch.update({k: jnp.asarray(v) for k, v in extras.items()})
    t0 = time.perf_counter()
    logits, _, cache = prefill(params, batch, cache)
    next_tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    next_tok.block_until_ready()
    reg.observe_value("serve.prefill_s", time.perf_counter() - t0)

    out = [next_tok]
    for _ in range(gen_len - 1):
        step_batch = {"tokens": out[-1][:, None]}
        t0 = time.perf_counter()
        next_tok, cache = serve_step(params, cache, step_batch)
        next_tok.block_until_ready()
        reg.observe_value("serve.decode_step_s", time.perf_counter() - t0)
        out.append(next_tok)
    return jnp.stack(out, axis=1)


def estimate_decode_step(cfg, batch: int, seq_len: int,
                         hub_dir: str | None = None, n_samples: int = 400,
                         workers: int = 1, journal_dir: str | None = None) -> float:
    """PR-oracle estimate of one decode step's time on the TPU-v5e platform.

    Loads a persisted oracle from ``hub_dir`` when one is available there,
    otherwise trains a small campaign in-process (and persists it to
    ``hub_dir`` for next time, if given).

    ``workers`` > 1 runs the campaign's measurements through the sharded
    runtime (process pool + crash-safe journal; see :mod:`repro.runtime`);
    ``journal_dir`` pins the journal location (defaults to ``hub_dir`` when a
    hub is given).  A run killed mid-campaign resumes from the journal.
    """
    from repro.api import Campaign, CampaignSpec, EstimatorHub, PerfOracle, RuntimeSpec
    from repro.core.network import decompose
    from repro.models.config import InputShape

    layer_types = ("dense", "attention_decode", "moe_gemm", "ssd_decode", "embed")
    platform_name = "tpu_v5e[gray]"
    oracle = None
    if hub_dir:
        hub = EstimatorHub(hub_dir)
        if all(hub.has(platform_name, lt) for lt in layer_types):
            oracle = PerfOracle.load(hub, platform_name, layer_types)
    if oracle is None:
        spec = CampaignSpec(
            platform="tpu_v5e",
            layer_types=layer_types,
            n_samples=n_samples,
            platform_kwargs={"knowledge": "gray", "noise": 0.001},
            hub_dir=hub_dir,
        )
        runtime = None
        if workers > 1 or journal_dir:
            from repro.checkpoint.manager import journal_path

            runtime = RuntimeSpec(
                workers=workers,
                journal_path=journal_path(journal_dir) if journal_dir else None,
            )
        campaign = Campaign(spec)
        oracle = campaign.run(runtime=runtime)
        if campaign.last_run_stats is not None:
            s = campaign.last_run_stats
            print(f"runtime: {s['measured']:.0f} measured, {s['cached']:.0f} cached, "
                  f"{s['replayed']:.0f} replayed over {s['chunks']:.0f} chunks "
                  f"({s['throughput_cfg_s']:.0f} cfg/s, workers={workers})")
    shape = InputShape(name="serve", seq_len=seq_len, global_batch=batch, kind="decode")
    blocks = decompose(cfg, shape, dp=1, tp=1)
    return oracle.predict_network(blocks)


def _metrics_reporter(server, interval_s: float):
    """Daemon loop: print a one-line metrics digest every ``interval_s``."""
    import threading

    from repro import obs

    stop = threading.Event()

    def loop() -> None:
        while not stop.wait(interval_s):
            snap = server.metrics.snapshot()
            reqs = sum(ep["requests"] for ep in snap["endpoints"].values())
            errs = sum(ep["errors"] for ep in snap["endpoints"].values())
            counters = obs.metrics().snapshot()["counters"]
            print(f"[metrics] {reqs} requests ({errs} errors), "
                  f"{snap['batches']} batches "
                  f"(mean {snap['mean_batch_size']:.1f}), "
                  f"cache {snap['gauges'].get('result_cache')}, "
                  f"counters {counters}", flush=True)

    t = threading.Thread(target=loop, name="metrics-reporter", daemon=True)
    t.start()
    return stop


def fsck_journal(args) -> int:
    """Check (and with ``--repair`` compact) a measurement journal (``--fsck``).

    Prints the :meth:`repro.runtime.MeasurementJournal.fsck` report as JSON;
    the exit code is 0 when the journal is healthy, 1 when issues were found
    (and left in place — rerun with ``--repair`` to compact them away).
    """
    import json

    from repro.checkpoint.manager import journal_path
    from repro.runtime import MeasurementJournal

    where = args.journal_dir or args.hub_dir
    if not where:
        raise SystemExit("--fsck requires --journal-dir or --hub-dir")
    journal = MeasurementJournal(journal_path(where))
    try:
        report = journal.fsck(repair=args.repair)
    finally:
        journal.close()
    print(json.dumps(report, indent=2, sort_keys=True))
    checked = report.get("after", report)
    issues = (
        checked["corrupt_lines"]
        + checked["duplicate_keys"]
        + (1 if checked["torn_tail"] else 0)
    )
    return 1 if issues else 0


def serve_oracle(args) -> None:
    """Run the oracle estimation service until interrupted (``--serve-oracle``)."""
    import contextlib
    import os

    from repro import obs
    from repro.serving import OracleServer, OracleSocketServer, ServeSpec

    if not args.hub_dir:
        raise SystemExit("--serve-oracle requires --hub-dir (a trained EstimatorHub)")
    spec = ServeSpec(
        hub_dir=args.hub_dir,
        platforms=tuple(args.warm_platforms or ()),
        window_s=args.window_ms / 1e3,
        cache_capacity=args.cache_capacity,
        predict_backend=args.predict_backend,
        max_queue=args.max_queue if args.max_queue > 0 else None,
        default_deadline_s=(
            args.deadline_ms / 1e3 if args.deadline_ms > 0 else None
        ),
    )
    server = OracleServer(spec=spec)
    sock = OracleSocketServer(
        server, host=args.host, port=args.port, unix_socket=args.unix_socket
    )
    where = sock.address if args.unix_socket else "%s:%d" % sock.address
    trace_ctx = contextlib.nullcontext()
    if args.trace_dir:
        trace_path = os.path.join(args.trace_dir, f"serve-{os.getpid()}.jsonl")
        trace_ctx = obs.tracing(trace_path)
        print(f"tracing to {trace_path} "
              f"(render: python -m repro.obs.report {trace_path})")
    reporter = None
    if args.metrics_interval and args.metrics_interval > 0:
        reporter = _metrics_reporter(server, args.metrics_interval)
    print(f"oracle server on {where} (hub: {args.hub_dir}, "
          f"platforms: {server.platforms()['hub']}, "
          f"window: {args.window_ms:.1f} ms)")
    try:
        with trace_ctx:
            sock.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if reporter is not None:
            reporter.set()
        # Graceful drain: in-flight requests are answered (bounded by
        # --drain-s) before the listening socket goes away.
        sock.close(drain_s=args.drain_s)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--estimate", action="store_true",
                    help="print a PR-oracle decode step-time estimate first")
    ap.add_argument("--estimate-only", action="store_true",
                    help="estimate and exit without compiling/running the model")
    ap.add_argument("--hub-dir", default=None,
                    help="EstimatorHub directory to reload/persist the oracle")
    ap.add_argument("--workers", type=int, default=1,
                    help="measurement worker processes for the estimate campaign "
                         "(>1 enables the sharded runtime)")
    ap.add_argument("--journal-dir", default=None,
                    help="directory for the crash-safe measurement journal "
                         "(interrupted estimate campaigns resume from it)")
    ap.add_argument("--serve-oracle", action="store_true",
                    help="serve oracle estimates over NDJSON sockets instead of "
                         "running a model (see repro.serving)")
    ap.add_argument("--host", default="127.0.0.1",
                    help="bind address for --serve-oracle TCP mode")
    ap.add_argument("--port", type=int, default=7070,
                    help="TCP port for --serve-oracle (0 = ephemeral)")
    ap.add_argument("--unix-socket", default=None,
                    help="serve on a unix socket path instead of TCP")
    ap.add_argument("--warm-platforms", nargs="*", default=None,
                    help="platforms to load eagerly at server startup")
    ap.add_argument("--window-ms", type=float, default=2.0,
                    help="admission-batching window in milliseconds")
    ap.add_argument("--cache-capacity", type=int, default=65536,
                    help="LRU result-cache capacity (entries)")
    ap.add_argument("--predict-backend", default=None,
                    choices=("numpy", "jax", "auto"),
                    help="inference engine for served oracles "
                         "(default: REPRO_PREDICT_BACKEND, else numpy)")
    ap.add_argument("--trace-dir", default=None,
                    help="write a span trace (serve-<pid>.jsonl) into this "
                         "directory; render with python -m repro.obs.report")
    ap.add_argument("--metrics-interval", type=float, default=0.0,
                    help="print a metrics digest every N seconds (0 = off)")
    ap.add_argument("--max-queue", type=int, default=8192,
                    help="admission-queue bound; overflowing requests get an "
                         "explicit overload response (0 = unbounded)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="default per-request deadline in milliseconds; "
                         "requests may override with their own deadline_ms "
                         "(0 = no deadline)")
    ap.add_argument("--drain-s", type=float, default=5.0,
                    help="graceful-shutdown drain budget: seconds to wait for "
                         "in-flight requests before closing the socket")
    ap.add_argument("--fsck", action="store_true",
                    help="check the measurement journal (torn tail, corrupt "
                         "lines, duplicate keys) and exit; nonzero on issues")
    ap.add_argument("--repair", action="store_true",
                    help="with --fsck: compact the journal to drop corruption")
    args = ap.parse_args()

    if args.fsck:
        raise SystemExit(fsck_journal(args))
    if args.serve_oracle:
        serve_oracle(args)
        return
    if not args.arch:
        ap.error("--arch is required unless --serve-oracle is given")
    cfg = get_config(args.arch)
    if args.reduced:
        from repro.models.config import reduced

        cfg = reduced(cfg)
    if args.estimate or args.estimate_only:
        t_step = estimate_decode_step(
            cfg, args.batch, args.prompt_len + args.gen, hub_dir=args.hub_dir,
            workers=args.workers, journal_dir=args.journal_dir,
        )
        print(f"oracle estimate (tpu_v5e[gray], dp=1 tp=1): "
              f"{t_step*1e3:.3f} ms/decode-step "
              f"(~{args.batch / max(t_step, 1e-12):.0f} tok/s)")
        if args.estimate_only:
            return
    import jax

    from repro.distributed import single_device_rules, use_rules
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import transformer as T

    enable_compile_cache()
    rules = single_device_rules()
    with use_rules(rules):
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        prompts = rng.integers(1, cfg.vocab, size=(args.batch, args.prompt_len)).astype(np.int32)
        extras = {}
        if cfg.family == "audio":
            extras["frames"] = rng.standard_normal(
                (args.batch, cfg.encoder_seq, cfg.d_model)
            ).astype(np.float32) * 0.1
        t0 = time.perf_counter()
        tokens = generate(cfg, params, prompts, args.gen, extras)
        dt = time.perf_counter() - t0
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)\n{np.asarray(tokens)[:2]}")


if __name__ == "__main__":
    main()
