"""Open-loop driver of the oracle service behind a local socket.

The service (``OracleServer`` behind ``OracleSocketServer`` on 127.0.0.1,
jax predict path) runs in this process, which owns the chip; the load comes
from ``bench/serve_client.py`` in a child process that never imports jax.
Each request is one ``predict`` of one config; keys are Zipf-distributed over
a universe of distinct configs of the configuration's layer types, arrivals
are Poisson at the mix's fixed rate.  Set-up compiles every row bucket the
admission batcher can form and fills the result cache with the hottest keys
(coldest first), as a long-running service would hold them.

Traffic parameters: ``rate_per_s``, ``keys``, ``zipf_theta``,
``connections``, ``prefill_keys``.  Configuration (``serve``): the
``ServeSpec`` fields the service runs with.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

from bench import estimators
from bench.common import BENCH, CHECKOUT
from bench.generate import KeyUniverse, uniform_columns
from bench.reference import max_rel_gap
from repro.core.batch import ConfigBatch
from repro.serving import OracleServer, OracleSocketServer, ServeSpec


def _bucket_sizes(max_batch: int) -> list[int]:
    sizes, b = [], 64
    while b < max_batch:
        sizes.append(b)
        b *= 2
    return sizes + [b]


def setup(run, log) -> None:
    cell = run.cell
    hub, platform, forests = estimators.build(cell)
    spaces = cell.config["layer_types"]
    types = list(spaces)
    spec = ServeSpec(hub_dir=str(hub), platforms=(platform,), predict_backend="jax",
                     **cell.config["serve"])
    # One jitted traversal serves every oracle with these forests' shapes, so
    # predicting each bucket here compiles what the service will run.
    warm = estimators.load_oracle(hub, platform)
    rng = np.random.default_rng(0)
    for lt in types:
        for b in _bucket_sizes(spec.max_batch):
            warm.predict(lt, ConfigBatch.from_columns(uniform_columns(spaces[lt], b, rng)))
    del warm
    server = OracleServer(spec=spec)
    sock = OracleSocketServer(server, host="127.0.0.1", port=0).start()
    run.state.update(hub=hub, forests=forests, server=server, sock=sock, types=types)

    traffic = cell.traffic
    universe = KeyUniverse(spaces, types, int(traffic["keys"]), run.seed)
    run.state["universe"] = universe
    hottest = universe.rank_to_key[: int(traffic["prefill_keys"])][::-1]
    for lt in types:
        cfgs = [universe.config(k)[1] for k in hottest.tolist()
                if universe.types[k % len(types)] == lt]
        for a in range(0, len(cfgs), spec.max_batch):
            resp = server.handle({"op": "predict", "platform": platform, "layer_type": lt,
                                  "configs": cfgs[a:a + spec.max_batch]})
            if not resp["ok"]:
                raise RuntimeError(f"prefill failed: {resp['error']}")

    tmp = tempfile.mkdtemp(prefix="bench-serve-")
    mix = f"{tmp}/traffic.json"
    with open(mix, "w") as f:
        json.dump(traffic, f)
    out = f"{tmp}/client.json"
    host, port = sock.address
    client = subprocess.Popen(
        [sys.executable, str(BENCH / "serve_client.py"), "--host", host, "--port", str(port),
         "--seed", str(run.seed), "--seconds", str(run.window_s),
         "--config", str(CHECKOUT / cell.config_entry["file"]), "--traffic", mix,
         "--platform", platform, "--out", out],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    run.state.update(client=client, out=out, tmp=tmp)
    if client.stdout.readline().strip() != "ready":
        client.kill()
        client.wait()
        raise RuntimeError("serving client did not start")
    log(f"[setup] service on {host}:{port}, client ready")


def _stats(server) -> dict:
    return server.handle({"op": "stats"})["result"]


def window(run, seconds: float) -> dict:
    st = run.state
    server, client = st["server"], st["client"]
    before = _stats(server)
    t0 = time.perf_counter()
    with run.span("bench.serve"):
        client.stdin.write("go\n")
        client.stdin.flush()
        line = client.stdout.readline().strip()
    elapsed = time.perf_counter() - t0
    client.wait(timeout=60)
    if line != "done":
        raise RuntimeError(f"serving client ended without its record ({line!r})")
    after = _stats(server)
    with open(st["out"]) as f:
        rec = json.load(f)
    st["record"] = rec
    run.stats["server_before"] = before
    run.stats["server_after"] = after
    return {"attempted": rec["attempted"], "failed": rec["failed"],
            "e2e": {"serve_p99_ms": rec["p99_ms"]},
            "info": {k: rec[k] for k in ("p50_ms", "p99_ms", "max_ms", "p99_ms_by_half",
                                         "late_p99_ms", "late_max_ms", "lost", "window_s")}
            | {"window_wall_s": elapsed, "answered_keys": len(rec["answers"])}}


def release(run) -> None:
    st = run.state
    client = st.pop("client", None)
    if client is not None and client.poll() is None:
        client.kill()
        client.wait()
    sock = st.pop("sock", None)
    if sock is not None:
        sock.close()
    server = st.pop("server", None)
    if server is not None:
        server.close()
    shutil.rmtree(st.get("tmp", ""), ignore_errors=True)
    shutil.rmtree(st.get("hub", ""), ignore_errors=True)


def gap(run, control: bool = False) -> float:
    """Widest relative gap of every served answer from the float64 reference;
    with ``control`` the answers are the reference's, in float32."""
    st = run.state
    types = st["types"]
    universe = st["universe"]
    forests = st["forests"]
    answers = {int(k): v for k, v in st["record"]["answers"].items()}
    worst = 0.0
    for lt in types:
        keys = [k for k in answers if universe.types[k % len(types)] == lt]
        if not keys:
            continue
        cfgs = [universe.config(k)[1] for k in keys]
        cols = {p: np.array([c[p] for c in cfgs], dtype=np.int64) for p in cfgs[0]}
        ref, _ = forests[lt].predict(cols)
        got = forests[lt].predict(cols, np.float32)[0] if control else [answers[k] for k in keys]
        worst = max(worst, max_rel_gap(got, ref))
    return worst


def check(run, log) -> list[tuple[str, float, float]]:
    rec = run.state["record"]
    c = run.delta["counters"]
    return [
        ("max_rel_gap", gap(run), float(run.cell.config["limits"]["max_rel_gap"])),
        ("lost_requests", float(rec["lost"]), 0.0),
        ("inconsistent_answers", float(rec["inconsistent"]), 0.0),
        ("window_compiles", float(c.get("jax.forest.traces", 0)), 0.0),
    ]


def control(run, log) -> dict:
    return {"max_rel_gap": gap(run, control=True)}
