"""Open-loop client of the oracle service, run as a child of ``bench/run.py``.

    python bench/serve_client.py --port P --seed N --seconds S \\
        --config <config.json> --traffic <mix.json> --platform NAME --out <file>

It never imports jax (the parent owns the chip).  It builds the mix's key
universe and schedule from the seed, opens the mix's connections, answers
``ready`` on standard output, and waits for ``go`` on standard input.  Then
it sends each request when it is due, whether or not earlier ones were
answered, on the first idle connection (a request that finds none waits,
and that wait counts in its latency: every latency runs from the due time).
Once every request is answered, or ``--wait`` seconds after the last was
due, it writes its record to ``--out`` and answers ``done``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench.common import percentile  # noqa: E402
from bench.generate import KeyUniverse, serve_schedule  # noqa: E402


def build(args):
    with open(args.config) as f:
        config = json.load(f)
    with open(args.traffic) as f:
        traffic = json.load(f)
    types = list(config["layer_types"])
    universe = KeyUniverse(config["layer_types"], types, int(traffic["keys"]), args.seed)
    due, keys = serve_schedule(traffic, universe, args.seed, args.seconds)
    lines = []
    for i, key in enumerate(keys.tolist()):
        lt, cfg = universe.config(key)
        lines.append(json.dumps({"id": i, "op": "predict", "platform": args.platform,
                                 "layer_type": lt, "configs": [cfg]},
                                separators=(",", ":")).encode() + b"\n")
    return traffic, due.tolist(), keys.tolist(), lines


async def serve_window(args, traffic, due, keys, lines) -> dict:
    loop = asyncio.get_running_loop()
    conns = []
    for _ in range(int(traffic["connections"])):
        reader, writer = await asyncio.open_connection(args.host, args.port)
        writer.write(b'{"id":-1,"op":"ping"}\n')
        await writer.drain()
        await reader.readline()
        conns.append((reader, writer))
    idle: asyncio.Queue = asyncio.Queue()
    for c in conns:
        idle.put_nowait(c)
    print("ready", flush=True)
    go = await loop.run_in_executor(None, sys.stdin.readline)
    if go.strip() != "go":
        raise SystemExit(f"serve_client: expected 'go', got {go!r}")

    n = len(due)
    latency = [None] * n
    late = [0.0] * n
    answers: dict[int, float] = {}
    failed = inconsistent = 0
    t0 = time.perf_counter()

    async def one(i: int) -> None:
        nonlocal failed, inconsistent
        late[i] = time.perf_counter() - (t0 + due[i])
        reader, writer = await idle.get()
        try:
            writer.write(lines[i])
            await writer.drain()
            resp = json.loads(await reader.readline())
        finally:
            idle.put_nowait((reader, writer))
        if not resp.get("ok") or resp.get("id") != i:
            failed += 1
            latency[i] = math.inf
            return
        latency[i] = time.perf_counter() - (t0 + due[i])
        value = resp["result"][0]
        prev = answers.setdefault(keys[i], value)
        if prev != value:
            inconsistent += 1

    tasks = []
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        while i < n and due[i] <= now:
            tasks.append(asyncio.ensure_future(one(i)))
            i += 1
        if i < n:
            await asyncio.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
    done, pending = await asyncio.wait(tasks, timeout=args.wait) if tasks else (set(), set())
    for t in pending:
        t.cancel()
    for t in done:
        t.exception()  # a request whose connection broke stays unanswered (lost)
    elapsed = time.perf_counter() - t0
    for _, writer in conns:
        writer.close()
    lost = sum(1 for v in latency if v is None)
    # A refused, failed or lost request misses every latency limit: it counts
    # as late as the longest wait.
    cap = args.seconds + args.wait
    lat_in_order = [cap if v is None or v == math.inf else v for v in latency]
    lat = sorted(lat_in_order)
    # a backlog that grows through the window shows as a later half slower
    # than the earlier one
    halves = [sorted(lat_in_order[: n // 2]), sorted(lat_in_order[n // 2:])]
    return {
        "attempted": n,
        "failed": failed,
        "lost": lost,
        "inconsistent": inconsistent,
        "window_s": elapsed,
        "p50_ms": percentile(lat, 50) * 1e3,
        "p99_ms": percentile(lat, 99) * 1e3,
        "max_ms": lat[-1] * 1e3,
        "p99_ms_by_half": [percentile(h, 99) * 1e3 if h else None for h in halves],
        "late_p99_ms": percentile(sorted(late), 99) * 1e3,
        "late_max_ms": max(late) * 1e3,
        "answers": {str(k): v for k, v in answers.items()},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--wait", type=float, default=60.0)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--platform", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    traffic, due, keys, lines = build(args)
    record = asyncio.run(serve_window(args, traffic, due, keys, lines))
    with open(args.out, "w") as f:
        json.dump(record, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
