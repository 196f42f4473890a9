"""Oracle serving layer: coalescing, caching, metrics, transport.

The serving contract under test:

* **bitwise parity** — a served answer (coalesced, cached, or over a
  socket) is identical to a direct ``PerfOracle`` call, because forest
  predictions are row-independent and cached values are the exact float64
  bits the forest produced (JSON round-trips doubles exactly);
* **coalescing** — concurrent requests share forest passes (the batch-size
  histogram proves it) without changing any answer;
* **robustness** — malformed requests, unknown ops/platforms and bad
  payloads produce error *responses*, never a dead server;
* **warm restart** — a new server over the same hub reloads persisted
  estimators and answers identically, without retraining.
"""

from __future__ import annotations

import json
import socket
import threading
import time

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.api import Campaign, CampaignSpec, EstimatorHub, PerfOracle
from repro.core.blocks import Block
from repro.serving import (
    AdmissionBatcher,
    DeadlineExceeded,
    MetricsRegistry,
    OracleClient,
    OracleServer,
    OracleSocketServer,
    OverloadError,
    ResultCache,
    ServeSpec,
    ServingError,
    block_payload,
    parse_block,
)

FAST_FOREST = {"n_estimators": 6, "max_depth": 10}
PLATFORM = "stepped_sim"


@pytest.fixture(scope="module")
def oracle() -> PerfOracle:
    spec = CampaignSpec(
        platform=PLATFORM,
        layer_types=("toy",),
        n_samples=80,
        seed=0,
        forest_kwargs=FAST_FOREST,
    )
    return Campaign(spec).run()


def _server(oracle, **spec_kwargs) -> OracleServer:
    spec_kwargs.setdefault("window_s", 0.001)
    return OracleServer(oracles={PLATFORM: oracle}, spec=ServeSpec(**spec_kwargs))


def _configs(n: int, offset: int = 0) -> list[dict]:
    return [
        {"a": (i * 7 + offset) % 64 + 1, "b": (i * 3 + offset) % 32 + 1}
        for i in range(n)
    ]


def _networks() -> list[list[Block]]:
    return [
        [
            Block(kind="k", layers=(("toy", {"a": 4, "b": 2}), ("toy", {"a": 8, "b": 4})), repeat=3),
            Block(kind="k", layers=(("toy", {"a": 16, "b": 8}),), collective_bytes=128.0),
        ],
        [Block(kind="k", layers=(("toy", {"a": 32, "b": 16}),))],
    ]


# --------------------------------------------------------------------- parity
class TestServedParity:
    def test_predict_matches_direct_oracle_bitwise(self, oracle):
        cfgs = _configs(37)
        direct = oracle.predict("toy", cfgs)
        with _server(oracle) as server:
            client = OracleClient(server=server)
            served = client.predict(PLATFORM, "toy", cfgs)
            # and again: the second pass is all cache hits — still identical
            cached = client.predict(PLATFORM, "toy", cfgs)
        assert served == [float(v) for v in direct]
        assert cached == served
        assert server.cache.stats()["hits"] >= len(cfgs)

    def test_predict_networks_matches_direct_oracle_bitwise(self, oracle):
        nets = _networks()
        direct = oracle.predict_networks(nets)
        with _server(oracle) as server:
            client = OracleClient(server=server)
            served = client.predict_networks(PLATFORM, nets)
            again = client.predict_networks(PLATFORM, nets)
        assert served == [float(v) for v in direct]
        assert again == served

    def test_predict_many_slices_match_standalone_predicts(self, oracle):
        items = [("toy", _configs(5)), ("toy", _configs(9, offset=3))]
        merged = oracle.predict_many(items)
        for (lt, cfgs), got in zip(items, merged):
            assert np.array_equal(got, oracle.predict(lt, cfgs))

    def test_socket_round_trip_is_bitwise_identical(self, oracle):
        cfgs = _configs(11)
        nets = _networks()
        with _server(oracle) as server:
            inproc = OracleClient(server=server)
            with OracleSocketServer(server, port=0).start() as sock:
                remote = OracleClient(address=sock.address)
                assert remote.predict(PLATFORM, "toy", cfgs) == inproc.predict(
                    PLATFORM, "toy", cfgs
                )
                assert remote.predict_networks(PLATFORM, nets) == inproc.predict_networks(
                    PLATFORM, nets
                )
                remote.close()

    def test_autotune_rides_network_coalescing_with_direct_parity(self):
        from repro.configs import get_config
        from repro.core.advisor import autotune
        from repro.models.config import SHAPES

        class _Stub:
            def predict_one(self, cfg) -> float:
                return 1e-6 * float(sum(v for v in cfg.values()))

        class _StubMap(dict):
            def __missing__(self, key):
                est = self[key] = _Stub()
                return est

        stub_oracle = PerfOracle(estimators=_StubMap())
        cfg = get_config("qwen2-1.5b")
        shape = SHAPES["train_4k"]
        direct = autotune(stub_oracle, cfg, shape, chips=16)
        with _server(stub_oracle) as server:
            client = OracleClient(server=server)
            served = client.autotune(
                PLATFORM, "qwen2-1.5b",
                shape_name=shape.name, seq_len=shape.seq_len,
                batch=shape.global_batch, kind=shape.kind, chips=16,
            )
        assert len(served) == len(direct)
        for (cand, seconds), row in zip(direct, served):
            assert (cand.dp, cand.tp, cand.microbatches) == (
                row["dp"], row["tp"], row["microbatches"]
            )
            if np.isfinite(seconds):
                assert row["seconds"] == seconds
            else:
                assert row["seconds"] is None


# ------------------------------------------------------------------ batching
class TestAdmissionBatcher:
    def test_concurrent_submits_coalesce_into_one_process_call(self):
        calls: list[int] = []
        release = threading.Event()

        def process(payloads):
            calls.append(len(payloads))
            return [p * 2 for p in payloads]

        with AdmissionBatcher(process, window_s=0.05) as batcher:
            results: dict[int, int] = {}
            barrier = threading.Barrier(8)

            def worker(i):
                barrier.wait()
                results[i] = batcher.submit(i)

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            release.set()
        assert results == {i: i * 2 for i in range(8)}
        assert max(calls) > 1  # at least one batch actually coalesced

    def test_per_item_exception_poisons_only_its_waiter(self):
        def process(payloads):
            return [
                ValueError("poisoned") if p == "bad" else p for p in payloads
            ]

        with AdmissionBatcher(process, window_s=0.001) as batcher:
            assert batcher.submit("ok") == "ok"
            with pytest.raises(ValueError, match="poisoned"):
                batcher.submit("bad")
            assert batcher.submit("still ok") == "still ok"

    def test_submit_after_close_raises(self):
        batcher = AdmissionBatcher(lambda ps: ps, window_s=0.001)
        batcher.close()
        with pytest.raises(ServingError):
            batcher.submit(1)


# ----------------------------------------------------------- overload control
class TestOverloadControl:
    def test_queue_overflow_is_an_explicit_answer_never_a_silent_drop(self):
        """Every submit is accounted for: answered with a result or answered
        with OverloadError — admitted + overloaded == issued."""
        entered = threading.Event()
        release = threading.Event()

        def process(payloads):
            entered.set()
            release.wait(timeout=10.0)
            return [p * 2 for p in payloads]

        batcher = AdmissionBatcher(process, window_s=0.001, max_queue=2)
        try:
            results: dict[int, int] = {}
            overloads: list[int] = []

            def plug():
                results[0] = batcher.submit(0)

            plug_thread = threading.Thread(target=plug)
            plug_thread.start()
            assert entered.wait(timeout=10.0)  # dispatcher is busy in process

            def worker(i):
                try:
                    results[i] = batcher.submit(i)
                except OverloadError:
                    overloads.append(i)

            threads = [threading.Thread(target=worker, args=(i,)) for i in (1, 2, 3, 4)]
            for t in threads:
                t.start()
            # the queued (non-overloaded) submits are parked in the queue
            deadline = time.perf_counter() + 10.0
            while len(overloads) < 2 and time.perf_counter() < deadline:
                time.sleep(0.005)
            release.set()
            plug_thread.join(timeout=10.0)
            for t in threads:
                t.join(timeout=10.0)
            assert len(results) + len(overloads) == 5  # nothing vanished
            assert len(overloads) == 2  # queue bound of 2 admitted exactly 2
            assert all(results[i] == i * 2 for i in results)
        finally:
            release.set()
            batcher.close()

    def test_server_marks_overload_responses(self, oracle):
        with _server(oracle) as server:
            def submit_overloaded(payload, deadline_s=None):
                raise OverloadError("queue full")

            server.batcher.submit = submit_overloaded
            response = server.handle(
                {"id": 1, "op": "predict", "platform": PLATFORM,
                 "layer_type": "toy", "configs": _configs(64)}
            )
        assert response["ok"] is False
        assert response["overloaded"] is True
        assert "OverloadError" in response["error"]

    def test_client_sees_overload_as_serving_error(self, oracle):
        with _server(oracle, max_queue=1) as server:
            entered = threading.Event()
            release = threading.Event()
            real_process = server.batcher.process

            def slow_process(payloads):
                entered.set()
                release.wait(timeout=10.0)
                return real_process(payloads)

            server.batcher.process = slow_process
            client = OracleClient(server=server)
            try:
                ok: list[list] = []
                errors: list[Exception] = []

                def worker(offset):
                    try:
                        ok.append(client.predict(PLATFORM, "toy", _configs(4, offset)))
                    except ServingError as exc:
                        errors.append(exc)

                threads = [
                    threading.Thread(target=worker, args=(o,)) for o in range(6)
                ]
                for t in threads:
                    t.start()
                entered.wait(timeout=10.0)
                deadline = time.perf_counter() + 10.0
                while len(ok) + len(errors) < 5 and time.perf_counter() < deadline:
                    time.sleep(0.005)
            finally:
                release.set()
            for t in threads:
                t.join(timeout=10.0)
            assert len(ok) + len(errors) == 6
            assert any("OverloadError" in str(e) for e in errors)


# ------------------------------------------------------------------ deadlines
class TestDeadlines:
    def test_submit_deadline_raises_typed_error(self):
        release = threading.Event()

        def process(payloads):
            release.wait(timeout=10.0)
            return list(payloads)

        batcher = AdmissionBatcher(process, window_s=0.001)
        try:
            t0 = time.perf_counter()
            with pytest.raises(DeadlineExceeded):
                batcher.submit("x", deadline_s=0.05)
            assert time.perf_counter() - t0 < 5.0  # did not wait forever
        finally:
            release.set()
            batcher.close()

    def test_expired_queue_entries_are_answered_not_dropped(self):
        """An entry whose deadline lapses while queued is answered with
        DeadlineExceeded at drain time — it never consumes a batch slot and
        never silently vanishes."""
        entered = threading.Event()
        release = threading.Event()
        processed: list = []

        def process(payloads):
            if not entered.is_set():
                entered.set()
                release.wait(timeout=10.0)
            processed.extend(payloads)
            return list(payloads)

        batcher = AdmissionBatcher(process, window_s=0.001)
        try:
            plug = threading.Thread(target=batcher.submit, args=("plug",))
            plug.start()
            assert entered.wait(timeout=10.0)
            with pytest.raises(DeadlineExceeded):
                batcher.submit("doomed", deadline_s=0.05)  # expires while queued
            release.set()
            plug.join(timeout=10.0)
            assert batcher.submit("after") == "after"
            assert "doomed" not in processed  # expired entry skipped dispatch
        finally:
            release.set()
            batcher.close()

    def test_server_marks_deadline_responses_and_validates_field(self, oracle):
        with _server(oracle) as server:
            def submit_expired(payload, deadline_s=None):
                raise DeadlineExceeded("too slow")

            server.batcher.submit = submit_expired
            response = server.handle(
                {"id": 2, "op": "predict", "platform": PLATFORM,
                 "layer_type": "toy", "configs": _configs(8), "deadline_ms": 50}
            )
            assert response["ok"] is False
            assert response["deadline_exceeded"] is True
            bad = server.handle(
                {"id": 3, "op": "predict", "platform": PLATFORM,
                 "layer_type": "toy", "configs": _configs(2), "deadline_ms": -1}
            )
            assert bad["ok"] is False and "deadline_ms" in bad["error"]

    def test_generous_deadline_answers_normally_and_bitwise(self, oracle):
        cfgs = _configs(9)
        direct = [float(v) for v in oracle.predict("toy", cfgs)]
        with _server(oracle, default_deadline_s=30.0) as server:
            response = server.handle(
                {"id": 4, "op": "predict", "platform": PLATFORM,
                 "layer_type": "toy", "configs": cfgs, "deadline_ms": 30000}
            )
        assert response["ok"] is True
        assert response["result"] == direct


# -------------------------------------------------------------- graceful drain
class TestGracefulDrain:
    def test_drain_answers_inflight_then_rejects_new_requests(self, oracle):
        entered = threading.Event()
        release = threading.Event()
        real_process = None
        with _server(oracle) as server:
            real_process = server.batcher.process

            def slow_process(payloads):
                entered.set()
                release.wait(timeout=10.0)
                return real_process(payloads)

            server.batcher.process = slow_process
            answers: list[dict] = []

            def inflight():
                answers.append(server.handle(
                    {"id": 5, "op": "predict", "platform": PLATFORM,
                     "layer_type": "toy", "configs": _configs(3)}
                ))

            t = threading.Thread(target=inflight)
            t.start()
            assert entered.wait(timeout=10.0)
            # drain times out while the request is stuck in the batcher...
            assert server.drain(timeout_s=0.05) is False
            # ...new work is already rejected with an explicit flag...
            rejected = server.handle({"id": 6, "op": "ping"})
            assert rejected["ok"] is False and rejected["draining"] is True
            # ...and once released, the in-flight waiter is answered.
            release.set()
            t.join(timeout=10.0)
            assert server.drain(timeout_s=10.0) is True
            assert answers and answers[0]["ok"] is True

    def test_socket_close_answers_inflight_before_closing(self, oracle):
        cfgs = _configs(4)
        direct = [float(v) for v in oracle.predict("toy", cfgs)]
        server = _server(oracle)
        entered = threading.Event()
        release = threading.Event()
        real_process = server.batcher.process

        def slow_process(payloads):
            entered.set()
            release.wait(timeout=10.0)
            return real_process(payloads)

        server.batcher.process = slow_process
        sock = OracleSocketServer(server, port=0).start()
        client = OracleClient(address=sock.address)
        results: list = []
        t = threading.Thread(
            target=lambda: results.append(client.predict(PLATFORM, "toy", cfgs))
        )
        t.start()
        assert entered.wait(timeout=10.0)
        release_timer = threading.Timer(0.2, release.set)
        release_timer.start()
        sock.close(drain_s=10.0)  # must wait for the in-flight answer
        t.join(timeout=10.0)
        client.close()
        assert results == [direct]


# ----------------------------------------------------------- client reconnect
class TestClientReconnect:
    def test_client_survives_a_server_restart(self, oracle):
        cfgs = _configs(6)
        direct = [float(v) for v in oracle.predict("toy", cfgs)]
        first = OracleSocketServer(_server(oracle), port=0).start()
        host, port = first.address
        client = OracleClient(address=(host, port))
        assert client.predict(PLATFORM, "toy", cfgs) == direct
        first.close(drain_s=0.0)
        # restart on the same port (allow_reuse_address) with fresh state
        second = OracleSocketServer(_server(oracle), host=host, port=port).start()
        try:
            # the old connection is dead; the client reconnects once and resends
            assert client.predict(PLATFORM, "toy", cfgs) == direct
            assert client.ping() is True
        finally:
            client.close()
            second.close(drain_s=0.0)

    def test_permanent_server_death_is_a_serving_error(self, oracle):
        sock = OracleSocketServer(_server(oracle), port=0).start()
        client = OracleClient(address=sock.address)
        assert client.ping() is True
        sock.close(drain_s=0.0)
        with pytest.raises(ServingError):  # never a raw OSError
            client.ping()
        client.close()

    def test_closed_client_raises_cleanly(self, oracle):
        with _server(oracle) as server:
            with OracleSocketServer(server, port=0).start() as sock:
                client = OracleClient(address=sock.address)
                client.close()
                with pytest.raises(ServingError, match="closed"):
                    client.ping()


# --------------------------------------------------------------------- cache
class TestResultCache:
    def test_lru_eviction_and_hit_accounting(self):
        cache = ResultCache(capacity=3)
        cache.put_many(["a", "b", "c"], [1.0, 2.0, 3.0])
        assert cache.get_many(["a", "b"]) == [1.0, 2.0]  # refreshes a, b
        cache.put_many(["d"], [4.0])  # evicts "c" (least recently used)
        assert cache.get_many(["c"]) == [None]
        assert cache.get_many(["a", "d"]) == [1.0, 4.0]
        stats = cache.stats()
        assert stats["size"] == 3 and stats["evictions"] == 1
        assert stats["hits"] == 4 and stats["misses"] == 1
        assert stats["hit_rate"] == 4 / 5

    def test_none_keys_are_never_stored(self):
        cache = ResultCache(capacity=4)
        cache.put_many([None, "x"], [1.0, 2.0])
        assert len(cache) == 1
        assert cache.get_many([None]) == [None]
        assert cache.stats()["misses"] == 1

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            ResultCache(capacity=0)

    def test_server_eviction_still_answers_correctly(self, oracle):
        cfgs = _configs(16)
        direct = [float(v) for v in oracle.predict("toy", cfgs)]
        with _server(oracle, cache_capacity=4) as server:
            client = OracleClient(server=server)
            for _ in range(3):  # repeated sweeps churn the tiny cache
                assert client.predict(PLATFORM, "toy", cfgs) == direct
            assert server.cache.stats()["evictions"] > 0


# ------------------------------------------------------------------- metrics
class TestMetrics:
    def test_registry_reports_percentiles_and_batches(self):
        reg = MetricsRegistry(window=16)
        for i in range(10):
            reg.observe("predict", latency_s=0.001 * (i + 1), items=2)
        reg.observe("predict", latency_s=0.5, error=True)
        reg.observe_batch(3)
        reg.observe_batch(5)
        snap = reg.snapshot()
        ep = snap["endpoints"]["predict"]
        assert ep["requests"] == 11 and ep["errors"] == 1 and ep["items"] == 21
        assert ep["p50_ms"] == pytest.approx(5.5)
        assert ep["p99_ms"] <= 10.0  # the error latency was not recorded
        assert snap["batches"] == 2 and snap["mean_batch_size"] == 4.0
        assert snap["batch_size_hist"] == {"4": 1, "8": 1}

    def test_server_stats_endpoint_shape(self, oracle):
        with _server(oracle) as server:
            client = OracleClient(server=server)
            client.predict(PLATFORM, "toy", _configs(4))
            stats = client.stats()
        assert stats["platforms"]["loaded"] == [PLATFORM]
        assert set(stats["result_cache"]) >= {"hits", "misses", "hit_rate", "evictions"}
        ep = stats["metrics"]["endpoints"]["predict"]
        for field in ("requests", "errors", "items", "requests_per_s",
                      "items_per_s", "p50_ms", "p95_ms", "p99_ms"):
            assert field in ep
        assert stats["metrics"]["batches"] >= 1

    def test_socket_stats_count_what_was_served(self, oracle):
        """Over a real socket: ping, platforms, cold and cached predicts and a
        network estimate equal the direct oracle, and ``stats`` counts them."""
        cfgs = _configs(4)
        network = _networks()[0]
        expected = [float(v) for v in oracle.predict("toy", cfgs)]
        expected_net = float(oracle.predict_network(network))
        with _server(oracle) as server:
            with OracleSocketServer(server, port=0).start() as sock:
                with OracleClient(address=sock.address) as client:
                    assert client.ping()
                    assert PLATFORM in client.platforms()["loaded"]
                    assert client.predict(PLATFORM, "toy", cfgs) == expected
                    assert client.predict(PLATFORM, "toy", cfgs) == expected
                    assert client.predict_network(PLATFORM, network) == expected_net
                    stats = client.stats()
        cache = stats["result_cache"]
        endpoints = stats["metrics"]["endpoints"]
        assert cache["hits"] >= len(cfgs) and cache["misses"] >= len(cfgs)
        assert endpoints["predict"]["requests"] == 2
        assert endpoints["predict"]["items"] == 2 * len(cfgs)
        assert endpoints["predict"]["errors"] == 0
        assert endpoints["predict"]["p99_ms"] is not None
        assert endpoints["predict_networks"]["requests"] == 1
        assert stats["uptime_s"] > 0.0


# --------------------------------------------------------------- concurrency
class TestConcurrentClients:
    def test_stress_deterministic_answers_and_coalescing(self, oracle):
        per_thread = 6
        n_threads = 16
        expected = {}
        for i in range(n_threads):
            cfgs = _configs(per_thread, offset=i)
            expected[i] = [float(v) for v in oracle.predict("toy", cfgs)]
        with _server(oracle, window_s=0.005) as server:
            client = OracleClient(server=server)
            results: dict[int, list] = {}
            errors: list[Exception] = []
            barrier = threading.Barrier(n_threads)

            def worker(i):
                try:
                    barrier.wait()
                    out = []
                    for j in range(per_thread):
                        out.extend(
                            client.predict(PLATFORM, "toy", [_configs(per_thread, offset=i)[j]])
                        )
                    results[i] = out
                except Exception as exc:  # pragma: no cover - failure detail
                    errors.append(exc)

            threads = [threading.Thread(target=worker, args=(i,)) for i in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = server.metrics.snapshot()
        assert not errors
        assert results == expected
        # concurrency actually coalesced: fewer dispatches than requests
        assert snap["batches"] < n_threads * per_thread
        assert snap["mean_batch_size"] > 1.0

    def test_max_batch_one_answers_each_request_alone_bitwise(self, oracle):
        """With coalescing off, concurrent single-config requests each take
        their own forest pass and get the direct oracle's bits."""
        cfgs = _configs(32)
        expected = [float(oracle.predict("toy", [c])[0]) for c in cfgs]
        served: list = [None] * len(cfgs)
        with _server(oracle, max_batch=1) as server:
            client = OracleClient(server=server)

            def worker(k):
                for i in range(k, len(cfgs), 8):
                    served[i] = client.predict_one(PLATFORM, "toy", cfgs[i])

            threads = [threading.Thread(target=worker, args=(k,)) for k in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            snap = server.metrics.snapshot()
        assert served == expected
        assert snap["batches"] == len(cfgs)
        assert snap["mean_batch_size"] == 1.0

    def test_concurrent_socket_clients(self, oracle):
        cfgs = _configs(5)
        direct = [float(v) for v in oracle.predict("toy", cfgs)]
        with _server(oracle) as server:
            with OracleSocketServer(server, port=0).start() as sock:
                outputs: list[list] = []
                errors: list[Exception] = []

                def worker():
                    try:
                        with OracleClient(address=sock.address) as c:
                            outputs.append(c.predict(PLATFORM, "toy", cfgs))
                    except Exception as exc:  # pragma: no cover
                        errors.append(exc)

                threads = [threading.Thread(target=worker) for _ in range(8)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
        assert not errors
        assert outputs == [direct] * 8


# ---------------------------------------------------------------- robustness
class TestRobustness:
    def test_malformed_requests_do_not_kill_the_server(self, oracle):
        with _server(oracle) as server:
            with OracleSocketServer(server, port=0).start() as sock:
                raw = socket.create_connection(sock.address)
                rfile = raw.makefile("rb")
                for bad in (
                    b"this is not json\n",
                    b"[1, 2, 3]\n",
                    b'{"op": "no_such_op"}\n',
                    b'{"op": "predict"}\n',
                    b'{"op": "predict", "platform": "nope", "layer_type": "toy", "configs": []}\n',
                    b'{"op": "predict", "platform": "stepped_sim", "layer_type": "nope", "configs": [{"a": 1}]}\n',
                    b'{"op": "predict_networks", "platform": "stepped_sim", "networks": [[42]]}\n',
                ):
                    raw.sendall(bad)
                    response = json.loads(rfile.readline())
                    assert response["ok"] is False and response["error"]
                # the same connection still serves good requests
                raw.sendall(
                    b'{"id": 9, "op": "predict", "platform": "stepped_sim", '
                    b'"layer_type": "toy", "configs": [{"a": 8, "b": 4}]}\n'
                )
                response = json.loads(rfile.readline())
                assert response["ok"] is True and response["id"] == 9
                raw.close()
            snap = server.metrics.snapshot()
            errors = sum(ep["errors"] for ep in snap["endpoints"].values())
            assert errors >= 5  # JSON-level failures never reach an endpoint

    def test_unknown_platform_is_a_serving_error(self, oracle):
        with _server(oracle) as server:
            client = OracleClient(server=server)
            with pytest.raises(ServingError, match="unknown platform"):
                client.predict("nope", "toy", [{"a": 1, "b": 1}])

    def test_block_payload_round_trip(self):
        block = _networks()[0][0]
        assert parse_block(block_payload(block)) == block
        assert parse_block(block) is block
        with pytest.raises(ServingError):
            parse_block(42)


# ------------------------------------------------------------------- restart
class TestWarmRestart:
    def test_new_server_over_same_hub_answers_identically(self, oracle, tmp_path):
        hub = EstimatorHub(str(tmp_path / "hub"))
        oracle.save(hub, PLATFORM)
        cfgs = _configs(9)
        nets = _networks()
        spec = ServeSpec(hub_dir=str(tmp_path / "hub"), window_s=0.001)
        with OracleServer(spec=spec) as first:
            c1 = OracleClient(server=first)
            layers_1 = c1.predict(PLATFORM, "toy", cfgs)
            nets_1 = c1.predict_networks(PLATFORM, nets)
        # "restart": a brand-new server process state over the same directory
        with OracleServer(spec=dataclasses_replace(spec, platforms=(PLATFORM,))) as second:
            assert PLATFORM in second.platforms()["loaded"]  # warm at startup
            c2 = OracleClient(server=second)
            assert c2.predict(PLATFORM, "toy", cfgs) == layers_1
            assert c2.predict_networks(PLATFORM, nets) == nets_1
        assert layers_1 == [float(v) for v in oracle.predict("toy", cfgs)]

    def test_gc_op_compacts_hub_artifacts(self, oracle, tmp_path):
        hub = EstimatorHub(str(tmp_path / "hub"), keep=4)
        for _ in range(4):
            oracle.save(hub, PLATFORM)
        spec = ServeSpec(hub_dir=str(tmp_path / "hub"))
        with OracleServer(spec=spec) as server:
            client = OracleClient(server=server)
            before = client.predict(PLATFORM, "toy", _configs(4))
            out = client.gc()  # the serving hub's default keep is 2
            assert out["steps_removed"] == 2
            # answers unchanged after gc (latest checkpoint untouched)
            assert client.predict(PLATFORM, "toy", _configs(4)) == before
        with OracleServer(spec=spec) as reloaded:
            c2 = OracleClient(server=reloaded)
            assert c2.predict(PLATFORM, "toy", _configs(4)) == before


def dataclasses_replace(spec: ServeSpec, **changes) -> ServeSpec:
    import dataclasses

    return dataclasses.replace(spec, **changes)
