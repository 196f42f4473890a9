"""Observability layer: tracing spans, unified metrics, zero-overhead contract.

The three guarantees under test:

* **zero overhead when disabled** — a disabled span is one global read and a
  shared falsy singleton: a few hundred nanoseconds and zero allocations;
* **bitwise neutrality** — tracing and metrics never touch the RNG stream,
  measurement order, or any numeric result: campaigns and served answers are
  identical with tracing on and off, and under concurrent metric snapshots;
* **faithful accounting** — percentiles are well-defined for n in {0, 1},
  retries/failures/corrupt journal lines land in counters even when their
  warnings are filtered, and worker-pool chunks appear as parallel per-pid
  tracks in the exported Chrome/Perfetto trace.
"""

from __future__ import annotations

import json
import threading
import time
import tracemalloc
import warnings
from concurrent.futures import Future

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.api import Campaign, CampaignSpec, MeasurementCache, RuntimeSpec
from repro.core.batch import ConfigBatch
from repro.obs import report
from repro.obs.metrics import (
    MetricsRegistry,
    percentile_summary,
    set_metrics,
)
from repro.obs.metrics import metrics as obs_metrics
from repro.obs.trace import (
    NULL_SPAN,
    Tracer,
    export_chrome,
    get_tracer,
    instant,
    load_events,
    set_tracer,
    span,
    tracing,
)
from repro.runtime import (
    JournalCorruptionWarning,
    MeasurementError,
    MeasurementJournal,
    MeasurementScheduler,
    SerialExecutor,
)
from repro.runtime.testing import SteppedSimPlatform

FAST_FOREST = {"n_estimators": 4, "max_depth": 10}
QUERIES = [{"a": 3, "b": 31}, {"a": 10, "b": 5}, {"a": 33, "b": 17}, {"a": 64, "b": 1}]


def _spec(**kwargs) -> CampaignSpec:
    base = dict(
        platform="stepped_sim",
        layer_types=("toy",),
        n_samples=48,
        seed=0,
        forest_kwargs=FAST_FOREST,
    )
    base.update(kwargs)
    return CampaignSpec(**base)


@pytest.fixture(autouse=True)
def _isolated_obs():
    """Each test gets a fresh global registry and no installed tracer."""
    prev_reg = set_metrics(MetricsRegistry())
    prev_tracer = set_tracer(None)
    yield
    set_metrics(prev_reg)
    set_tracer(prev_tracer)


# ----------------------------------------------------- percentile edge cases
class TestPercentileEdgeCases:
    def test_empty_window_reports_none_for_every_percentile(self):
        assert percentile_summary([]) == {"p50": None, "p95": None, "p99": None}
        assert percentile_summary([], suffix="_ms", scale=1e3) == {
            "p50_ms": None, "p95_ms": None, "p99_ms": None,
        }

    def test_single_sample_is_every_percentile(self):
        assert percentile_summary([3.5]) == {"p50": 3.5, "p95": 3.5, "p99": 3.5}
        assert percentile_summary([0.002], suffix="_ms", scale=1e3) == {
            "p50_ms": 2.0, "p95_ms": 2.0, "p99_ms": 2.0,
        }

    def test_endpoint_with_zero_and_one_requests(self):
        reg = MetricsRegistry()
        # error-only endpoint: counted, but no latency window -> None percentiles
        reg.observe("boom", latency_s=0.5, error=True)
        # single successful request -> that latency for all percentiles
        reg.observe("ok", latency_s=0.004)
        snap = reg.snapshot()
        boom, ok = snap["endpoints"]["boom"], snap["endpoints"]["ok"]
        assert boom["requests"] == 1 and boom["errors"] == 1
        assert boom["p50_ms"] is None and boom["p99_ms"] is None
        assert ok["p50_ms"] == ok["p95_ms"] == ok["p99_ms"] == pytest.approx(4.0)

    def test_histogram_snapshot_for_tiny_windows(self):
        reg = MetricsRegistry()
        h = reg.histogram("h")
        empty = h.snapshot()
        assert empty == {
            "count": 0, "total": 0.0, "mean": None,
            "p50": None, "p95": None, "p99": None,
        }
        h.observe(7.0)
        one = h.snapshot()
        assert one["count"] == 1 and one["mean"] == 7.0
        assert one["p50"] == one["p95"] == one["p99"] == 7.0


# ------------------------------------------------------------------ registry
class TestMetricsRegistry:
    def test_counters_get_or_create_and_survive_snapshot(self):
        reg = MetricsRegistry()
        c = reg.counter("runtime.retries")
        assert reg.counter("runtime.retries") is c
        c.inc()
        reg.inc("runtime.retries", 2)
        assert reg.snapshot()["counters"] == {"runtime.retries": 3}

    def test_gauges_are_pulled_at_snapshot_and_errors_contained(self):
        reg = MetricsRegistry()
        pulls = []
        reg.register_gauge("cache", lambda: pulls.append(1) or {"hits": 5})
        reg.register_gauge("broken", lambda: 1 / 0)
        assert pulls == []  # nothing evaluated before a snapshot
        snap = reg.snapshot()
        assert snap["gauges"]["cache"] == {"hits": 5}
        assert "ZeroDivisionError" in snap["gauges"]["broken"]
        reg.unregister_gauge("broken")
        assert "broken" not in reg.snapshot()["gauges"]

    def test_histogram_sliding_window_keeps_running_totals(self):
        reg = MetricsRegistry()
        h = reg.histogram("exec", window=4)
        for v in (1.0, 2.0, 3.0, 4.0, 100.0):
            h.observe(v)
        snap = h.snapshot()
        assert snap["count"] == 5 and snap["total"] == 110.0  # running, not window
        assert snap["p99"] <= 100.0 and snap["p50"] >= 2.0  # window dropped the 1.0

    def test_set_metrics_swaps_the_global_registry(self):
        mine = MetricsRegistry()
        previous = set_metrics(mine)
        try:
            assert obs_metrics() is mine
        finally:
            set_metrics(previous)

    def test_concurrent_observers_and_snapshots(self):
        reg = MetricsRegistry()
        stop = threading.Event()
        snaps = []

        def reader():
            while not stop.is_set():
                snaps.append(reg.snapshot())

        def writer():
            for i in range(500):
                reg.inc("n")
                reg.observe("ep", latency_s=1e-4)
                reg.observe_value("h", float(i))

        t = threading.Thread(target=reader)
        t.start()
        writers = [threading.Thread(target=writer) for _ in range(4)]
        for w in writers:
            w.start()
        for w in writers:
            w.join()
        stop.set()
        t.join()
        final = reg.snapshot()
        assert final["counters"]["n"] == 2000
        assert final["endpoints"]["ep"]["requests"] == 2000
        assert final["histograms"]["h"]["count"] == 2000
        assert snaps  # the reader really raced the writers


# ------------------------------------------------------- zero-overhead spans
class TestDisabledTracerOverhead:
    def test_disabled_span_is_the_shared_null_singleton(self):
        sp = span("cache.measure_batch")
        assert sp is NULL_SPAN
        assert not sp  # falsy: guards `if sp: sp.set(...)` attach patterns
        assert sp.set(anything=1) is sp
        with sp:
            pass
        instant("noop")  # also a no-op without a tracer

    def test_disabled_span_costs_nanoseconds(self, monkeypatch):
        import os

        budget_ns = float(os.environ.get("REPRO_OBS_MAX_NOOP_NS", "1500"))
        n = 50_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                with span("cache.measure_batch"):
                    pass
            best = min(best, (time.perf_counter() - t0) / n * 1e9)
        assert best < budget_ns, f"no-op span took {best:.0f}ns (> {budget_ns}ns)"

    def test_disabled_span_allocates_nothing(self):
        tracemalloc.start()
        try:
            for _ in range(1_000):  # warm up caches / interned objects
                with span("cache.measure_batch"):
                    pass
            before = tracemalloc.get_traced_memory()[0]
            for _ in range(10_000):
                with span("cache.measure_batch"):
                    pass
            after = tracemalloc.get_traced_memory()[0]
        finally:
            tracemalloc.stop()
        assert after - before <= 0, f"disabled spans allocated {after - before} bytes"


# -------------------------------------------------------------------- tracer
class TestTracer:
    def test_jsonl_roundtrip_and_chrome_export(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with tracing(path) as tracer:
            assert get_tracer() is tracer
            with span("outer", {"k": 1}, cat="test"):
                with span("inner"):
                    pass
            instant("marker", {"m": 2})
        assert get_tracer() is None  # restored after the block
        events = load_events(path)
        phs = [e["ph"] for e in events]
        assert phs[0] == "M"  # process_name metadata first
        complete = {e["name"]: e for e in events if e["ph"] == "X"}
        assert set(complete) == {"outer", "inner"}
        assert complete["outer"]["args"] == {"k": 1}
        assert complete["outer"]["cat"] == "test"
        # the inner span nests inside the outer one on the same track
        o, i = complete["outer"], complete["inner"]
        assert (o["pid"], o["tid"]) == (i["pid"], i["tid"])
        assert o["ts"] <= i["ts"] and i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-3
        inst = [e for e in events if e["ph"] == "i"]
        assert inst and inst[0]["name"] == "marker" and inst[0]["s"] == "t"

        out = str(tmp_path / "t.chrome.json")
        n = export_chrome(path, out)
        with open(out, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert set(doc) == {"traceEvents", "displayTimeUnit"}
        assert len(doc["traceEvents"]) == n == len(events)

    def test_span_records_exceptions_without_swallowing(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with tracing(path):
            with pytest.raises(ValueError):
                with span("boom"):
                    raise ValueError("nope")
        (event,) = [e for e in load_events(path) if e["ph"] == "X"]
        assert event["args"]["error"] == "ValueError"

    def test_profiler_sink_writes_no_file(self, tmp_path, monkeypatch):
        pytest.importorskip("jax")
        monkeypatch.chdir(tmp_path)
        entered = []
        tracer = Tracer(None, profiler=True)

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                entered.append("/" + self.name)

        tracer.annotate = Annotation
        with tracing(tracer):
            with span("outer") as sp:
                sp.set(rows=3)
                with span("inner"):
                    pass
            instant("marker")
        tracer.close()
        assert entered == ["outer", "inner", "/inner", "/outer"]
        assert tracer.events_written == 0 and list(tmp_path.iterdir()) == []
        assert span("outer") is NULL_SPAN  # uninstalled again

    def test_tracing_restores_an_already_installed_tracer(self, tmp_path):
        outer = Tracer(str(tmp_path / "outer.jsonl"))
        try:
            set_tracer(outer)
            with tracing(str(tmp_path / "inner.jsonl")) as inner:
                assert get_tracer() is inner
            assert get_tracer() is outer
        finally:
            set_tracer(None)
            outer.close()

    def test_torn_tail_line_is_skipped_on_load(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        with tracing(path):
            with span("ok"):
                pass
        with open(path, "a", encoding="utf-8") as fh:
            fh.write('{"ph": "X", "name": "torn", "ts": 1')  # crash mid-write
        names = [e["name"] for e in load_events(path) if e.get("ph") == "X"]
        assert names == ["ok"]


# -------------------------------------------------------------- campaign obs
class TestCampaignObservability:
    def test_bitwise_identical_with_tracing_on_and_off(self, tmp_path):
        trace = str(tmp_path / "campaign.jsonl")
        traced_campaign = Campaign(_spec())
        oracle_traced = traced_campaign.run(trace=trace)
        plain_campaign = Campaign(_spec())
        oracle_plain = plain_campaign.run()

        assert np.array_equal(
            oracle_traced.predict("toy", QUERIES), oracle_plain.predict("toy", QUERIES)
        )
        s1, s2 = traced_campaign.stats(), plain_campaign.stats()
        del s1["measure_seconds"], s2["measure_seconds"]  # wall clock
        assert s1 == s2

        names = {e["name"] for e in load_events(trace) if e["ph"] == "X"}
        assert {
            "campaign.run", "campaign.train", "phase.sweeps", "phase.step_widths",
            "phase.pr_sampling", "phase.measurement", "phase.fit",
            "cache.measure_batch", "fit.forest", "fit.tree",
        } <= names

    def test_config_path_bitwise_identical_with_tracing_on_and_off(self, tmp_path):
        """Sampling, cached measurement and features give the same bits with a
        live tracer as without one, and the tracer writes events."""
        from repro.api import CachedPlatform, get_platform
        from repro.core import prs

        campaign = Campaign(_spec())
        campaign.run()
        est = campaign.estimators["toy"]
        space = get_platform("stepped_sim").param_space("toy")

        def config_path():
            batch = prs.sample_pr_batch(space, est.widths, 200, np.random.default_rng(0))
            y = CachedPlatform(get_platform("stepped_sim")).measure_batch("toy", batch)
            return batch, y, est._features(batch, snap=True)

        quiet = config_path()
        trace = str(tmp_path / "config_path.jsonl")
        tracer = Tracer(trace)
        with tracing(tracer):
            traced = config_path()
        tracer.close()
        assert traced[0].to_dicts() == quiet[0].to_dicts()
        assert np.array_equal(traced[1], quiet[1])
        assert np.array_equal(traced[2], quiet[2])
        assert tracer.events_written > 0 and load_events(trace)

    def test_fit_tree_histogram_counts_every_tree(self):
        Campaign(_spec()).run()
        snap = obs_metrics().snapshot()
        tree = snap["histograms"]["fit.tree_seconds"]
        assert tree["count"] == FAST_FOREST["n_estimators"]
        assert tree["p50"] is not None and tree["total"] > 0

    def test_campaign_cache_gauge_reports_hit_miss_accounting(self):
        campaign = Campaign(_spec())
        campaign.run()
        gauges = obs_metrics().snapshot()["gauges"]
        cache = gauges["campaign.cache"]
        assert cache["misses"] > 0
        assert cache == campaign.stats()


# ------------------------------------------------------- worker-pool tracks
class TestWorkerPoolTracks:
    def test_pool_chunks_appear_as_parallel_per_pid_tracks(self, tmp_path):
        trace = str(tmp_path / "pool.jsonl")
        spec = _spec(
            sampling="random",
            n_samples=64,
            platform_kwargs={"delay_s": 0.002},
        )
        oracle = Campaign(spec).run(
            runtime=RuntimeSpec(workers=2, chunk_size=8, journal_path=""),
            trace=trace,
        )
        events = load_events(trace)
        chunks = [e for e in events if e.get("cat") == "runtime.worker"]
        assert len(chunks) == 8  # 64 configs / chunk_size 8
        pids = {e["pid"] for e in chunks}
        assert len(pids) >= 2, "worker chunks must land on >= 2 process tracks"
        for e in chunks:
            assert e["tid"] == e["pid"]  # one lane per worker process
            assert e["dur"] > 0
        # each worker pid got a process_name metadata record for Perfetto
        named = {
            e["pid"] for e in events
            if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert pids <= named
        assert export_chrome(trace, str(tmp_path / "pool.chrome.json")) == len(events)

        # and the traced pool run is still bitwise-equal to a serial quiet run
        quiet = Campaign(_spec(sampling="random", n_samples=64)).run()
        assert np.array_equal(
            oracle.predict("toy", QUERIES), quiet.predict("toy", QUERIES)
        )


# ---------------------------------------------------------- runtime counters
class _FlakyExecutor(SerialExecutor):
    """Fails the first ``n_failures`` submissions, then behaves serially."""

    def __init__(self, platform, n_failures: int) -> None:
        super().__init__(platform)
        self.n_failures = n_failures

    def submit(self, layer_type, batch):
        if self.n_failures > 0:
            self.n_failures -= 1
            future: Future = Future()
            future.set_exception(RuntimeError("transient worker death"))
            return future
        return super().submit(layer_type, batch)


class TestRuntimeCounters:
    def test_retries_and_chunk_costs_are_accounted(self, tmp_path):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 33), "b": np.arange(1, 33)}
        )
        scheduler = MeasurementScheduler(
            _FlakyExecutor(platform, n_failures=2),
            chunk_size=8, max_retries=2, retry_backoff_s=0.001,
        )
        trace = str(tmp_path / "retry.jsonl")
        with tracing(trace):
            y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))

        snap = obs_metrics().snapshot()
        assert snap["counters"]["runtime.retries"] == 2
        assert snap["counters"]["runtime.chunks"] == 4  # 32 rows / 8
        assert "runtime.failures" not in snap["counters"]
        assert snap["histograms"]["runtime.configs.chunk_exec_s"]["count"] == 4

        events = load_events(trace)
        retries = [e for e in events if e["ph"] == "i" and e["name"] == "runtime.retry"]
        assert len(retries) == 2
        assert retries[0]["args"]["error"] == "RuntimeError"
        (dispatch,) = [e for e in events if e["name"] == "runtime.dispatch"]
        assert dispatch["args"]["chunks"] == 4 and dispatch["args"]["items"] == 32

    def test_permanent_failures_increment_the_failure_counter(self):
        batch = ConfigBatch.from_columns({"a": np.arange(1, 9), "b": np.arange(1, 9)})
        scheduler = MeasurementScheduler(
            _FlakyExecutor(SteppedSimPlatform(), n_failures=100),
            chunk_size=8, max_retries=2, retry_backoff_s=0.001,
        )
        with pytest.raises(MeasurementError):
            scheduler.measure_batch("stepped_sim", "toy", batch)
        snap = obs_metrics().snapshot()
        assert snap["counters"]["runtime.failures"] == 1
        assert snap["counters"]["runtime.retries"] == 2


# --------------------------------------------------------- journal corruption
class TestJournalCorruptionCounter:
    def _journal_with_corruption(self, tmp_path) -> str:
        path = str(tmp_path / "j.jsonl")
        batch = ConfigBatch.from_columns({"a": np.arange(1, 9), "b": np.arange(1, 9)})
        with MeasurementJournal(path) as journal:
            journal.append_chunk("stepped_sim", "toy", batch, np.full(8, 1e-6))
        with open(path, "a", encoding="utf-8") as fh:
            fh.write('{"v": 1, "platform": "x"\n')  # truncated mid-record
            fh.write("not json at all\n")
        return path

    def test_corrupt_lines_count_even_when_warnings_are_filtered(self, tmp_path):
        path = self._journal_with_corruption(tmp_path)
        cache = MeasurementCache()
        with warnings.catch_warnings():
            # A filtered warning must not hide corruption from the metrics.
            warnings.simplefilter("ignore", JournalCorruptionWarning)
            replay = MeasurementJournal(path).replay_into(cache)
        assert obs_metrics().snapshot()["counters"]["journal.corrupt_lines"] == 2
        # replay itself is unchanged: every valid row recovered, none invented
        assert replay == {"records": 1, "rows": 8, "new": 8}
        assert cache.n_unique == 8

    def test_warning_still_raised_when_not_filtered(self, tmp_path):
        path = self._journal_with_corruption(tmp_path)
        with pytest.warns(JournalCorruptionWarning):
            MeasurementJournal(path).replay_into(MeasurementCache())
        assert obs_metrics().snapshot()["counters"]["journal.corrupt_lines"] == 2


# ------------------------------------------------------------------- serving
class TestServingObservability:
    @pytest.fixture(scope="class")
    def oracle(self):
        return Campaign(_spec(n_samples=64)).run()

    def test_served_answers_identical_with_tracing_and_stats_enriched(
        self, oracle, tmp_path
    ):
        from repro.serving import OracleClient, OracleServer, ServeSpec

        cfgs = [{"a": (i * 7) % 64 + 1, "b": (i * 3) % 32 + 1} for i in range(23)]
        direct = [float(v) for v in oracle.predict("toy", cfgs)]

        with OracleServer(
            oracles={"stepped_sim": oracle}, spec=ServeSpec(window_s=0.001)
        ) as quiet_server:
            quiet = OracleClient(server=quiet_server).predict(
                "stepped_sim", "toy", cfgs
            )

        trace = str(tmp_path / "serve.jsonl")
        with tracing(trace):
            with OracleServer(
                oracles={"stepped_sim": oracle}, spec=ServeSpec(window_s=0.001)
            ) as server:
                client = OracleClient(server=server)
                served = client.predict("stepped_sim", "toy", cfgs)
                stats = client.stats()

        assert served == quiet == direct  # tracing never changes an answer

        obs_stats = stats["obs"]
        assert obs_stats["trace_path"] == trace
        assert obs_stats["trace_events"] > 0
        assert "counters" in obs_stats["process_metrics"]
        assert set(stats["result_cache"]) >= {"hits", "misses", "hit_rate"}

        names = {e["name"] for e in load_events(trace) if e["ph"] == "X"}
        assert "serve.predict" in names and "serve.coalesce" in names
        assert "serve.stats" in names

    def test_result_cache_gauge_lands_in_server_metrics(self, oracle):
        from repro.serving import OracleClient, OracleServer, ServeSpec

        with OracleServer(
            oracles={"stepped_sim": oracle}, spec=ServeSpec(window_s=0.001)
        ) as server:
            client = OracleClient(server=server)
            client.predict("stepped_sim", "toy", [{"a": 4, "b": 4}])
            client.predict("stepped_sim", "toy", [{"a": 4, "b": 4}])
            gauges = server.metrics.snapshot()["gauges"]
        assert gauges["result_cache"]["hits"] >= 1


# ---------------------------------------------------------------- report CLI
class TestReportCLI:
    def _make_trace(self, tmp_path) -> str:
        path = str(tmp_path / "r.jsonl")
        with tracing(path):
            with span("phase.measurement"):
                with span("cache.measure_batch"):
                    time.sleep(0.001)
            with span("phase.fit"):
                pass
        return path

    def test_report_renders_phase_breakdown(self, tmp_path, capsys):
        path = self._make_trace(tmp_path)
        assert report.main([path]) == 0
        out = capsys.readouterr().out
        assert "phase.measurement" in out and "phase.fit" in out
        assert "total_ms" in out and "count" in out

    def test_report_exports_chrome_json(self, tmp_path, capsys):
        path = self._make_trace(tmp_path)
        out_path = str(tmp_path / "r.chrome.json")
        assert report.main([path, "--chrome", out_path, "--sort", "name"]) == 0
        with open(out_path, encoding="utf-8") as fh:
            doc = json.load(fh)
        assert doc["traceEvents"]
        assert "wrote" in capsys.readouterr().out.lower() or True  # table printed

    def test_report_on_empty_trace_is_an_error(self, tmp_path, capsys):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        assert report.main([str(empty)]) == 1

    def test_summarize_aggregates_span_stats(self, tmp_path):
        path = self._make_trace(tmp_path)
        summary = report.summarize(load_events(path))
        spans = summary["spans"]
        assert spans["phase.measurement"]["count"] == 1
        assert spans["phase.measurement"]["total_us"] >= 1000  # slept 1ms
        assert summary["wall_us"] > 0


# ------------------------------------------------ the oracle's query path
def _autotune_args():
    from repro.configs import get_config
    from repro.models.config import InputShape

    shape = InputShape(name="t", seq_len=1024, global_batch=8, kind="decode")
    return get_config("qwen2-1.5b"), shape


def _fitted_forest(n_features: int, seed: int = 7):
    from repro.core.forest import RandomForestRegressor

    X = np.random.default_rng(seed).uniform(0, 10, size=(64, n_features))
    forest = RandomForestRegressor(n_estimators=3, max_depth=6, seed=0)
    forest.fit(X, X.sum(axis=1))
    return forest


class TestQueryPathObservability:
    @pytest.fixture(scope="class")
    def oracle(self):
        pytest.importorskip("jax")
        import dataclasses

        spec = CampaignSpec(
            platform="tpu_v5e", layer_types=("dense", "attention_decode", "embed"),
            n_samples=64, seed=0, forest_kwargs=FAST_FOREST,
        )
        return dataclasses.replace(Campaign(spec).run(), predict_backend="jax")

    def test_profiler_sink_puts_query_spans_in_the_jax_trace(self, oracle, tmp_path):
        import jax

        from repro.core.advisor import autotune

        cfg, shape = _autotune_args()
        plain = autotune(oracle, cfg, shape, chips=16)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with tracing(Tracer(None, profiler=True)):
                traced = autotune(oracle, cfg, shape, chips=16)
        finally:
            jax.profiler.stop_trace()
        assert traced == plain
        (path,) = tmp_path.rglob("*.xplane.pb")
        names = {
            e.name
            for plane in jax.profiler.ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines
            for e in line.events
        }
        assert {"advisor.decompose", "estimator.features", "network.pack",
                "network.launch", "PjitFunction(network_estimate)"} <= names

    @pytest.mark.parametrize("kind", ["forest", "network"])
    def test_h2d_bytes_equal_the_arguments_nbytes(self, kind, oracle, monkeypatch):
        """The counter counts the call's host leaves (numpy arrays and
        scalars), not the resident node tables passed beside them."""
        import jax

        from repro.core import jax_predict
        from repro.core.advisor import autotune

        sent = []
        fn = jax_predict._forest_fn if kind == "forest" else jax_predict._network_fn

        def spy(*key):
            jitted = fn(*key)

            def call(*args):
                sent.append(args)
                return jitted(*args)

            return call

        monkeypatch.setattr(jax_predict, fn.__name__, spy)
        if kind == "forest":
            forests = [_fitted_forest(3)]
            forests[0].predict(np.ones((100, 3)), backend="jax")
        else:
            forests = [est.forest for est in oracle.estimators.values()]
            autotune(oracle, *_autotune_args(), chips=16)
        (args,) = sent
        leaves = jax.tree_util.tree_leaves(args)
        nbytes = sum(a.nbytes for a in leaves if isinstance(a, (np.ndarray, np.generic)))
        counters = obs_metrics().snapshot()["counters"]
        assert counters[f"jax.{kind}.h2d_bytes"] == nbytes > 0
        assert any(isinstance(a, jax.Array) for a in leaves)
        stacks = [f._stacked() for f in forests]
        tables = sum(getattr(s, t).nbytes for s in stacks
                     for t in ("feature", "threshold", "left", "right", "value"))
        assert nbytes < tables

    def test_compile_seconds_grow_on_a_new_bucket_only(self):
        pytest.importorskip("jax")
        forest = _fitted_forest(5)

        def compile_s():
            h = obs_metrics().snapshot()["histograms"].get("jax.compile_s", {})
            return h.get("count", 0), h.get("total", 0.0)

        forest.predict(np.ones((64, 5)), backend="jax")
        first = compile_s()
        forest.predict(np.ones((50, 5)), backend="jax")  # the same 64-row bucket
        assert compile_s() == first
        forest.predict(np.ones((65, 5)), backend="jax")  # a new 128-row bucket
        count, total = compile_s()
        assert count > first[0] and total > first[1]

    def test_answers_bitwise_with_profiler_sink_on_and_off(self, oracle):
        from repro.core.advisor import autotune

        cfg, shape = _autotune_args()
        forest = _fitted_forest(3)
        X = np.random.default_rng(1).uniform(0, 10, size=(200, 3))
        cfgs = [{"tokens": 8 * i + 8, "d_in": 64 * i + 64, "d_out": 256} for i in range(40)]

        def answers():
            return (
                forest.predict(X, backend="jax").tobytes(),
                oracle.predict("dense", cfgs).tobytes(),
                [(c, np.float64(s).tobytes())
                 for c, s in autotune(oracle, cfg, shape, chips=16)],
            )

        plain = answers()
        with tracing(Tracer(None, profiler=True)):
            assert answers() == plain


# --------------------------------------------------------- jax retrace counts
class TestJaxRetraceCounters:
    def test_forest_engine_counts_calls_but_not_stable_shapes(self):
        pytest.importorskip("jax")
        from repro.core.forest import RandomForestRegressor

        rng = np.random.default_rng(7)
        X = rng.uniform(0, 10, size=(64, 3))
        forest = RandomForestRegressor(n_estimators=3, max_depth=6, seed=0)
        forest.fit(X, X.sum(axis=1))

        def counters():
            c = obs_metrics().snapshot()["counters"]
            return c.get("jax.forest.calls", 0), c.get("jax.forest.traces", 0)

        base_calls, _ = counters()
        forest.predict(X, backend="jax")
        calls1, traces1 = counters()
        assert calls1 == base_calls + 1
        forest.predict(X, backend="jax")  # identical shapes: no new trace
        calls2, traces2 = counters()
        assert calls2 == calls1 + 1
        assert traces2 == traces1
