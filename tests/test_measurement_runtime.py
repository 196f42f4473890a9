"""Measurement runtime: sharded scheduler, worker pool, crash-safe journal.

The three guarantees under test:

* **determinism** — a campaign produces bitwise-identical results (estimator
  checkpoints, predictions, cache stats) for any worker count, because chunk
  boundaries depend only on ``chunk_size`` and results merge in
  first-occurrence order;
* **crash-safe resume** — killing a run mid-campaign loses at most the
  chunks still in flight (completed chunks are journaled the moment they
  finish, even out of merge order); re-running replays the fsync'd journal
  into the measurement cache and finishes with zero duplicate measurements,
  bitwise-equal to an uninterrupted run;
* **fault tolerance** — transient chunk failures and gather timeouts are
  retried with backoff; corrupt journal lines are skipped with a warning.
"""

from __future__ import annotations

import json
import os
from concurrent.futures import Future

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.api import Campaign, CampaignSpec, MeasurementCache, RuntimeSpec
from repro.core.batch import ConfigBatch
from repro.runtime import (
    JournalCorruptionWarning,
    MeasurementError,
    MeasurementJournal,
    MeasurementRuntime,
    MeasurementScheduler,
    SerialExecutor,
    WorkerPool,
)
from repro.runtime.testing import SteppedSimPlatform

FAST_FOREST = {"n_estimators": 4, "max_depth": 10}


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


def _hub_content(hub_dir) -> dict:
    """Exact persisted content of a hub, byte-compared array by array.

    Two normalizations, both about *when* a checkpoint was written rather than
    *what* was measured: ``npz`` zip-member timestamps are bypassed by reading
    the stored arrays, and the meta blob's ``mean_measure_seconds`` (wall-clock
    bookkeeping for Table-1 reporting) is dropped.  Everything derived from
    measurements — tree node tables, step widths, spaces, targets — must match
    to the byte.  Manifests are skipped: they are derived from the arrays.
    """
    content: dict = {}
    for root, _, files in os.walk(hub_dir):
        for fname in sorted(files):
            path = os.path.join(root, fname)
            rel = os.path.relpath(path, hub_dir)
            if fname.endswith(".npz"):
                entry: dict = {}
                with np.load(path) as z:
                    for k in z.files:
                        if k == "meta":
                            meta = json.loads(bytes(z[k]).decode("utf-8"))
                            meta.pop("mean_measure_seconds", None)
                            entry[k] = json.dumps(meta, sort_keys=True)
                        else:
                            entry[k] = (z[k].dtype.str, z[k].shape, z[k].tobytes())
                content[rel] = entry
            elif fname == "oracle.json":
                with open(path, "rb") as f:
                    content[rel] = f.read()
    return content


QUERIES = [{"a": 3, "b": 31}, {"a": 10, "b": 5}, {"a": 33, "b": 17}, {"a": 64, "b": 1}]


# ---------------------------------------------------------------- determinism
class TestWorkerCountDeterminism:
    def test_bitwise_identical_for_worker_counts(self, tmp_path):
        """Same seed => bitwise-identical campaigns for workers in {1, 2, 4}."""
        contents, preds, stats = [], [], []
        for workers in (1, 2, 4):
            hub = tmp_path / f"hub_w{workers}"
            campaign = Campaign(_spec(hub_dir=str(hub)))
            oracle = campaign.run(
                runtime=RuntimeSpec(workers=workers, chunk_size=16, journal_path=None)
            )
            contents.append(_hub_content(hub))
            preds.append(oracle.predict("toy", QUERIES))
            cache_stats = campaign.stats()
            del cache_stats["measure_seconds"]  # wall clock, not deterministic
            stats.append(cache_stats)
        assert contents[0] == contents[1] == contents[2]
        assert np.array_equal(preds[0], preds[1])
        assert np.array_equal(preds[0], preds[2])
        assert stats[0] == stats[1] == stats[2]

    def test_scheduler_merges_in_first_occurrence_order(self):
        platform = SteppedSimPlatform()
        space_batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 61), "b": (np.arange(1, 61) % 32) + 1}
        )
        direct = platform.measure_batch("toy", space_batch)
        for chunk_size in (1, 7, 64, 1000):
            scheduler = MeasurementScheduler(
                SerialExecutor(platform), chunk_size=chunk_size
            )
            merged = scheduler.measure_batch("stepped_sim", "toy", space_batch)
            assert np.array_equal(merged, direct)

    def test_empty_batch(self):
        scheduler = MeasurementScheduler(SerialExecutor(SteppedSimPlatform()))
        out = scheduler.measure_batch("stepped_sim", "toy", ConfigBatch.from_dicts([]))
        assert out.shape == (0,)


# ------------------------------------------------------- xla_cpu (acceptance)
class TestXLACPUSyntheticCampaign:
    """The ISSUE acceptance path: xla_cpu + process pool + journal resume."""

    def _spec(self, hub_dir=None):
        return CampaignSpec(
            platform="xla_cpu",
            layer_types=("dense",),
            n_samples=32,
            seed=0,
            forest_kwargs=FAST_FOREST,
            platform_kwargs={"synthetic": True, "repeats": 1},
            hub_dir=hub_dir,
        )

    def test_pool_checkpoints_byte_identical_to_serial(self, tmp_path):
        hub_serial, hub_pool = tmp_path / "serial", tmp_path / "pool"
        c_serial = Campaign(self._spec(str(hub_serial)))
        c_serial.run(runtime=RuntimeSpec(workers=1, chunk_size=64, journal_path=None))
        c_pool = Campaign(self._spec(str(hub_pool)))
        c_pool.run(
            runtime=RuntimeSpec(
                workers=2,
                chunk_size=64,
                journal_path=str(tmp_path / "pool.jsonl"),
            )
        )
        assert _hub_content(hub_serial) == _hub_content(hub_pool)
        assert c_serial.cache.misses == c_pool.cache.misses

        # Resume from the pool's journal: a fresh campaign re-measures nothing.
        resumed = Campaign(self._spec())
        oracle = resumed.run(
            runtime=RuntimeSpec(workers=1, journal_path=str(tmp_path / "pool.jsonl"))
        )
        assert resumed.cache.misses == 0
        assert resumed.cache.replayed == c_pool.cache.misses
        assert resumed.last_run_stats["measured"] == 0
        ref = Campaign(self._spec()).run()
        test = [{"tokens": 17, "d_in": 100, "d_out": 640}]
        assert np.array_equal(oracle.predict("dense", test), ref.predict("dense", test))


# ------------------------------------------------------------- journal resume
class _CrashingPlatform(SteppedSimPlatform):
    """Raises once a measurement budget is exhausted (simulated mid-run kill)."""

    def __init__(self, fail_after_rows: int) -> None:
        super().__init__()
        self._remaining = fail_after_rows

    def measure_batch(self, layer_type, batch):
        if self._remaining < len(batch):
            raise RuntimeError("injected crash")
        self._remaining -= len(batch)
        return super().measure_batch(layer_type, batch)


class TestJournalResume:
    def test_serial_journals_each_chunk_as_it_completes(self, tmp_path):
        """Serial execution must journal chunk-by-chunk, not batch-at-the-end.

        A crash mid-batch may lose only the chunk in flight — every chunk
        measured before it must already be on disk.
        """
        path = str(tmp_path / "j.jsonl")
        platform = _CrashingPlatform(fail_after_rows=20)
        journal = MeasurementJournal(path)
        scheduler = MeasurementScheduler(
            SerialExecutor(platform), journal=journal, chunk_size=8, max_retries=0
        )
        batch = ConfigBatch.from_columns({"a": np.arange(1, 33), "b": np.arange(1, 33)})
        with pytest.raises(MeasurementError):
            scheduler.measure_batch("stepped_sim", "toy", batch)
        journal.close()
        rows = sum(len(r["rows"]) for r in MeasurementJournal(path).iter_records())
        assert rows == 16  # two full chunks durably recorded before the crash

    def test_prefetched_chunks_journal_even_when_an_earlier_chunk_fails(self, tmp_path):
        """Pool path: completed chunks persist regardless of merge order.

        Chunk 0 dies permanently while chunks 1..3 complete in other workers;
        their measurements must be on disk when the run aborts.
        """

        class _FirstChunkDies(SerialExecutor):
            workers = 2  # prefetch path

            def __init__(self, platform):
                super().__init__(platform)
                self.calls = 0

            def submit(self, layer_type, batch):
                self.calls += 1
                if self.calls == 1:
                    future: Future = Future()
                    future.set_exception(RuntimeError("worker died"))
                    return future
                return super().submit(layer_type, batch)

        path = str(tmp_path / "j.jsonl")
        journal = MeasurementJournal(path)
        scheduler = MeasurementScheduler(
            _FirstChunkDies(SteppedSimPlatform()),
            journal=journal,
            chunk_size=8,
            max_retries=0,
        )
        batch = ConfigBatch.from_columns({"a": np.arange(1, 33), "b": np.arange(1, 33)})
        with pytest.raises(MeasurementError):
            scheduler.measure_batch("stepped_sim", "toy", batch)
        journal.close()
        records = list(MeasurementJournal(path).iter_records())
        assert sum(len(r["rows"]) for r in records) == 24  # chunks 1..3, not 0
        assert [1, 2, 3] not in [r["rows"][0] for r in records]  # chunk 0 absent

    def test_journal_opt_out_overrides_hub_default(self, tmp_path):
        hub = tmp_path / "hub"
        campaign = Campaign(_spec(hub_dir=str(hub)))
        campaign.run(runtime=RuntimeSpec(workers=1, journal_path=""))
        assert not os.path.exists(hub / "measurements.jsonl")
        # and the default (journal_path=None) does land in the hub
        campaign2 = Campaign(_spec(hub_dir=str(hub)))
        campaign2.run(runtime=RuntimeSpec(workers=1))
        assert os.path.exists(hub / "measurements.jsonl")

    def test_resume_equals_uninterrupted_with_zero_duplicates(self, tmp_path):
        journal = str(tmp_path / "measurements.jsonl")
        spec = _spec()

        # Run 1: crashes partway through (retries disabled: the "hardware"
        # fails permanently, like a killed process).
        crashed = Campaign(spec, platform=_CrashingPlatform(fail_after_rows=60))
        with pytest.raises(MeasurementError):
            crashed.run(
                runtime=RuntimeSpec(
                    workers=1, chunk_size=32, max_retries=0, journal_path=journal
                )
            )
        rows_before = sum(len(r["rows"]) for r in MeasurementJournal(journal).iter_records())
        assert 0 < rows_before <= 60

        # Run 2: fresh campaign, same journal -> resumes and completes.
        resumed = Campaign(spec)
        oracle = resumed.run(
            runtime=RuntimeSpec(workers=1, chunk_size=32, journal_path=journal)
        )

        # Control: uninterrupted run, no journal.
        control = Campaign(spec)
        control_oracle = control.run(runtime=RuntimeSpec(workers=1, chunk_size=32))

        # Bitwise-equal outcome...
        assert np.array_equal(
            oracle.predict("toy", QUERIES), control_oracle.predict("toy", QUERIES)
        )
        # ...with zero duplicate measurements: replay + new misses == one full
        # run's misses, and the journal holds each unique config exactly once.
        assert resumed.cache.replayed == rows_before
        assert resumed.cache.misses == control.cache.misses - rows_before
        keys = []
        for record in MeasurementJournal(journal).iter_records():
            for row in record["rows"]:
                keys.append((record["platform"], record["layer_type"],
                             tuple(record["params"]), tuple(row)))
        assert len(keys) == len(set(keys)) == control.cache.misses

    def test_pool_journal_resume_measures_nothing_again(self, tmp_path):
        """A campaign journaled by a 2-worker pool resumes serially from its
        journal: every config is replayed and none is measured again."""
        journal = str(tmp_path / "measurements.jsonl")
        first = Campaign(_spec())
        first_oracle = first.run(
            runtime=RuntimeSpec(workers=2, chunk_size=16, journal_path=journal)
        )
        resumed = Campaign(_spec())
        oracle = resumed.run(runtime=RuntimeSpec(workers=1, journal_path=journal))
        assert resumed.cache.misses == 0
        assert resumed.cache.replayed == first.cache.misses > 0
        assert np.array_equal(
            oracle.predict("toy", QUERIES), first_oracle.predict("toy", QUERIES)
        )

    def test_replay_is_idempotent(self, tmp_path):
        journal = str(tmp_path / "j.jsonl")
        spec = _spec()
        Campaign(spec).run(runtime=RuntimeSpec(workers=1, journal_path=journal))
        cache = MeasurementCache()
        j = MeasurementJournal(journal)
        first = j.replay_into(cache)
        again = j.replay_into(cache)
        assert first["new"] == first["rows"] > 0
        assert again["new"] == 0
        assert cache.n_unique == first["rows"]


# ---------------------------------------------------------- journal integrity
class TestJournalCorruption:
    def _write_chunks(self, path, n_chunks=2, rows_per_chunk=3):
        with MeasurementJournal(path) as journal:
            for c in range(n_chunks):
                batch = ConfigBatch.from_columns(
                    {
                        "a": np.arange(1, rows_per_chunk + 1) + 10 * c,
                        "b": np.arange(1, rows_per_chunk + 1),
                    }
                )
                journal.append_chunk(
                    "stepped_sim", "toy", batch, np.full(rows_per_chunk, 1e-6 * (c + 1))
                )

    def test_corrupt_lines_skipped_with_warning(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        self._write_chunks(path)
        with open(path, "a") as f:
            f.write('{"v": 1, "platform": "x"\n')  # truncated mid-record
            f.write("not json at all\n")
            f.write('{"v": 1, "platform": "p", "layer_type": "toy", '
                    '"params": ["a"], "rows": [[1], [2]], "seconds": [1.0]}\n')  # mismatch
            f.write('{"v": 1, "platform": "p", "layer_type": "toy", '
                    '"params": ["a", "b"], "rows": [[1, 2], [3]], '
                    '"seconds": [1.0, 2.0]}\n')  # ragged rows (valid JSON)
            f.write('{"v": 1, "platform": "p", "layer_type": "toy", '
                    '"params": ["a", "b"], "rows": [[1, "x"]], '
                    '"seconds": [1.0]}\n')  # non-numeric cell (valid JSON+shape)
        cache = MeasurementCache()
        with pytest.warns(JournalCorruptionWarning):
            replay = MeasurementJournal(path).replay_into(cache)
        assert replay == {"records": 2, "rows": 6, "new": 6}
        assert cache.n_unique == 6 and cache.misses == 0

    def test_missing_journal_is_empty(self, tmp_path):
        replay = MeasurementJournal(str(tmp_path / "absent.jsonl")).replay_into(
            MeasurementCache()
        )
        assert replay == {"records": 0, "rows": 0, "new": 0}

    def test_float_round_trip_is_exact(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        rng = np.random.default_rng(0)
        seconds = rng.random(16) * 1e-3
        batch = ConfigBatch.from_columns({"a": np.arange(16), "b": np.arange(16)})
        with MeasurementJournal(path) as journal:
            journal.append_chunk("p", "toy", batch, seconds)
        cache = MeasurementCache()
        MeasurementJournal(path).replay_into(cache)
        times, miss_rows, _ = cache.lookup_many("p", "toy", batch)
        assert miss_rows.size == 0
        assert np.array_equal(times, seconds)


# ------------------------------------------------------------ fault tolerance
class _FlakyExecutor(SerialExecutor):
    """Fails the first ``n_failures`` submissions, then behaves serially."""

    def __init__(self, platform, n_failures: int) -> None:
        super().__init__(platform)
        self.n_failures = n_failures
        self.submissions = 0

    def submit(self, layer_type, batch):
        self.submissions += 1
        if self.n_failures > 0:
            self.n_failures -= 1
            future: Future = Future()
            future.set_exception(RuntimeError("transient worker death"))
            return future
        return super().submit(layer_type, batch)


class _StallingExecutor(SerialExecutor):
    """First submission never completes (hung worker); retries succeed."""

    def __init__(self, platform) -> None:
        super().__init__(platform)
        self.stalls = 1

    def submit(self, layer_type, batch):
        if self.stalls > 0:
            self.stalls -= 1
            return Future()  # never resolved
        return super().submit(layer_type, batch)


class _BreakingPoolExecutor(SerialExecutor):
    """Emulates an abrupt worker death: the first submission returns a failed
    future AND breaks the pool (submit raises, like BrokenProcessPool) until
    ``respawn`` rebuilds it."""

    workers = 2  # exercise the prefetch path

    def __init__(self, platform) -> None:
        super().__init__(platform)
        self.broken = False
        self.died = False
        self.respawns = 0

    def submit(self, layer_type, batch):
        if self.broken:
            raise RuntimeError("pool is broken")
        if not self.died:
            self.died = True
            self.broken = True
            future: Future = Future()
            future.set_exception(RuntimeError("worker died abruptly"))
            return future
        return super().submit(layer_type, batch)

    def respawn(self):
        self.broken = False
        self.respawns += 1


class TestRetryAndTimeout:
    def test_stale_timed_out_attempt_cannot_poison_the_journal(self, tmp_path):
        """A timed-out attempt that completes late must not leave its values
        as the journal's last word for the chunk — replay must yield exactly
        the values the run merged and trained on."""
        import threading
        import time as _time

        platform = SteppedSimPlatform()
        wrong = np.zeros(8)

        class _RunningFuture(Future):
            def cancel(self):
                return False  # like a ProcessPool future that is already executing

        class _StaleThenSlowRetry(SerialExecutor):
            workers = 2  # prefetch path, with journal callbacks

            def __init__(self):
                super().__init__(platform)
                self.calls = 0

            def submit(self, layer_type, batch):
                self.calls += 1
                if self.calls == 1:
                    stale: Future = _RunningFuture()
                    # completes mid-retry with values the run will discard
                    threading.Timer(0.1, stale.set_result, args=(wrong,)).start()
                    return stale
                _time.sleep(0.3)  # keep the retry slow so the stale completes first
                return super().submit(layer_type, batch)

        path = str(tmp_path / "j.jsonl")
        journal = MeasurementJournal(path)
        scheduler = MeasurementScheduler(
            _StaleThenSlowRetry(),
            journal=journal,
            chunk_size=8,
            max_retries=1,
            retry_backoff_s=0.001,
            chunk_timeout_s=0.03,
        )
        batch = ConfigBatch.from_columns({"a": np.arange(1, 9), "b": np.arange(1, 9)})
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        journal.close()
        expected = platform.measure_batch("toy", batch)
        assert np.array_equal(y, expected)
        # the stale callback journaled its record, then the merge loop
        # appended a superseding one...
        assert len(list(MeasurementJournal(path).iter_records())) == 2
        # ...and last-writer-wins replay recovers the merged values
        cache = MeasurementCache()
        MeasurementJournal(path).replay_into(cache)
        times, miss_rows, _ = cache.lookup_many("stepped_sim", "toy", batch)
        assert miss_rows.size == 0
        assert np.array_equal(times, expected)

    def test_broken_pool_is_respawned_and_chunk_retried(self):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns({"a": np.arange(1, 33), "b": np.arange(1, 33)})
        executor = _BreakingPoolExecutor(platform)
        scheduler = MeasurementScheduler(
            executor, chunk_size=8, max_retries=1, retry_backoff_s=0.001
        )
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert executor.respawns == 1
        assert scheduler.stats.failures == 0
    def test_transient_failures_are_retried(self):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns({"a": np.arange(1, 33), "b": np.arange(1, 33)})
        executor = _FlakyExecutor(platform, n_failures=2)
        scheduler = MeasurementScheduler(
            executor, chunk_size=8, max_retries=2, retry_backoff_s=0.001
        )
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert scheduler.stats.retries == 2
        assert scheduler.stats.failures == 0

    def test_retry_budget_exhaustion_raises(self):
        batch = ConfigBatch.from_columns({"a": np.arange(1, 9), "b": np.arange(1, 9)})
        executor = _FlakyExecutor(SteppedSimPlatform(), n_failures=100)
        scheduler = MeasurementScheduler(
            executor, chunk_size=8, max_retries=2, retry_backoff_s=0.001
        )
        with pytest.raises(MeasurementError):
            scheduler.measure_batch("stepped_sim", "toy", batch)
        assert scheduler.stats.failures == 1
        assert scheduler.stats.in_flight == 0

    def test_hung_chunk_times_out_and_retries(self):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns({"a": np.arange(1, 9), "b": np.arange(1, 9)})
        scheduler = MeasurementScheduler(
            _StallingExecutor(platform),
            chunk_size=8,
            max_retries=1,
            retry_backoff_s=0.001,
            chunk_timeout_s=0.05,
        )
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert scheduler.stats.retries == 1


# ------------------------------------------------------------- pool teardown
class TestWedgedWorkerClose:
    def test_close_terminates_wedged_worker_within_bounded_time(self):
        """``close(wait=False)`` must actually abandon a wedged worker.

        ProcessPoolExecutor workers are non-daemon, and concurrent.futures
        joins them from an atexit hook — without an explicit ``terminate()``
        a worker stuck inside a measurement would hang the campaign process
        at interpreter exit.  The chunk below wedges its worker for ~60 s;
        close must come back (with every worker process dead) in seconds.
        """
        import time

        platform = SteppedSimPlatform(delay_s=1.0)
        pool = WorkerPool(platform.spawn_spec(), workers=1)
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 61), "b": (np.arange(1, 61) % 32) + 1}
        )
        future = pool.submit("toy", batch)  # ~60 s of emulated measurement
        deadline = time.perf_counter() + 30
        while not pool._pool._processes and time.perf_counter() < deadline:
            time.sleep(0.05)  # wait for the worker process to exist
        procs = list(pool._pool._processes.values())
        assert procs, "worker process never spawned"

        t0 = time.perf_counter()
        pool.close()  # wait=False is the default
        elapsed = time.perf_counter() - t0
        assert elapsed < 15, f"close took {elapsed:.1f}s (wedged worker not abandoned)"
        for p in procs:
            p.join(timeout=10)
        assert all(not p.is_alive() for p in procs), "worker survived close"
        assert not future.done() or future.exception() is not None


# ------------------------------------------------------------ progress surface
class TestRunStats:
    def test_campaign_accounting(self, tmp_path):
        journal = str(tmp_path / "j.jsonl")
        c1 = Campaign(_spec())
        c1.run(runtime=RuntimeSpec(workers=1, chunk_size=16, journal_path=journal))
        s1 = c1.last_run_stats
        assert s1["measured"] == c1.cache.misses
        assert s1["cached"] == c1.cache.hits
        assert s1["chunks"] > 0 and s1["in_flight"] == 0
        assert s1["throughput_cfg_s"] > 0

        c2 = Campaign(_spec())
        oracle = c2.run(runtime=RuntimeSpec(workers=1, chunk_size=16, journal_path=journal))
        s2 = c2.last_run_stats
        assert s2["measured"] == 0
        assert s2["replayed"] == s1["measured"]
        assert oracle.run_stats == s2  # provenance rides on the oracle

    def test_stale_stats_not_attached_to_runtime_less_run(self):
        campaign = Campaign(_spec())
        campaign.run(runtime=RuntimeSpec(workers=1))
        assert campaign.last_run_stats is not None
        oracle = campaign.run()  # no runtime this time
        assert campaign.last_run_stats is None
        assert oracle.run_stats is None

    def test_render_mentions_core_counters(self):
        runtime = MeasurementRuntime(RuntimeSpec(workers=1), SteppedSimPlatform())
        runtime.stats.measured, runtime.stats.cached = 10, 4
        line = runtime.stats.render()
        assert "10 measured" in line and "4 cached" in line
        runtime.close()


# ----------------------------------------------------- feature-matrix memoize
class TestSamplingCurveFeatureMemo:
    def test_test_set_featurized_once(self):
        campaign = Campaign(_spec())
        test = [{"a": int(a), "b": int(b)} for a, b in zip(range(1, 21), range(32, 12, -1))]
        curve = campaign.sampling_curve("toy", [40, 60, 80], test)
        assert len(curve) == 3
        # one miss (first size), then one hit per remaining size
        assert campaign.cache.feature_hits == 2
        # the memoized matrix is exactly what a fresh featurization produces
        est = campaign.estimators["toy"]
        batch = ConfigBatch.from_dicts(test)
        X_memo = campaign.cache.lookup_features(
            campaign.platform.cache_key(), "toy", est.widths, True, batch
        )
        assert X_memo is not None
        assert np.array_equal(X_memo, est._features(batch, snap=True))
        # and the curve's metrics match an independent est.evaluate
        metrics = est.evaluate(campaign.platform, test)
        assert curve[-1]["mape"] == metrics["mape"]
        assert curve[-1]["rmspe"] == metrics["rmspe"]


# ------------------------------------------------------------- journal compact
class TestJournalCompaction:
    def _populate(self, path) -> MeasurementJournal:
        journal = MeasurementJournal(str(path))
        b1 = ConfigBatch.from_dicts([{"a": 1, "b": 2}, {"a": 3, "b": 4}])
        # same configs journaled again under reversed param order + a retry
        # that superseded {a:1,b:2} with a different final value
        b2 = ConfigBatch(
            params=("b", "a"), values=np.array([[2, 1], [6, 5]], dtype=np.int64)
        )
        journal.append_chunk("p", "toy", b1, np.array([1.0, 2.0]))
        journal.append_chunk("p", "toy", b2, np.array([1.5, 3.0]))
        journal.append_chunk("p", "toy", b1, np.array([1.75, 2.0]))
        from repro.core.batch import BlockBatch
        from repro.core.blocks import Block

        blocks = BlockBatch.from_blocks(
            [
                Block(kind="k", layers=(("toy", {"a": 2, "b": 2}),)),
                Block(kind="k", layers=(("toy", {"a": 4, "b": 4}),)),
            ]
        )
        journal.append_block_chunk("p", blocks, np.array([0.1, 0.2]))
        journal.append_block_chunk("p", blocks.take(np.array([0])), np.array([0.15]))
        journal.close()
        return journal

    def test_compact_preserves_replay_state_bitwise(self, tmp_path):
        journal = self._populate(tmp_path / "j.jsonl")
        before = MeasurementCache()
        MeasurementJournal(journal.path).replay_into(before)
        stats = MeasurementJournal(journal.path).compact()
        after = MeasurementCache()
        replay = MeasurementJournal(journal.path).replay_into(after)
        assert stats["records_in"] == 5 and stats["records_out"] == 3
        assert stats["rows_in"] == 9 and stats["rows_out"] == 5
        assert stats["bytes_out"] < stats["bytes_in"]
        # last-writer-wins values survive under first-occurrence keys
        assert after.lookup("p", "toy", {"a": 1, "b": 2}) == 1.75
        assert after.lookup("p", "toy", {"a": 3, "b": 4}) == 2.0
        assert after.lookup("p", "toy", {"a": 5, "b": 6}) == 3.0
        assert before._configs == after._configs if hasattr(before, "_configs") else True
        assert replay["rows"] == stats["rows_out"]

    def test_compact_is_idempotent(self, tmp_path):
        journal = self._populate(tmp_path / "j.jsonl")
        first = MeasurementJournal(journal.path).compact()
        second = MeasurementJournal(journal.path).compact()
        assert second["records_out"] == first["records_out"]
        assert second["rows_out"] == first["rows_out"]
        assert second["bytes_out"] == first["bytes_out"]

    def test_compact_missing_file_is_a_no_op(self, tmp_path):
        stats = MeasurementJournal(str(tmp_path / "absent.jsonl")).compact()
        assert stats["records_in"] == 0 and stats["records_out"] == 0

    def test_hub_gc_drops_superseded_artifacts_keeps_latest(self, tmp_path):
        from repro.api import EstimatorHub, PerfOracle
        from repro.checkpoint.manager import journal_path

        hub = EstimatorHub(str(tmp_path), keep=4)
        campaign = Campaign(_spec())
        oracle = campaign.run()
        for _ in range(3):
            oracle.save(hub, "stepped_sim")
        slot = os.path.join(str(tmp_path), "stepped_sim", "toy")
        os.makedirs(os.path.join(slot, "step_000000042.tmp"))
        journal = MeasurementJournal(journal_path(str(tmp_path)))
        batch = ConfigBatch.from_dicts([{"a": 1, "b": 1}])
        journal.append_chunk("stepped_sim", "toy", batch, np.array([1e-6]))
        journal.append_chunk("stepped_sim", "toy", batch, np.array([2e-6]))
        journal.close()

        ref = oracle.predict("toy", [{"a": 7, "b": 3}])
        out = hub.gc(keep=1)
        assert out["steps_removed"] == 2 and out["tmp_removed"] == 1
        assert out["journal"]["records_out"] == 1
        assert sorted(os.listdir(slot)) == ["step_000000003"]
        reloaded = PerfOracle.load(hub, "stepped_sim")
        assert np.array_equal(reloaded.predict("toy", [{"a": 7, "b": 3}]), ref)


# --------------------------------------------------------- executor-side costs
class TestExecutorSideCostTimer:
    def test_serial_executor_reports_exec_seconds(self):
        scheduler = MeasurementScheduler(SerialExecutor(SteppedSimPlatform()))
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 33), "b": (np.arange(1, 33) % 32) + 1}
        )
        scheduler.measure_batch("stepped_sim", "toy", batch)
        items, spent = scheduler._exec_costs["configs"]
        assert items == 32 and spent > 0.0
        assert scheduler.stats.exec_seconds == spent
        assert scheduler.stats.snapshot()["exec_seconds"] == spent
        # exec time excludes dispatch overhead, so it never exceeds wall
        assert spent <= scheduler._path_costs["configs"][1]

    def test_exec_costs_preferred_over_wall_costs(self):
        scheduler = MeasurementScheduler(SerialExecutor(SteppedSimPlatform()))
        scheduler._path_costs["configs"] = [10, 100.0]  # wall says 1 chunk=0
        scheduler._exec_costs["configs"] = [100, 1.0]  # exec says 10 ms/item
        assert scheduler.effective_chunk_size("configs") == 100
        # no exec data for blocks: falls back to the wall pool untouched
        scheduler._path_costs["blocks"] = [10, 20.0]
        assert scheduler.effective_chunk_size("blocks") == 1

    def test_bare_array_results_still_accepted(self):
        """Third-party executors may return arrays without a timing tuple."""

        class BareExecutor(SerialExecutor):
            def submit(self, layer_type, batch):
                future = Future()
                future.set_result(
                    np.asarray(
                        self.platform.measure_batch(layer_type, batch),
                        dtype=np.float64,
                    )
                )
                return future

        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 17), "b": (np.arange(1, 17) % 32) + 1}
        )
        scheduler = MeasurementScheduler(BareExecutor(platform))
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert scheduler._exec_costs["configs"] == [0, 0.0]
        assert scheduler.stats.exec_seconds == 0.0

    def test_worker_pool_reports_exec_seconds_across_processes(self):
        platform = SteppedSimPlatform(delay_s=0.001)
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 17), "b": (np.arange(1, 17) % 32) + 1}
        )
        pool = WorkerPool(platform.spawn_spec(), workers=2)
        try:
            scheduler = MeasurementScheduler(pool, chunk_size=8)
            y = scheduler.measure_batch("stepped_sim", "toy", batch)
        finally:
            pool.close()
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        items, spent = scheduler._exec_costs["configs"]
        assert items == 16 and spent >= 16 * 0.001
