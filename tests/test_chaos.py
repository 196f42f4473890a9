"""Chaos-hardened runtime: deterministic fault injection, health, fsck.

The hard invariant under test: for ANY fault schedule the runtime can
survive, the campaign's results — estimator checkpoints, predictions, cache
accounting — are **bitwise identical** to a fault-free run, with zero
duplicate durable measurements; schedules it cannot survive end in a typed
:class:`MeasurementError` naming the exhausted budget, never a silent
partial result.  Faults are injected through :class:`FaultPlan` — seeded,
replayable schedules whose events are indistinguishable from organic
failures (a crash fails like a died worker, a corrupt payload carries a
stale integrity envelope, a torn write leaves real torn bytes on disk).
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time

import numpy as np
import pytest

import repro.runtime.testing  # noqa: F401  (registers "stepped_sim")
from repro.api import Campaign, CampaignSpec, MeasurementCache, RuntimeSpec
from repro.core.batch import ConfigBatch
from repro.runtime import (
    DegradationReport,
    FaultEvent,
    FaultPlan,
    FaultyExecutor,
    HealthPolicy,
    HealthTracker,
    MeasurementError,
    MeasurementJournal,
    MeasurementScheduler,
    SerialExecutor,
    TornWrite,
    WorkerPool,
)
from repro.runtime.faults import CHUNK_SITE, JOURNAL_SITE, corrupt_payload
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


def _hub_content(hub_dir) -> dict:
    """Persisted hub bytes, normalized for wall-clock-only fields (see
    tests/test_measurement_runtime.py for the rationale)."""
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


def _clean_run(tmp_path, name="clean"):
    """Reference fault-free campaign: (hub content, predictions, cache misses)."""
    hub = tmp_path / name
    campaign = Campaign(_spec(hub_dir=str(hub)))
    oracle = campaign.run(
        runtime=RuntimeSpec(workers=1, chunk_size=8, journal_path="")
    )
    return _hub_content(hub), oracle.predict("toy", QUERIES), campaign.cache.misses


# ------------------------------------------------------------------ fault plan
class TestFaultPlan:
    def test_sample_is_reproducible_from_seed(self):
        a = FaultPlan.sample(seed=7, n_faults=5, horizon=20, journal_faults=2)
        b = FaultPlan.sample(seed=7, n_faults=5, horizon=20, journal_faults=2)
        assert a.describe() == b.describe()
        assert len(a.events) == 7
        c = FaultPlan.sample(seed=8, n_faults=5, horizon=20, journal_faults=2)
        assert a.describe() != c.describe()

    def test_event_validation(self):
        with pytest.raises(ValueError, match="site"):
            FaultEvent("nowhere", 0, "crash")
        with pytest.raises(ValueError, match="not injectable"):
            FaultEvent(JOURNAL_SITE, 0, "crash")
        with pytest.raises(ValueError, match="not injectable"):
            FaultEvent(CHUNK_SITE, 0, "torn_write")
        with pytest.raises(ValueError, match="index"):
            FaultEvent(CHUNK_SITE, -1, "crash")
        with pytest.raises(TypeError):
            FaultPlan(["crash"])
        with pytest.raises(ValueError, match="duplicate"):
            FaultPlan(
                [FaultEvent(CHUNK_SITE, 3, "crash"), FaultEvent(CHUNK_SITE, 3, "slow")]
            )

    def test_take_consumes_each_event_exactly_once(self):
        event = FaultEvent(CHUNK_SITE, 2, "crash")
        plan = FaultPlan([event])
        assert plan.take(CHUNK_SITE, 0) is None
        assert plan.take(CHUNK_SITE, 2) is event
        assert plan.take(CHUNK_SITE, 2) is None  # consumed
        assert plan.fired() == (event,)

    def test_corrupt_payload_flips_exactly_the_low_mantissa_bit(self):
        y = np.array([1.0, 2.5e-6, -3.0])
        c = corrupt_payload(y)
        assert not np.array_equal(c, y)  # bitwise different...
        assert np.allclose(c, y)  # ...numerically indistinguishable


# --------------------------------------------------- campaign chaos invariant
class TestChaosCampaignInvariant:
    def _chaos_run(self, tmp_path, plan, name, workers=1, max_retries=3, **rt):
        hub = tmp_path / name
        campaign = Campaign(_spec(hub_dir=str(hub)))
        oracle = campaign.run(
            runtime=RuntimeSpec(
                workers=workers,
                chunk_size=8,
                max_retries=max_retries,
                retry_backoff_s=0.001,
                journal_path="",
                fault_plan=plan,
                **rt,
            )
        )
        return (
            _hub_content(hub),
            oracle.predict("toy", QUERIES),
            campaign.cache.misses,
            campaign.last_run_stats["degradation"],
        )

    def test_targeted_faults_leave_results_bitwise_identical(self, tmp_path):
        ref_hub, ref_preds, ref_misses = _clean_run(tmp_path)
        plan = FaultPlan(
            [
                FaultEvent(CHUNK_SITE, 0, "crash"),
                FaultEvent(CHUNK_SITE, 2, "corrupt"),
                FaultEvent(CHUNK_SITE, 4, "slow", delay_s=0.02),
            ]
        )
        hub, preds, misses, degradation = self._chaos_run(tmp_path, plan, "chaos")
        assert hub == ref_hub
        assert np.array_equal(preds, ref_preds)
        assert misses == ref_misses  # zero duplicate measurements
        assert degradation["injected"] == 3
        assert degradation["crashes"] == 1
        assert degradation["corrupt_results"] == 1

    def test_hang_is_timed_out_and_survived(self, tmp_path):
        ref_hub, ref_preds, ref_misses = _clean_run(tmp_path)
        plan = FaultPlan([FaultEvent(CHUNK_SITE, 1, "hang", delay_s=2.0)])
        hub, preds, misses, degradation = self._chaos_run(
            tmp_path, plan, "hang", chunk_timeout_s=0.1
        )
        assert hub == ref_hub
        assert np.array_equal(preds, ref_preds)
        assert misses == ref_misses
        assert degradation["injected"] == 1
        assert degradation["hangs"] == 1

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_sampled_schedules_are_survived_bitwise(self, tmp_path, seed):
        ref_hub, ref_preds, ref_misses = _clean_run(tmp_path)
        plan = FaultPlan.sample(
            seed=seed, n_faults=4, horizon=8, kinds=("crash", "corrupt", "slow")
        )
        hub, preds, misses, degradation = self._chaos_run(
            tmp_path, plan, f"sampled{seed}"
        )
        assert hub == ref_hub
        assert np.array_equal(preds, ref_preds)
        assert misses == ref_misses
        assert degradation["injected"] >= 1  # the plan actually bit

    def test_pool_chaos_is_survived_bitwise(self, tmp_path):
        ref_hub, ref_preds, ref_misses = _clean_run(tmp_path)
        plan = FaultPlan(
            [
                FaultEvent(CHUNK_SITE, 1, "crash"),
                FaultEvent(CHUNK_SITE, 3, "corrupt"),
            ]
        )
        hub, preds, misses, degradation = self._chaos_run(
            tmp_path, plan, "pool", workers=2
        )
        assert hub == ref_hub
        assert np.array_equal(preds, ref_preds)
        assert misses == ref_misses
        assert degradation["injected"] == 2

    def test_fault_free_hooks_change_no_measurement(self):
        """An empty fault plan and a live health tracker leave every number
        as a scheduler without them measures it."""
        platform = SteppedSimPlatform()
        rows = np.arange(512, dtype=np.int64)
        batch = ConfigBatch.from_columns({"a": rows % 64 + 1, "b": rows % 32 + 1})
        hooked = MeasurementScheduler(
            FaultyExecutor(SerialExecutor(platform), FaultPlan([])),
            chunk_size=128,
            health=HealthTracker(HealthPolicy()),
        )
        plain = MeasurementScheduler(SerialExecutor(platform), chunk_size=128, health=None)
        assert np.array_equal(
            hooked.measure_batch("stepped_sim", "toy", batch),
            plain.measure_batch("stepped_sim", "toy", batch),
        )
        assert hooked.stats.degradation.injected == 0

    def test_exhausted_budget_is_a_typed_error(self, tmp_path):
        plan = FaultPlan([FaultEvent(CHUNK_SITE, i, "crash") for i in range(3)])
        campaign = Campaign(_spec())
        with pytest.raises(MeasurementError, match=r"failed after 3 attempt"):
            campaign.run(
                runtime=RuntimeSpec(
                    workers=1,
                    chunk_size=64,  # a single chunk: all 3 attempts crash
                    max_retries=2,
                    retry_backoff_s=0.001,
                    journal_path="",
                    fault_plan=plan,
                )
            )


# ----------------------------------------------------------------- quarantine
class TestQuarantine:
    def test_repeat_offender_shrinks_the_pool_bitwise(self):
        """corrupt results are attributable (the integrity envelope names the
        pid); with quarantine_after=1 the first one evicts the worker —
        pool shrinks by a slot, results stay bitwise-identical."""
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 49), "b": (np.arange(1, 49) % 32) + 1}
        )
        expected = platform.measure_batch("toy", batch)
        plan = FaultPlan([FaultEvent(CHUNK_SITE, 0, "corrupt")])
        pool = WorkerPool(platform.spawn_spec(), workers=2)
        try:
            scheduler = MeasurementScheduler(
                FaultyExecutor(pool, plan),
                chunk_size=8,
                max_retries=2,
                retry_backoff_s=0.001,
                health=HealthTracker(HealthPolicy(quarantine_after=1)),
            )
            y = scheduler.measure_batch("stepped_sim", "toy", batch)
        finally:
            pool.close()
        assert np.array_equal(y, expected)
        assert pool.workers == 1  # shrank from 2
        assert len(pool.quarantined) == 1
        assert pool.quarantined[0] is not None  # the envelope named the pid
        assert scheduler.stats.degradation.quarantines == 1
        assert scheduler.stats.failures == 0

    def test_anonymous_streak_quarantines_without_attribution(self):
        """Injected crashes carry no pid; the pool-level streak still trips."""
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 17), "b": np.arange(1, 17)}
        )
        plan = FaultPlan([FaultEvent(CHUNK_SITE, 0, "crash")])
        pool = WorkerPool(platform.spawn_spec(), workers=2)
        try:
            scheduler = MeasurementScheduler(
                FaultyExecutor(pool, plan),
                chunk_size=8,
                max_retries=2,
                retry_backoff_s=0.001,
                health=HealthTracker(HealthPolicy(quarantine_after=1)),
            )
            y = scheduler.measure_batch("stepped_sim", "toy", batch)
        finally:
            pool.close()
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert pool.quarantined == [None]

    def test_health_disabled_means_no_quarantine(self):
        platform = SteppedSimPlatform()
        batch = ConfigBatch.from_columns({"a": np.arange(1, 17), "b": np.arange(1, 17)})
        plan = FaultPlan([FaultEvent(CHUNK_SITE, 0, "crash")])
        scheduler = MeasurementScheduler(
            FaultyExecutor(SerialExecutor(platform), plan),
            chunk_size=8,
            max_retries=2,
            retry_backoff_s=0.001,
            health=None,
        )
        y = scheduler.measure_batch("stepped_sim", "toy", batch)
        assert np.array_equal(y, platform.measure_batch("toy", batch))
        assert scheduler.stats.degradation.quarantines == 0


# -------------------------------------------------------------- worker SIGKILL
class TestWorkerSigkill:
    def test_sigkilled_pool_worker_is_respawned_bitwise(self):
        """A real worker process SIGKILLed mid-chunk: the pool breaks, the
        scheduler respawns it, retries the lost chunks, and the merged result
        is bitwise-identical to an undisturbed run."""
        platform = SteppedSimPlatform(delay_s=0.02)
        batch = ConfigBatch.from_columns(
            {"a": np.arange(1, 49), "b": (np.arange(1, 49) % 32) + 1}
        )
        expected = SteppedSimPlatform().measure_batch("toy", batch)
        pool = WorkerPool(platform.spawn_spec(), workers=2)
        killed = []

        def assassin() -> None:
            deadline = time.perf_counter() + 30
            while time.perf_counter() < deadline:
                procs = list((pool._pool._processes or {}).values())
                if procs:
                    os.kill(procs[0].pid, signal.SIGKILL)
                    killed.append(procs[0].pid)
                    return
                time.sleep(0.01)

        try:
            scheduler = MeasurementScheduler(
                pool, chunk_size=8, max_retries=3, retry_backoff_s=0.01
            )
            killer = threading.Thread(target=assassin, daemon=True)
            killer.start()
            y = scheduler.measure_batch("stepped_sim", "toy", batch)
            killer.join(timeout=30)
        finally:
            pool.close()
        assert killed, "no worker process appeared to kill"
        assert np.array_equal(y, expected)
        assert pool.respawns >= 1
        assert scheduler.stats.failures == 0


# ------------------------------------------------------------------ torn write
class TestTornWriteResume:
    def test_injected_torn_write_then_fsck_and_bitwise_resume(self, tmp_path):
        """A journal append torn mid-record kills the run; fsck names the
        damage; a resumed campaign replays every durable chunk (re-measuring
        none of them) and finishes bitwise-identical to an undisturbed run."""
        journal = str(tmp_path / "j.jsonl")
        plan = FaultPlan([FaultEvent(JOURNAL_SITE, 2, "torn_write")])
        crashed = Campaign(_spec())
        # the torn write emulates a crash mid-write(2): the run dies with the
        # injected fault, leaving real torn bytes on disk
        with pytest.raises(TornWrite):
            crashed.run(
                runtime=RuntimeSpec(
                    workers=1, chunk_size=8, journal_path=journal, fault_plan=plan
                )
            )
        report = MeasurementJournal(journal).fsck()
        assert report["torn_tail"] is True
        assert report["records"] == 2  # appends 0 and 1 are durable
        assert report["corrupt_lines"] == 1  # the torn fragment
        durable_rows = report["rows"]
        assert durable_rows == 16

        resumed = Campaign(_spec())
        oracle = resumed.run(
            runtime=RuntimeSpec(workers=1, chunk_size=8, journal_path=journal)
        )
        control = Campaign(_spec())
        control_oracle = control.run(runtime=RuntimeSpec(workers=1, chunk_size=8))
        assert np.array_equal(
            oracle.predict("toy", QUERIES), control_oracle.predict("toy", QUERIES)
        )
        # nothing durable was re-measured, nothing was measured twice
        assert resumed.cache.replayed == durable_rows
        assert resumed.cache.misses == control.cache.misses - durable_rows

    def test_next_append_seals_a_torn_tail(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        with open(path, "w") as f:
            f.write('{"torn": ')  # fragment, no newline
        batch = ConfigBatch.from_dicts([{"a": 1, "b": 2}])
        with MeasurementJournal(path) as journal:
            journal.append_chunk("p", "toy", batch, np.array([1e-6]))
            assert journal.sealed_tails == 1
        cache = MeasurementCache()
        import warnings

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # the sealed fragment is corrupt
            replay = MeasurementJournal(path).replay_into(cache)
        assert replay["rows"] == 1  # the fragment cost one line, not two

    def test_manually_truncated_record_fsck_repair_resume(self, tmp_path):
        """Torn write emulated the crude way — truncate the file mid-record —
        then fsck --repair compacts the damage away and resume re-measures
        only the lost rows."""
        journal = str(tmp_path / "j.jsonl")
        full = Campaign(_spec())
        full.run(runtime=RuntimeSpec(workers=1, chunk_size=8, journal_path=journal))
        size = os.path.getsize(journal)
        with open(journal, "rb") as f:
            data = f.read()
        # cut halfway into the final record
        last_line_start = data.rstrip(b"\n").rfind(b"\n") + 1
        cut = last_line_start + (len(data) - last_line_start) // 2
        with open(journal, "r+b") as f:
            f.truncate(cut)
        assert os.path.getsize(journal) < size

        report = MeasurementJournal(journal).fsck(repair=True)
        assert report["torn_tail"] is True and report["corrupt_lines"] == 1
        assert report["repaired"] is True
        after = report["after"]
        assert after["torn_tail"] is False
        assert after["corrupt_lines"] == 0 and after["duplicate_keys"] == 0

        resumed = Campaign(_spec())
        oracle = resumed.run(
            runtime=RuntimeSpec(workers=1, chunk_size=8, journal_path=journal)
        )
        control = Campaign(_spec())
        control_oracle = control.run(runtime=RuntimeSpec(workers=1, chunk_size=8))
        assert np.array_equal(
            oracle.predict("toy", QUERIES), control_oracle.predict("toy", QUERIES)
        )
        assert resumed.cache.replayed == after["rows"]
        assert resumed.cache.misses == control.cache.misses - after["rows"]


# ------------------------------------------------------------------------ fsck
class TestJournalFsck:
    def _write_chunks(self, path, n_chunks=2):
        with MeasurementJournal(path) as journal:
            for c in range(n_chunks):
                batch = ConfigBatch.from_columns(
                    {"a": np.arange(1, 4) + 10 * c, "b": np.arange(1, 4)}
                )
                journal.append_chunk(
                    "stepped_sim", "toy", batch, np.full(3, 1e-6 * (c + 1))
                )

    def test_clean_journal_reports_no_issues(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        self._write_chunks(path)
        report = MeasurementJournal(path).fsck()
        assert report["exists"] is True
        assert report["records"] == 2 and report["rows"] == 6
        assert report["corrupt_lines"] == 0
        assert report["torn_tail"] is False
        assert report["duplicate_keys"] == 0
        assert report["repaired"] is False

    def test_missing_journal(self, tmp_path):
        report = MeasurementJournal(str(tmp_path / "absent.jsonl")).fsck()
        assert report["exists"] is False and report["records"] == 0

    def test_detects_torn_tail_and_corrupt_lines(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        self._write_chunks(path)
        with open(path, "a") as f:
            f.write("garbage line\n")
            f.write('{"v": 1, "torn": ')  # no newline: torn tail
        report = MeasurementJournal(path).fsck()
        assert report["torn_tail"] is True
        assert report["corrupt_lines"] == 2
        assert report["records"] == 2  # intact records still counted

    def test_counts_duplicate_keys_and_kind_switches(self, tmp_path):
        from repro.core.batch import BlockBatch
        from repro.core.blocks import Block

        path = str(tmp_path / "j.jsonl")
        batch = ConfigBatch.from_dicts([{"a": 1, "b": 2}])
        blocks = BlockBatch.from_blocks(
            [Block(kind="k", layers=(("toy", {"a": 2, "b": 2}),))]
        )
        with MeasurementJournal(path) as journal:
            journal.append_chunk("p", "toy", batch, np.array([1.0]))
            journal.append_block_chunk("p", blocks, np.array([0.1]))
            journal.append_chunk("p", "toy", batch, np.array([2.0]))  # retry dup
        report = MeasurementJournal(path).fsck()
        assert report["duplicate_keys"] == 1
        assert report["kind_switches"] == 2
        assert report["rows"] == 2  # unique measurements

    def test_repair_compacts_and_recheck_is_clean(self, tmp_path):
        path = str(tmp_path / "j.jsonl")
        self._write_chunks(path)
        batch = ConfigBatch.from_columns({"a": np.arange(1, 4), "b": np.arange(1, 4)})
        with MeasurementJournal(path) as journal:
            journal.append_chunk("stepped_sim", "toy", batch, np.full(3, 9e-6))
        with open(path, "a") as f:
            f.write('{"half a record')
        report = MeasurementJournal(path).fsck(repair=True)
        assert report["repaired"] is True
        assert report["compaction"]["records_out"] <= report["records"]
        after = report["after"]
        assert after["torn_tail"] is False
        assert after["corrupt_lines"] == after["duplicate_keys"] == 0
        # replay yields exactly the pre-repair last-writer-wins values
        cache = MeasurementCache()
        MeasurementJournal(path).replay_into(cache)
        assert cache.lookup("stepped_sim", "toy", {"a": 1, "b": 1}) == 9e-6


# -------------------------------------------------------------- health tracker
class TestHealthTracker:
    def test_consecutive_failures_advise_quarantine(self):
        tracker = HealthTracker(HealthPolicy(quarantine_after=3))
        assert tracker.record_failure(pid=11) is False
        assert tracker.record_failure(pid=11) is False
        assert tracker.record_failure(pid=11) is True  # third strike
        snap = tracker.snapshot()["workers"][0]
        assert snap["pid"] == 11 and snap["failures"] == 3
        assert snap["quarantined"] is True

    def test_success_resets_the_streak(self):
        tracker = HealthTracker(HealthPolicy(quarantine_after=2))
        tracker.record_failure(pid=7)
        tracker.record_success(pid=7, exec_s=0.01)
        assert tracker.record_failure(pid=7) is False  # streak restarted

    def test_anonymous_failures_build_a_pool_streak(self):
        tracker = HealthTracker(HealthPolicy(quarantine_after=2))
        assert tracker.record_failure() is False
        assert tracker.record_failure() is True
        assert tracker.record_failure() is False  # streak reset after advice

    def test_slow_outlier_detection_via_ewma(self):
        tracker = HealthTracker(HealthPolicy(slow_factor=4.0))
        assert tracker.record_success(pid=5, exec_s=0.01) is None  # seeds EWMA
        assert tracker.record_success(pid=5, exec_s=0.011) is None
        assert tracker.record_success(pid=5, exec_s=0.1) == "slow"

    def test_slow_floor_gates_microsecond_jitter(self):
        """Sub-floor chunks are never "slow": at µs scale the EWMA ratio
        measures scheduler jitter, not worker health."""
        tracker = HealthTracker(HealthPolicy(slow_factor=4.0, slow_floor_s=0.05))
        assert tracker.record_success(pid=5, exec_s=1e-5) is None  # seeds EWMA
        assert tracker.record_success(pid=5, exec_s=1e-3) is None  # 100x, gated
        tracker2 = HealthTracker(HealthPolicy(slow_factor=4.0, slow_floor_s=0.0))
        assert tracker2.record_success(pid=5, exec_s=1e-5) is None
        assert tracker2.record_success(pid=5, exec_s=1e-3) == "slow"

    def test_degradation_report_counts_and_caps_events(self):
        report = DegradationReport()
        report.record("crash", chunk=0)
        report.record("corrupt", chunk=1)
        report.record("injected", site="chunk", index=0, fault="crash")
        assert report.crashes == 1 and report.corrupt_results == 1
        assert report.survived() == 2  # injected is bookkeeping, not survival
        with pytest.raises(ValueError, match="unknown"):
            report.record("gremlins")
        from repro.runtime.health import MAX_EVENTS

        for _ in range(MAX_EVENTS + 50):
            report.record("error")
        assert report.errors == MAX_EVENTS + 50  # counters stay exact
        assert len(report.events) == MAX_EVENTS  # event log is bounded
        snap = report.snapshot()
        assert snap["errors"] == MAX_EVENTS + 50
        assert snap["survived"] == report.survived()

    def test_runtime_stats_surface_degradation(self, tmp_path):
        plan = FaultPlan([FaultEvent(CHUNK_SITE, 0, "crash")])
        campaign = Campaign(_spec())
        campaign.run(
            runtime=RuntimeSpec(
                workers=1,
                chunk_size=8,
                max_retries=2,
                retry_backoff_s=0.001,
                journal_path="",
                fault_plan=plan,
            )
        )
        stats = campaign.last_run_stats
        assert stats["degradation"]["crashes"] == 1
        assert stats["degradation"]["survived"] >= 1
