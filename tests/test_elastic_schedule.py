"""Step-based schedule parsing + elastic dataset unit tests, and the
schedule-driven elastic training e2e.

Parity: ops/cpu/elastic.cpp:16-81 (schedule), v1/datasets/adaptor.py
(elastic dataset), hooks/elastic.py (schedule-driven training).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from ports import kfrun_ports

from kungfu_tpu.elastic.dataset import ElasticDataset
from kungfu_tpu.elastic.schedule import parse_schedule, schedule_target

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AGENT = os.path.join(REPO, "tests", "integration", "schedule_agent.py")


class TestSchedule:
    def test_parse(self):
        assert parse_schedule("2:10,4:20,1:5") == [(2, 10), (4, 20), (1, 5)]
        assert parse_schedule(" 3:7 ") == [(3, 7)]

    def test_parse_rejects_garbage(self):
        for bad in ("", "0:5", "2:-1", "2:0", "x:1"):
            with pytest.raises(ValueError):
                parse_schedule(bad)

    def test_target_by_step(self):
        s = parse_schedule("2:10,4:20,1:5")
        assert schedule_target(s, 0) == 2
        assert schedule_target(s, 9) == 2
        assert schedule_target(s, 10) == 4
        assert schedule_target(s, 29) == 4
        assert schedule_target(s, 30) == 1
        assert schedule_target(s, 34) == 1
        assert schedule_target(s, 35) is None  # exhausted


class TestElasticDataset:
    def _ds(self, n=100, b=8):
        x = np.arange(n)
        return ElasticDataset([x], b, seed=1)

    def test_batches_partition_cluster_step(self):
        """One cluster step at size k covers k disjoint batches."""
        ds = self._ds()
        got = np.concatenate(
            [ds.batch_at(0, r, 4)[0] for r in range(4)]
        )
        assert len(set(got.tolist())) == 32  # no duplicates within the step

    def test_progress_continuity_across_resize(self):
        """Samples consumed before and after a resize don't overlap within
        one epoch."""
        ds = self._ds(n=1000, b=10)
        before = np.concatenate(
            [ds.batch_at(0, r, 2)[0] for r in range(2)]
        )  # progress 0..20
        after = np.concatenate(
            [ds.batch_at(20, r, 3)[0] for r in range(3)]
        )  # progress 20..50 on the grown cluster
        assert not set(before.tolist()) & set(after.tolist())

    def test_epoch_wrap(self):
        ds = self._ds(n=10, b=8)
        (b,) = ds.batch_at(8, 0, 1)  # crosses into epoch 1
        assert len(b) == 8
        assert all(0 <= v < 10 for v in b)

    def test_deterministic(self):
        a = self._ds().batch_at(16, 1, 2)[0]
        b = self._ds().batch_at(16, 1, 2)[0]
        assert np.array_equal(a, b)

    def test_rejects_mismatched_arrays(self):
        with pytest.raises(ValueError):
            ElasticDataset([np.arange(4), np.arange(5)], 2)

    def test_cluster_delta(self):
        assert self._ds(b=8).cluster_delta(4) == 32


def test_schedule_driven_elastic_training_converges():
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [
            sys.executable, "-m", "kungfu_tpu.runner.cli",
            *kfrun_ports().args,  # this xdist worker's block
            "-np", "2",
            "-H", "127.0.0.1:4",
            "-w",
            "-builtin-config-port", "0",
            "--", sys.executable, AGENT,
        ],
        env=env, capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    finished = [l for l in r.stdout.splitlines() if "reason=finished" in l]
    assert len(finished) == 2, r.stdout  # final size per the schedule


class TestMaybeProposeRetry:
    """A lost proposal must be retried by the (acting) rank 0 instead of
    the schedule silently skipping the resize (ADVICE r2)."""

    def _patch(self, monkeypatch, rank, size, fail_once=False):
        import kungfu_tpu.elastic.schedule as sched_mod

        calls = []
        state = {"fail": fail_once}

        def propose(n):
            if state["fail"]:
                state["fail"] = False
                raise ConnectionError("config server blip")
            calls.append(n)

        monkeypatch.setattr(sched_mod.api, "current_rank", lambda: rank)
        monkeypatch.setattr(sched_mod.api, "cluster_size", lambda: size)
        monkeypatch.setattr(sched_mod.api, "propose_new_size", propose)
        return calls

    def test_failed_propose_is_retried(self, monkeypatch):
        from kungfu_tpu.elastic.schedule import StepBasedSchedule

        calls = self._patch(monkeypatch, rank=0, size=2, fail_once=True)
        s = StepBasedSchedule("4:10")
        # transient PUT failure is swallowed (ADVICE r3): the proposing
        # worker must not die over a blip; _last_proposed stays unset
        assert s.maybe_propose(0) is None
        assert s.maybe_propose(1) == 4  # retried
        assert calls == [4]
        assert s.maybe_propose(2) is None  # proposed, awaiting consensus

    def test_new_acting_rank0_reproposes(self, monkeypatch):
        """If the proposing rank 0 detaches, the next acting rank 0 (a
        different process whose _last_proposed was never set) proposes."""
        from kungfu_tpu.elastic.schedule import StepBasedSchedule

        calls = self._patch(monkeypatch, rank=1, size=2)
        s = StepBasedSchedule("4:10")
        assert s.maybe_propose(0) is None  # not rank 0: never proposes
        assert calls == []
        # … original rank 0 died; this peer becomes rank 0
        import kungfu_tpu.elastic.schedule as sched_mod

        monkeypatch.setattr(sched_mod.api, "current_rank", lambda: 0)
        assert s.maybe_propose(1) == 4
        assert calls == [4]

    def test_satisfied_target_not_proposed(self, monkeypatch):
        from kungfu_tpu.elastic.schedule import StepBasedSchedule

        calls = self._patch(monkeypatch, rank=0, size=4)
        s = StepBasedSchedule("4:10,2:5")
        assert s.maybe_propose(0) is None  # already at 4
        assert calls == []
        assert s.maybe_propose(10) == 2  # next boundary proposes
        assert calls == [2]
