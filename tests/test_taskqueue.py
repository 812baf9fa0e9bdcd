"""Task queue fault tolerance: leases, retries, speculation, elasticity."""

import pytest

from repro.core.metadata import MetadataStore
from repro.core.taskqueue import DEAD, DONE, PENDING, RUNNING, TaskQueue, run_workers
from repro.launch.elastic import ElasticTrainer, RangeSpec, submit_step_ranges


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_happy_path():
    q = TaskQueue()
    q.submit_batch({f"t{i}": i for i in range(10)})
    run_workers(q, lambda x: x + 1, num_workers=3)
    assert q.done()
    assert q.results()["t3"] == 4


def test_priority_order():
    clock = Clock()
    q = TaskQueue(clock=clock)
    q.submit("low", 1, priority=0)
    q.submit("high", 2, priority=10)
    assert q.claim("w").task_id == "high"
    assert q.claim("w").task_id == "low"


def test_lease_expiry_requeues():
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", "payload")
    t1 = q.claim("w1")
    assert t1 is not None and q.counts()[RUNNING] == 1
    clock.t = 11.0  # w1 died: lease expired
    t2 = q.claim("w2")
    assert t2 is not None and t2.task_id == "t" and t2.attempt == 2
    assert q.stats["expired"] == 1


def test_heartbeat_extends_lease():
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", 0)
    q.claim("w1")
    clock.t = 8.0
    assert q.heartbeat("t", "w1")
    clock.t = 15.0  # within the extended lease
    assert q.claim("w2") is None  # not expired
    assert q.counts()[RUNNING] == 1


def test_max_retries_dead_letter():
    clock = Clock()
    q = TaskQueue(clock=clock)
    q.submit("t", 0, max_retries=2)
    for i in range(3):
        task = q.claim(f"w{i}")
        q.fail("t", f"w{i}", "boom")
    assert q.counts()[DEAD] == 1
    assert q.dead_tasks()[0].error == "boom"


def test_dead_letter_keeps_the_first_error():
    """A retry that fails differently does not hide the first cause."""
    q = TaskQueue()
    q.submit("t", 0, max_retries=1)
    for worker, error in (("w0", "out of memory"), ("w1", "boom")):
        q.claim(worker)
        q.fail("t", worker, error)
    assert q.counts()[DEAD] == 1
    assert q.dead_tasks()[0].error == "out of memory"


def test_idempotent_completion():
    q = TaskQueue()
    q.submit("t", 0)
    q.claim("w1")
    assert q.complete("t", "w1", "r1")
    assert not q.complete("t", "w2", "r2")  # duplicate ignored
    assert q.results()["t"] == "r1"
    assert q.stats["duplicate_completions"] == 1


def test_straggler_speculation():
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=1000,
                  speculation_factor=3.0, min_completions_for_speculation=3)
    for i in range(4):
        q.submit(f"fast{i}", i)
    q.submit("slow", 99)
    # complete 4 fast tasks at t=1 each to establish the median
    for i in range(4):
        t = q.claim("w1")
        clock.t += 1.0
        q.complete(t.task_id, "w1")
    slow = q.claim("w1")
    assert slow.task_id == "slow"
    clock.t += 50.0  # way beyond 3x median
    spec = q.claim("w2")  # no pending work -> speculate on the straggler
    assert spec is not None and spec.task_id == "slow"
    assert q.stats["speculated"] == 1
    # first completion wins
    assert q.complete("slow", "w2", "spec-won")
    assert not q.complete("slow", "w1", "late")
    assert q.results()["slow"] == "spec-won"


def test_lease_expiry_reclaim_first_completion_wins():
    """A dead worker's task is re-claimed; its late completion is ignored."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", "payload")
    assert q.claim("w1").task_id == "t"
    clock.t = 11.0  # w1 presumed dead
    t2 = q.claim("w2")
    assert t2.task_id == "t" and q.stats["expired"] == 1
    assert q.complete("t", "w2", "w2-result")
    assert not q.complete("t", "w1", "w1-late")  # zombie finishes late
    assert q.results()["t"] == "w2-result"
    assert q.stats["duplicate_completions"] == 1


def test_lease_expiry_exhausts_retries_to_dead():
    """Repeated expiry (not explicit fail) also lands in the dead letter."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", 0, max_retries=1)
    assert q.claim("w1").attempt == 1
    clock.t = 11.0
    assert q.claim("w2").attempt == 2  # expiry -> requeue -> re-claim
    clock.t = 22.0
    assert q.claim("w3") is None  # second expiry exhausts retries
    assert q.counts()[DEAD] == 1 and q.stats["dead"] == 1
    assert "lease expired" in q.dead_tasks()[0].error
    assert q.done()  # dead tasks don't wedge the campaign


def test_late_completion_cannot_resurrect_dead_task():
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", 0, max_retries=0)
    q.claim("w1")
    clock.t = 11.0
    assert q.claim("w2") is None  # expiry exhausts retries -> DEAD
    assert q.counts()[DEAD] == 1
    assert not q.complete("t", "w1", "late")  # zombie result rejected
    assert q.counts()[DEAD] == 1 and len(q.dead_tasks()) == 1
    assert q.stats["duplicate_completions"] == 1
    assert "t" not in q.results()


def test_zombie_fail_and_heartbeat_after_expiry_ignored():
    """A dead worker's late fail/heartbeat must not disturb the re-claim."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", 0)
    q.claim("w1")
    clock.t = 11.0  # w1 presumed dead
    t2 = q.claim("w2")
    assert t2.task_id == "t"
    assert not q.heartbeat("t", "w1")  # zombie can't extend w2's lease
    q.fail("t", "w1", "late failure from dead worker")  # ignored
    assert q.counts()[RUNNING] == 1 and q.stats["retried"] == 0
    assert q.complete("t", "w2", "ok")
    assert q.results()["t"] == "ok"


def test_zombie_late_complete_keeps_first_completion_time():
    """After a speculation handoff, the crashed worker's late complete
    must neither overwrite the result nor move the completion timestamp
    (the instant a serving tier turns into latency) — and it must count
    as a duplicate, not a second completion."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("t", 0)
    q.claim("w1")
    clock.t = 11.0  # w1 crashed: lease expires, w2 takes over
    assert q.claim("w2").task_id == "t"
    clock.t = 12.5
    assert q.complete("t", "w2", "fresh")
    assert q.completion_times() == {"t": 12.5}
    clock.t = 99.0  # the zombie wakes up and reports
    assert not q.complete("t", "w1", "stale")
    assert not q.heartbeat("t", "w1")
    assert q.completion_times() == {"t": 12.5}  # timestamp unmoved
    assert q.results()["t"] == "fresh"
    assert q.stats["completed"] == 1
    assert q.stats["duplicate_completions"] == 1


def test_speculation_duplicate_dispatch_original_wins():
    """Speculative twin dispatched, but the original finishes first."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=1000,
                  speculation_factor=3.0, min_completions_for_speculation=3)
    for i in range(3):
        q.submit(f"fast{i}", i)
    q.submit("slow", 99)
    for _ in range(3):
        t = q.claim("w1")
        clock.t += 1.0
        q.complete(t.task_id, "w1")
    assert q.claim("w1").task_id == "slow"
    clock.t += 50.0
    spec = q.claim("w2")  # duplicate-dispatch of the straggler
    assert spec is not None and spec.task_id == "slow"
    assert q.complete("slow", "w1", "original-won")
    assert not q.complete("slow", "w2", "spec-late")
    assert q.results()["slow"] == "original-won"
    assert q.stats["speculated"] == 1
    assert q.stats["duplicate_completions"] == 1


def test_worker_exception_retries_then_succeeds():
    q = TaskQueue()
    q.submit("t", 0, max_retries=3)
    attempts = {"n": 0}

    def handler(_):
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise ValueError("flaky")
        return "ok"

    run_workers(q, handler, num_workers=2)
    assert q.results()["t"] == "ok"
    assert q.stats["retried"] == 2


# ---------------------------------------------------------------------------
# elastic trainer on top of the queue
# ---------------------------------------------------------------------------
def test_elastic_trainer_preemption_resume():
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=5)
    submit_step_ranges(q, total_steps=30, range_size=10)

    committed = {"step": 0}
    steps_run = []

    def mk(worker):
        return ElasticTrainer(
            q, worker,
            step_fn=lambda s: steps_run.append(s),
            save_fn=lambda s: committed.__setitem__("step", s),
            restore_fn=lambda: committed["step"],
            lease_s=5)

    # worker 1 dies mid-second-range (no fail, no complete)
    w1 = mk("w1")
    w1.run_once()  # range 0..10 committed
    assert committed["step"] == 10
    w1.run_once(die_at_step=13)  # abandons 10..20 at step 13
    assert committed["step"] == 10  # nothing committed

    clock.t += 10.0  # lease expires
    w2 = mk("w2")
    while w2.run_once() is not None:
        pass
    assert committed["step"] == 30
    # no step below the last commit was lost; re-run from 10 is expected
    assert max(steps_run) == 29
    assert q.done()


def test_pending_by_pool_tracks_every_transition():
    """The per-pool PENDING counter (the autoscaler's backlog signal) must
    stay exact through submit, claim, lease-expiry requeue, retry, and the
    zombie-completion-from-PENDING corner."""
    clock = Clock()
    q = TaskQueue(clock=clock, default_lease_s=10)
    q.submit("a0", 0, pool="a")
    q.submit("a1", 1, pool="a")
    q.submit("d0", 2)  # default pool
    assert q.pending_by_pool() == {"a": 2, None: 1}
    t = q.claim("w1", pool="a")
    assert t.task_id == "a0"
    assert q.pending_by_pool() == {"a": 1, None: 1}
    # lease expires: a0 re-queued, the count comes back
    clock.t = 11.0
    assert q.claim("w2", pool="b") is None  # triggers the reap
    assert q.pending_by_pool() == {"a": 2, None: 1}
    # the zombie's late completion lands while a0 is PENDING: consumed
    # without ever being claimed again
    assert q.complete("a0", "w1") is True
    assert q.pending_by_pool() == {"a": 1, None: 1}
    # a failure retries back to PENDING
    t = q.claim("w2", pool="a")
    q.fail(t.task_id, "w2", "boom")
    assert q.pending_by_pool() == {"a": 1, None: 1}
    # and the counter always matches a fresh scan
    scan = {}
    for task in q._tasks.values():
        if task.state == PENDING:
            scan[task.pool] = scan.get(task.pool, 0) + 1
    assert q.pending_by_pool() == scan
