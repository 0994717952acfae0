"""Request-scheduler state machine + serving-stats coverage (DESIGN.md §12).

Pure host-side: no model, no jax — the scheduler and stats are plain-Python
so every admission-order / preemption / budget invariant is exact and fast.
"""
import numpy as np
import pytest

from repro.serving.scheduler import (POLICIES, Admit, Evict, Request,
                                     RequestScheduler)
from repro.serving.stats import RequestTiming, Series, ServingStats, percentile


def _req(uid, plen=4, max_new=8, priority=0):
    return Request(uid=uid, prompt=np.arange(plen, dtype=np.int32),
                   max_new_tokens=max_new, priority=priority)


def _admits(events):
    return [e for e in events if isinstance(e, Admit)]


def _evicts(events):
    return [e for e in events if isinstance(e, Evict)]


# -- admission order ---------------------------------------------------------

def test_fcfs_admits_in_arrival_order():
    s = RequestScheduler(2, policy="fcfs")
    for uid, plen in ((0, 9), (1, 2), (2, 5)):
        s.submit(_req(uid, plen=plen))
    ev = s.schedule()
    assert [a.req.uid for a in _admits(ev)] == [0, 1]     # arrival order
    assert not _evicts(ev)
    assert s.pending() == 1


def test_spf_admits_shortest_prompt_first():
    s = RequestScheduler(2, policy="spf")
    for uid, plen in ((0, 9), (1, 2), (2, 5)):
        s.submit(_req(uid, plen=plen))
    ev = s.schedule()
    assert [a.req.uid for a in _admits(ev)] == [1, 2]     # 2 < 5 < 9
    assert s.pending() == 1


def test_spf_orders_by_effective_prefix_after_progress():
    # a requeued request's committed tokens count toward its prefill cost
    s = RequestScheduler(1, policy="spf")
    r = _req(0, plen=2)
    r.out_tokens.extend([7, 7, 7, 7])                     # effective len 6
    s.submit(r)
    s.submit(_req(1, plen=4))                             # effective len 4
    ev = s.schedule()
    assert _admits(ev)[0].req.uid == 1


@pytest.mark.parametrize("policy", POLICIES)
def test_priority_ranks_above_policy_order(policy):
    s = RequestScheduler(1, policy=policy)
    s.submit(_req(0, plen=1, priority=0))
    s.submit(_req(1, plen=9, priority=3))                 # longer AND later
    ev = s.schedule()
    assert _admits(ev)[0].req.uid == 1


def test_unknown_policy_rejected():
    with pytest.raises(ValueError):
        RequestScheduler(2, policy="lifo")


# -- preemption / requeue ----------------------------------------------------

def test_preemption_evicts_lowest_priority_and_requeues():
    s = RequestScheduler(2)
    s.submit(_req(0, priority=1))
    s.submit(_req(1, priority=0))
    s.schedule()
    s.request(1).out_tokens.extend([5, 6])                # victim progress
    s.submit(_req(2, priority=5))
    ev = s.schedule()
    assert [e.req.uid for e in _evicts(ev)] == [1]        # lower prio loses
    assert [a.req.uid for a in _admits(ev)] == [2]
    # evicted request is requeued with committed tokens intact
    assert s.pending() == 1
    assert s._queue[0].uid == 1
    assert s._queue[0].out_tokens == [5, 6]


def test_preemption_requires_strictly_higher_priority():
    s = RequestScheduler(1)
    s.submit(_req(0, priority=2))
    s.schedule()
    s.submit(_req(1, priority=2))                         # equal: no preempt
    assert s.schedule() == []
    assert s.live() == [0]
    assert s.request(0).uid == 0


def test_requeued_request_keeps_fcfs_position():
    s = RequestScheduler(1, policy="fcfs")
    s.submit(_req(0, priority=0))
    s.schedule()
    s.submit(_req(1, priority=0))                         # waits behind 0
    s.submit(_req(2, priority=4))                         # preempts 0
    ev = s.schedule()
    assert _evicts(ev)[0].req.uid == 0
    assert _admits(ev)[0].req.uid == 2
    s.retire(0)                                           # uid2 finishes
    # uid0 kept its original arrival seq, so it re-admits BEFORE uid1
    ev = s.schedule()
    assert _admits(ev)[0].req.uid == 0


def test_preempt_admit_roundtrip_resumes_with_remaining_budget():
    s = RequestScheduler(1)
    s.submit(_req(0, max_new=8))
    s.schedule()
    for _ in range(3):
        s.request(0).out_tokens.append(9)
        s.on_token(0)
    s.submit(_req(1, priority=9, max_new=1))
    s.schedule()                                          # evicts uid0
    s.retire(0)
    ev = s.schedule()                                     # uid0 comes back
    a = _admits(ev)[0]
    assert a.req.uid == 0
    assert a.req.out_tokens == [9, 9, 9]
    assert int(s.remaining[a.slot]) == 5                  # 8 - 3 committed


def test_victim_is_lowest_priority_then_least_progress():
    s = RequestScheduler(3)
    for uid, prio in ((0, 1), (1, 0), (2, 0)):
        s.submit(_req(uid, priority=prio))
    s.schedule()
    s.request(1).out_tokens.extend([1, 2, 3])             # uid1 has progress
    s.submit(_req(3, priority=7))
    ev = s.schedule()
    # both uid1/uid2 are prio 0; uid2 has less progress -> cheaper to redo
    assert _evicts(ev)[0].req.uid == 2


# -- budgets -----------------------------------------------------------------

def test_budget_exhaustion_and_cap():
    s = RequestScheduler(1)
    s.submit(_req(0, max_new=3))
    ev = s.schedule()
    slot = _admits(ev)[0].slot
    s.cap_remaining(slot, 2)                              # engine capacity clamp
    assert not s.exhausted(slot)
    s.on_token(slot)
    assert not s.exhausted(slot)
    s.on_token(slot)
    assert s.exhausted(slot)


def test_retire_frees_slot_but_keeps_request_visible():
    s = RequestScheduler(1)
    s.submit(_req(0))
    s.schedule()
    s.retire(0)
    assert s.live() == []
    assert s.slots[0].uid == 0                            # still inspectable
    s.submit(_req(1))
    ev = s.schedule()
    assert _admits(ev)[0].slot == 0                       # slot was reusable


def test_schedule_is_idempotent_when_nothing_can_move():
    s = RequestScheduler(1)
    s.submit(_req(0))
    assert len(s.schedule()) == 1
    assert s.schedule() == []
    assert s.schedule() == []


# -- stats -------------------------------------------------------------------

def test_percentile_nearest_rank():
    xs = [float(v) for v in range(1, 11)]                 # 1..10
    assert percentile(xs, 50) == 5.0
    assert percentile(xs, 95) == 10.0
    assert percentile(xs, 0) == 1.0
    assert percentile([3.0], 99) == 3.0
    with pytest.raises(ValueError):
        percentile([], 50)


def test_series_summary():
    s = Series()
    for v in (1.0, 2.0, 3.0, 4.0):
        s.add(v)
    out = s.summary("x")
    assert out["x_mean"] == 2.5
    assert out["x_p50"] == 2.0
    assert Series().summary("y") == {}


def test_series_percentiles_follow_the_latest_samples():
    """Past ``max_samples`` the ring drops the oldest: after 5,000 gaps of
    which the last 4,096 are slow, p95 and p50 read the slow ones, while
    count and mean stay exact over all 5,000."""
    t = [0.0]
    stats = ServingStats(clock=lambda: t[0])
    stats.on_submit(0, stats.now())
    stats.on_token(0, stats.now())
    for i in range(5000):
        t[0] += 0.01 if i < 5000 - 4096 else 1.0
        stats.on_token(0, stats.now())
    s = stats.token_latency
    assert s.count == 5000 and len(s.samples) == 4096
    assert s.mean == pytest.approx((904 * 0.01 + 4096 * 1.0) / 5000)
    assert s.p(95) == pytest.approx(1.0) and s.p(50) == pytest.approx(1.0)
    assert stats.snapshot()["serving/token_latency_p95"] == pytest.approx(1.0)


def test_stats_lifecycle_with_fake_clock():
    t = [0.0]
    stats = ServingStats(clock=lambda: t[0])
    stats.on_submit(7, stats.now())
    t[0] = 1.0
    stats.on_admit(7, stats.now())
    t[0] = 3.0
    stats.on_token(7, stats.now())                        # first token
    t[0] = 4.0
    stats.on_token(7, stats.now())
    stats.on_preempt(7, stats.now())
    t[0] = 6.0
    stats.on_finish(7, stats.now())
    s = stats.requests[7].summary()
    assert s["queue_wait"] == 1.0
    assert s["ttft"] == 3.0
    assert s["latency"] == 6.0
    assert s["tokens"] == 2
    assert s["preemptions"] == 1
    assert s["done"]
    snap = stats.snapshot()
    assert snap["serving/requests_finished"] == 1.0
    assert snap["serving/preemptions"] == 1.0
    assert snap["serving/ttft_p50"] == 3.0
    assert snap["serving/wall_s"] == 6.0
    assert snap["serving/tokens_per_s"] == pytest.approx(2 / 6.0)


def test_stats_second_admission_keeps_first_queue_wait():
    t = [0.0]
    stats = ServingStats(clock=lambda: t[0])
    stats.on_submit(0, 0.0)
    t[0] = 2.0
    stats.on_admit(0, 2.0)
    t[0] = 5.0
    stats.on_admit(0, 5.0)                                # readmission
    assert stats.requests[0].admit_t == 2.0
    assert stats.admissions == 2
    assert stats.queue_wait.count == 1
