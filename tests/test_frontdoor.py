"""Front-door serving: admission, deadlines, coalescing, degradation.

The contract under test (DESIGN.md section 12): every ADMITTED request
is answered exactly once — even when faultinject kills a flush mid-
flight — every `partial=False` answer is bit-identical to the
synchronous `QueryEngine` result, rejected requests carry actionable
backpressure (retry-after), bulk is shed before interactive, and
deadline knife-edges (expired at admission, expiring mid-walk, zero
timeout) degrade to certified-partial answers instead of blocking or
lying.
"""

import threading
import time

import numpy as np
import pytest

from repro.core.cabin import CabinParams
from repro.index import QueryEngine
from repro.runtime import faultinject
from repro.serve import (CLASS_BULK, CLASS_INTERACTIVE, AdmissionQueue,
                         Deadline, FrontDoor, FrontDoorClosed,
                         RejectedError, ServiceEstimator)

N_DIMS = 400
P = CabinParams.create(N_DIMS, 256, seed=11)


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    return (rng.random((n, N_DIMS)) < 0.05).astype(np.int32)


@pytest.fixture(scope="module")
def engine():
    eng = QueryEngine(P, band_rows=64)
    eng.add_dense(_rows(2048, 1))
    eng.compact()
    return eng


class GatedEngine:
    """Engine proxy whose query path blocks on a gate — makes queue
    buildup deterministic for backpressure tests."""

    def __init__(self, eng, gate):
        self._eng = eng
        self.obs = eng.obs
        self.gate = gate

    def topk(self, queries, k):
        self.gate.wait()
        return self._eng.topk(queries, k)

    def topk_budgeted(self, queries, k, deadline=None):
        self.gate.wait()
        return self._eng.topk_budgeted(queries, k, deadline=deadline)

    def radius(self, queries, r):
        self.gate.wait()
        return self._eng.radius(queries, r)


class CountdownDeadline:
    """Scripted deadline: `expired` flips True after `checks` reads —
    lets a test place the expiry exactly between band-walk rounds
    without sleeping."""

    def __init__(self, checks, remaining_s=1e-4):
        self.checks = checks
        self._rem = remaining_s

    def remaining_s(self):
        return self._rem  # tiny: the front door routes us to the
        # budgeted sub-batch without treating us as already dead

    @property
    def expired(self):
        self.checks -= 1
        return self.checks < 0


# ---------------------------------------------------------------------------
# deadline / estimator units
# ---------------------------------------------------------------------------


def test_deadline_clock_injection():
    t = [100.0]
    d = Deadline(timeout_ms=50.0, clock=lambda: t[0])
    assert not d.expired
    assert d.remaining_ms() == pytest.approx(50.0)
    t[0] = 100.049
    assert not d.expired
    t[0] = 100.051
    assert d.expired
    assert d.remaining_ms() < 0
    with pytest.raises(ValueError):
        Deadline()
    with pytest.raises(ValueError):
        Deadline(timeout_ms=1.0, at=1.0)
    assert Deadline(at=99.0, clock=lambda: t[0]).expired


def test_service_estimator_ewma_and_prior():
    est = ServiceEstimator(default_ms=20.0, alpha=0.5)
    assert est.estimate_ms("topk") == 20.0  # prior before any observation
    est.observe("topk", 10.0)
    assert est.estimate_ms("topk") == 10.0  # first observation replaces
    est.observe("topk", 20.0)
    assert est.estimate_ms("topk") == pytest.approx(15.0)
    assert est.estimate_ms("radius") == 20.0  # per-op isolation
    est.observe("topk", -5.0)  # garbage observation is ignored
    assert est.estimate_ms("topk") == pytest.approx(15.0)


# ---------------------------------------------------------------------------
# admission queue: bounds, shed ordering, retry-after
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, cls, rows=1, key=("topk", 10, "dense")):
        self.cls = cls
        self.rows = rows
        self.key = key


def test_admission_sheds_bulk_before_interactive():
    q = AdmissionQueue(interactive_limit=4, bulk_limit=4, bulk_headroom=0.5)
    q.offer(_FakeReq(CLASS_BULK))  # admitted while interactive is empty
    q.offer(_FakeReq(CLASS_INTERACTIVE))
    q.offer(_FakeReq(CLASS_INTERACTIVE))  # interactive depth 2 == shed bar
    with pytest.raises(RejectedError) as ei:
        q.offer(_FakeReq(CLASS_BULK))
    assert ei.value.reason == "shed"
    assert ei.value.cls == CLASS_BULK
    # interactive still has room — it is NOT shed
    q.offer(_FakeReq(CLASS_INTERACTIVE))
    q.offer(_FakeReq(CLASS_INTERACTIVE))
    with pytest.raises(RejectedError) as ei:
        q.offer(_FakeReq(CLASS_INTERACTIVE))
    assert ei.value.reason == "full"
    assert q.depth(CLASS_INTERACTIVE) == 4
    assert q.depth(CLASS_BULK) == 1


def test_admission_bulk_full_and_retry_after_from_drain_rate():
    q = AdmissionQueue(interactive_limit=64, bulk_limit=2, bulk_headroom=1.0)
    q.offer(_FakeReq(CLASS_BULK))
    q.offer(_FakeReq(CLASS_BULK))
    with pytest.raises(RejectedError) as ei:
        q.offer(_FakeReq(CLASS_BULK))
    assert ei.value.reason == "full"
    assert ei.value.retry_after_s > 0  # default hint before any drain
    q.note_drained(10)  # 10 answered recently -> rate = 2/s over 5s window
    assert q.drain_rate() == pytest.approx(2.0)
    with pytest.raises(RejectedError) as ei:
        q.offer(_FakeReq(CLASS_BULK))
    # depth 2, rate 2/s -> (2+1)/2 = 1.5s
    assert ei.value.retry_after_s == pytest.approx(1.5)


def test_admission_take_group_prefers_interactive_and_coalesces():
    q = AdmissionQueue(interactive_limit=8, bulk_limit=8, bulk_headroom=1.0)
    other = ("topk", 5, "dense")
    q.offer(_FakeReq(CLASS_BULK, rows=2))
    q.offer(_FakeReq(CLASS_INTERACTIVE, rows=1))
    q.offer(_FakeReq(CLASS_INTERACTIVE, rows=1, key=other))
    q.offer(_FakeReq(CLASS_BULK, rows=3))
    group = q.take_group(max_rows=64)
    # leader is the first INTERACTIVE request even though bulk arrived
    # first; both same-key bulk requests coalesce behind it
    assert [g.cls for g in group] == [CLASS_INTERACTIVE, CLASS_BULK,
                                      CLASS_BULK]
    assert q.depth() == 1  # the other-key request stays queued
    group2 = q.take_group(max_rows=64)
    assert group2[0].key == other


# ---------------------------------------------------------------------------
# front door: exactness, concurrency, deadline knife-edges
# ---------------------------------------------------------------------------


def test_concurrent_no_deadline_answers_bit_identical(engine):
    batches = [_rows(3, 100 + i) for i in range(12)]
    want = [engine.topk(b, 10) for b in batches]
    results: list = [None] * len(batches)
    with FrontDoor(engine, max_wait_ms=1.0) as fd:
        def worker(i):
            results[i] = fd.topk(batches[i], 10)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(batches))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert fd.double_answers == 0
        assert fd.answered == len(batches)
    for res, (ids, dists) in zip(results, want):
        assert res.ok and not res.partial and res.cert_gap == 0.0
        np.testing.assert_array_equal(res.ids, ids)
        np.testing.assert_array_equal(res.dists, dists)


def test_assign_coalesces_with_top1(engine):
    q = _rows(4, 7)
    ids1, d1 = engine.topk(q, 1)
    with FrontDoor(engine) as fd:
        res = fd.assign(q)
    assert res.ids.shape == (4,)
    np.testing.assert_array_equal(res.ids, ids1[:, 0])
    np.testing.assert_array_equal(res.dists, d1[:, 0])


def test_radius_through_front_door(engine):
    q = _rows(3, 8)
    r = float(np.median(engine.topk(q, 5)[1])) + 0.5
    want = engine.radius(q, r)
    with FrontDoor(engine) as fd:
        res = fd.radius(q, r)
    assert res.ok and not res.partial
    assert len(res.hits) == 3
    for got, exp in zip(res.hits, want):
        np.testing.assert_array_equal(got, exp)


def test_zero_timeout_contract_never_enqueued(engine):
    with FrontDoor(engine) as fd:
        h = fd.submit("topk", _rows(2, 9), k=5, timeout_ms=0)
        res = h.result(timeout=5)
        assert res.partial and res.timed_out and res.ok
        assert res.ids.shape == (2, 0) and res.cert_gap == np.inf
        assert fd.queue.depth() == 0  # it never touched the queue
        # radius + assign honour the same contract with their own shapes
        ra = fd.submit("assign", _rows(2, 9), timeout_ms=0).result(timeout=5)
        assert ra.timed_out and (ra.ids == -1).all()
        rr = fd.submit("radius", _rows(2, 9), r=1.0,
                       timeout_ms=0).result(timeout=5)
        assert rr.timed_out and [len(h) for h in rr.hits] == [0, 0]


def test_deadline_expiring_mid_flush_returns_certified_partial(engine):
    q = _rows(2, 10)
    with FrontDoor(engine, max_wait_ms=0.0) as fd:
        # 1 pre-walk check (admission); expiry then lands between band
        # rounds inside topk_rows_banded — the mid-flush knife edge.
        # NOTE: the exact reference is computed AFTER this call — a
        # budgeted query that finds the exact answer already in the LRU
        # is upgraded to it (partial results never enter the cache)
        h = fd.submit("topk", q, k=10, deadline=CountdownDeadline(checks=1))
        res = h.result(timeout=30)
    ids_x, d_x = engine.topk(q, 10)
    assert res.ok
    assert res.partial
    assert res.cert_gap > 0
    # degraded, not wrong: every returned candidate is a true stored row
    # at its true distance, so distances can only be >= the exact answer
    assert res.ids.shape == (2, 10)
    filled = res.ids >= 0
    assert np.all(res.dists[filled] >= d_x[filled] - 1e-6)
    assert np.all(np.isinf(res.dists[~filled]))


def test_partial_false_property_under_mixed_deadlines(engine):
    """Property test: whatever the deadline mix and thread interleaving,
    partial=False answers are bit-identical to the synchronous engine."""
    pool = [_rows(2, 200 + i) for i in range(10)]
    want = [engine.topk(b, 8) for b in pool]
    rng = np.random.default_rng(0)
    jobs = [(int(rng.integers(len(pool))),
             [None, 0.0, 0.05, 50.0, None][int(rng.integers(5))])
            for _ in range(40)]
    out: list = [None] * len(jobs)
    with FrontDoor(engine, max_wait_ms=1.0,
                   interactive_limit=len(jobs)) as fd:
        def worker(j):
            qi, tmo = jobs[j]
            out[j] = fd.topk(pool[qi], 8, timeout_ms=tmo)

        threads = [threading.Thread(target=worker, args=(j,))
                   for j in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert fd.double_answers == 0
        assert fd.answered == len(jobs)
    for j, res in enumerate(out):
        qi = jobs[j][0]
        assert res.ok
        if not res.partial:
            assert res.cert_gap == 0.0
            np.testing.assert_array_equal(res.ids, want[qi][0])
            np.testing.assert_array_equal(res.dists, want[qi][1])
        else:
            assert res.cert_gap > 0


# ---------------------------------------------------------------------------
# backpressure and shutdown through the full stack
# ---------------------------------------------------------------------------


def test_backpressure_sheds_bulk_first_through_front_door(engine):
    gate = threading.Event()
    fd = FrontDoor(GatedEngine(engine, gate), interactive_limit=4,
                   bulk_limit=4, bulk_headroom=0.5, max_wait_ms=0.0)
    try:
        handles = [fd.submit("topk", _rows(1, 20), k=5)]
        deadline = time.monotonic() + 5
        while fd.queue.depth() > 0:  # dispatcher holds it at the gate
            assert time.monotonic() < deadline, "dispatcher never picked up"
            time.sleep(0.001)
        handles += [fd.submit("topk", _rows(1, 21 + i), k=5)
                    for i in range(4)]  # exactly fills the bounded queue
        assert fd.queue.depth(CLASS_INTERACTIVE) == 4
        with pytest.raises(RejectedError) as ei:
            fd.submit("topk", _rows(1, 30), k=5, cls=CLASS_BULK)
        assert ei.value.reason == "shed"  # bulk dies before interactive
        with pytest.raises(RejectedError) as ei:
            fd.submit("topk", _rows(1, 31), k=5)
        assert ei.value.reason == "full"
        assert ei.value.retry_after_s > 0
        gate.set()
        for h in handles:
            assert h.result(timeout=30).ok
    finally:
        gate.set()
        fd.close()


def test_close_drains_admitted_requests(engine):
    gate = threading.Event()
    fd = FrontDoor(GatedEngine(engine, gate), max_wait_ms=0.0)
    handles = [fd.submit("topk", _rows(1, 40 + i), k=3) for i in range(6)]
    closer = threading.Thread(target=fd.close)
    closer.start()
    time.sleep(0.02)
    gate.set()  # release the engine AFTER close began: drain must finish
    closer.join(timeout=30)
    assert not closer.is_alive()
    for h in handles:
        assert h.result(timeout=5).ok  # drained, not dropped
    with pytest.raises((FrontDoorClosed, RejectedError)):
        fd.submit("topk", _rows(1, 50), k=3)


# ---------------------------------------------------------------------------
# chaos: crash points at enqueue / flush / publish
# ---------------------------------------------------------------------------


def test_crash_at_enqueue_is_not_an_ack(engine):
    with FrontDoor(engine) as fd:
        with faultinject.armed("frontdoor.enqueue"):
            with pytest.raises(faultinject.InjectedCrash):
                fd.submit("topk", _rows(1, 60), k=5)
        assert fd.queue.depth() == 0  # never admitted -> nothing owed
        res = fd.topk(_rows(1, 61), 5)  # the door still serves
        assert res.ok and not res.partial


@pytest.mark.parametrize("point", ["frontdoor.flush", "frontdoor.publish"])
def test_crash_mid_flush_retries_exactly_once_answered(engine, point):
    q = _rows(2, 70)
    want = engine.topk(q, 6)
    with FrontDoor(engine, max_wait_ms=0.0, backoff_ms=0.1) as fd:
        faultinject.record_hits()
        faultinject.clear_hits()
        with faultinject.armed(point):
            res = fd.topk(q, 6)
        faultinject.record_hits(False)
        assert point in faultinject.hits()  # the crash actually fired
        assert res.ok and not res.partial
        np.testing.assert_array_equal(res.ids, want[0])
        np.testing.assert_array_equal(res.dists, want[1])
        assert fd.double_answers == 0
        assert fd.answered == 1
    snap = engine.obs_snapshot()
    if snap:  # REPRO_OBS=1: the fault and retry were recorded
        assert snap["frontdoor_faults_total"] >= 1
        assert snap["frontdoor_retries_total"] >= 1


def test_retries_exhausted_surface_as_error_result(engine):
    class BrokenEngine:
        obs = engine.obs

        def topk(self, queries, k):
            raise RuntimeError("engine on fire")

    fd = FrontDoor(BrokenEngine(), max_retries=2, backoff_ms=0.1,
                   max_wait_ms=0.0)
    try:
        res = fd.topk(_rows(1, 80), 5)
        assert not res.ok
        assert isinstance(res.error, RuntimeError)
        assert fd.answered == 1  # an error result is still an answer
        assert fd.double_answers == 0
    finally:
        fd.close()


def test_flush_spans_share_the_flush_number(engine):
    """One flush's fill, flush and distribute spans carry one `flush`
    number, which the requests it served keep; its engine call nests in
    its `frontdoor.flush` span."""
    from repro import obs
    from _capture import capture

    if not obs.enabled():
        pytest.skip("obs disabled in this environment")
    qs = [_rows(2, 300 + i) for i in range(2)]
    for q in qs:
        engine.topk(q, 3)  # compile outside the capture
    with capture() as cap:
        with FrontDoor(engine, max_wait_ms=1.0) as fd:
            reqs = []
            for q in qs:  # one at a time: two flushes
                reqs.append(fd.submit("topk", q, k=3))
                assert reqs[-1].result(timeout=60).ok
    assert [r.flush for r in reqs] == [1, 2]
    for n in (1, 2):
        [fill, flush, dist] = [
            [e for e in cap.named(f"frontdoor.{what}")
             if e.args["flush"] == n]
            for what in ("fill", "flush", "distribute")]
        assert len(fill) == len(flush) == len(dist) == 1
        assert fill[0].end_ns <= flush[0].start_ns
        assert flush[0].end_ns <= dist[0].start_ns
        assert flush[0].args["op"] == "topk" and flush[0].args["rows"] == 2
        assert any(e.within(flush[0]) for e in cap.named("engine.topk"))
