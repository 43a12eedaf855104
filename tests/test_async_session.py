"""The continuous-admission drain engine (ISSUE 3 tentpole): out-of-order
completion bitwise-parity vs InlineBackend, page-pool hit/eviction
accounting, partial-ledger resume after fault injection, early-result
delivery ordering, and continuous admission mid-drain."""
import numpy as np
import pytest

from repro.compile import PagePool
from repro.core import DMLData, DMLPlan, DMLSession
from repro.core.session import compile_request
from repro.data import make_irm_data, make_plr_data
from repro.serverless import InlineBackend, PoolConfig, make_backend


def _plr(n_obs, seed, *, learner="ridge", learner_params=None, n_rep=2,
         n_folds=3):
    data = DMLData.from_dict(make_plr_data(n_obs=n_obs, dim_x=5, theta=0.5,
                                           seed=seed))
    if learner_params is None:
        learner_params = {"reg": 1.0}
    plan = DMLPlan.for_model(
        "plr", learner=learner, learner_params=learner_params,
        n_folds=n_folds, n_rep=n_rep, seed=seed + 100)
    return plan, data


FAMILIES = [
    ("ridge", {"reg": 1.0}),
    ("ols", {}),
    ("lasso", {"reg": 0.01}),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 32}),
    ("mlp", {"hidden": (8,), "n_steps": 20}),
]


# ---------------------------------------------------------------------------
# out-of-order completion: bitwise parity vs the synchronous inline path
# ---------------------------------------------------------------------------
def test_out_of_order_completion_bitwise_parity_all_families():
    """Tiny wave capacity forces many interleaved waves and out-of-order
    bucket completion across mixed learner families; every request's
    prediction tensor must be bitwise-identical to a synchronous
    InlineBackend drain of the same request."""
    cases = [_plr(100 + 7 * i, seed=i, learner=name, learner_params=params)
             for i, (name, params) in enumerate(FAMILIES)]
    # logistic rides along via the IRM propensity nuisance
    irm = (DMLPlan.for_model("irm", learner="ridge", n_folds=3, n_rep=2,
                             seed=77),
           DMLData.from_dict(make_irm_data(n_obs=130, dim_x=4, theta=0.4,
                                           seed=9)))
    cases.append(irm)

    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=2, memory_mb=256))
    rids = [sess.submit(plan, data) for plan, data in cases]
    sess.run()
    assert sess.last_run_info.waves >= 2           # really interleaved

    for rid, (plan, data) in zip(rids, cases):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(
            sess.request(rid).gathered_preds(), ref.gathered_preds())


def test_idle_session_keeps_telemetry_and_rejects_unknown_ids():
    """run()/wait()/poll() on an idle session neither clobber the last
    drain's telemetry nor invent a drain; unknown ids fail fast."""
    plan, data = _plr(100, seed=20)
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=2, memory_mb=256))
    rid = sess.submit(plan, data)
    sess.run()
    info = sess.last_run_info
    assert info.waves >= 1
    assert sess.run() == [] and sess.poll() == []
    assert sess.wait(rid).request_id == rid        # already-complete: ok
    assert sess.last_run_info is info              # telemetry preserved
    with pytest.raises(KeyError, match="unknown request id"):
        sess.wait(999)


def test_poll_interleaves_and_run_matches_batch():
    """Driving the engine wave-by-wave via poll() completes everything and
    matches a blocking run() bitwise."""
    plan_a, data_a = _plr(120, seed=1)
    plan_b, data_b = _plr(90, seed=2)
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=1, memory_mb=256))
    ra = sess.submit(plan_a, data_a)
    rb = sess.submit(plan_b, data_b)
    done = []
    for _ in range(100):
        done += sess.poll()
        if len(done) == 2:
            break
    assert sorted(done) == sorted([ra, rb])

    sess2 = DMLSession(backend="wave",
                       pool=PoolConfig(n_workers=1, memory_mb=256))
    sess2.submit(plan_a, data_a)
    sess2.submit(plan_b, data_b)
    res = sess2.run()
    np.testing.assert_array_equal(sess.result(ra).thetas, res[0].thetas)


def test_continuous_admission_mid_drain():
    """A request submitted while the drain is running joins the same
    drain (no barrier) and still returns its solo-run theta bitwise."""
    plan_a, data_a = _plr(150, seed=3, n_rep=4)
    plan_b, data_b = _plr(100, seed=4)
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=2, memory_mb=256))
    ra = sess.submit(plan_a, data_a)
    sess.poll()                                   # drain already moving
    rb = sess.submit(plan_b, data_b)              # late admission
    res_b = sess.wait(rb)
    info = sess.last_run_info
    assert len(info.wave_members) > 1
    assert any(rb in m and ra in m for m in info.wave_members)  # shared wave
    sess.wait(ra)

    ref = compile_request(plan_b, data_b)
    InlineBackend().run_requests([ref])
    np.testing.assert_array_equal(sess.request(rb).gathered_preds(),
                                  ref.gathered_preds())
    assert res_b.request_id == rb


# ---------------------------------------------------------------------------
# early-result delivery
# ---------------------------------------------------------------------------
def test_early_result_delivery_ordering():
    """A small request submitted after a large one completes first (its
    few invocations drain while the large grid is still executing), and
    its callback fires before the large request finishes."""
    big_plan, big_data = _plr(140, seed=5, n_rep=8)     # 16 invocations
    small_plan, small_data = _plr(80, seed=6, n_rep=1)  # 2 invocations
    order = []
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=2, memory_mb=256))
    rid_big = sess.submit(big_plan, big_data,
                          on_complete=lambda r: order.append(r.request_id))
    rid_small = sess.submit(small_plan, small_data,
                            on_complete=lambda r: order.append(r.request_id))
    res = sess.run()
    assert order == [rid_small, rid_big]          # early delivery
    assert sess.completion_order == [rid_small, rid_big]
    assert [r.request_id for r in res] == [rid_big, rid_small]  # submit order


# ---------------------------------------------------------------------------
# page pool
# ---------------------------------------------------------------------------
def test_page_pool_steady_state_zero_transfer():
    """Warm drains of the same datasets re-transfer nothing: page hit rate
    1.0 and zero host->device bytes after the warmup drain."""
    cases = [_plr(100 + i, seed=i) for i in range(3)]
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=8, memory_mb=1024))
    for plan, data in cases:
        sess.submit(plan, data)
    sess.run()                                    # warmup: cold transfers
    pool = sess.backend.pages
    assert pool.stats.misses >= 1
    warm0 = pool.stats.snapshot()
    for _ in range(3):                            # steady state
        for plan, data in cases:
            sess.submit(plan, data)
        sess.run()
    d = pool.stats.delta(warm0)
    assert d.bytes_h2d == 0
    assert d.misses == 0 and d.hits > 0
    assert d.hit_rate == 1.0
    assert d.stack_hits >= 1                      # same composition reused


def test_page_pool_shared_across_equal_data():
    """Two requests over equal-content datasets share one resident page
    (content fingerprint, not object identity)."""
    plan_a, data = _plr(100, seed=7)
    copy = DMLData(x=np.array(data.x), y=np.array(data.y),
                   d=np.array(data.d))
    sess = DMLSession(backend="inline")
    sess.submit(plan_a, data)
    sess.submit(_plr(100, seed=8)[0], copy)
    sess.run()
    assert sess.backend.pages.n_pages == 1


def test_page_pool_eviction_accounting(monkeypatch):
    """A byte budget below the traffic's dataset set forces LRU evictions
    and re-transfers, all visible in the stats (pages needed by the
    in-flight launch are never evicted).

    Runs chaos-free even under REPRO_CHAOS: injected retries re-touch
    resident pages and legitimately add hits, which would smear the
    exact transfer counts this test pins.  Estimate bitwise parity
    under chaos is tests/test_chaos.py's job; this one is about LRU
    byte accounting."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    page_bytes = 104 * 8 * 4                       # N_pad=104, P_pad=8
    pool = PagePool(byte_budget=page_bytes)        # fits exactly one page
    backend = make_backend("inline")
    backend.pages = pool
    cases = [_plr(100 + i, seed=10 + i) for i in range(3)]
    for _ in range(2):
        for p, d in cases:                         # one dataset per drain
            backend.run_requests([compile_request(p, d)])
    assert pool.stats.evictions >= 3               # LRU churn under budget
    # floor = the in-flight working set (one page + its cached stack),
    # which is never evicted even when it exceeds the budget
    assert pool.total_bytes <= 2 * page_bytes
    # every re-visit of an evicted dataset re-transferred: 2 rounds x 3
    assert pool.stats.misses == 6 and pool.stats.hits == 0
    assert pool.stats.bytes_h2d == pool.stats.misses * page_bytes

    pool.byte_budget = 10 * page_bytes             # now everything fits
    for p, d in cases:
        backend.run_requests([compile_request(p, d)])
    for p, d in cases:
        backend.run_requests([compile_request(p, d)])
    # one refill round (the tight phase's survivor is still resident),
    # then residency pays
    assert pool.stats.misses == 8
    assert pool.stats.hits == 4


def test_page_pool_disabled_by_budget_zero():
    sess = DMLSession(backend="inline",
                      pool=PoolConfig(page_pool_bytes=0))
    plan, data = _plr(100, seed=12)
    res = sess.estimate(plan, data)
    assert sess.backend.pages is None
    assert np.isfinite(res.theta)


# ---------------------------------------------------------------------------
# non-blocking dispatch (ISSUE 5)
# ---------------------------------------------------------------------------
def test_inflight_entries_excluded_from_pending_and_harvested_later(
        monkeypatch):
    """A dispatched bucket's invocations leave the scheduler's pending
    view immediately (no double dispatch) but only reach the ledger at
    harvest — a later step books them while new work dispatches.

    Chaos-free even under REPRO_CHAOS: injected failures retry and
    inflate the exact dispatched/harvested counts pinned below."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    backend = make_backend("inline")
    state = backend.begin_drain()
    for n, seed in ((100, 30), (300, 31)):        # two distinct buckets
        backend.admit(state, compile_request(*_plr(n, seed=seed)))

    assert backend.step(state)                    # dispatch bucket 1
    inflight = state.in_flight_entries()
    assert inflight                               # really in flight
    done_before = sum(r.ledger.n_done for r in state.requests)
    groups = state.plan.pending_by_bucket(exclude=inflight)
    for entries in groups.values():               # no re-dispatch overlap
        assert not (set(entries) & inflight)

    while backend.step(state):                    # dispatch 2, harvest both
        pass
    assert all(q.empty for q in state.queues.values())
    assert all(r.ledger.complete for r in state.requests)
    assert sum(r.ledger.n_done for r in state.requests) > done_before
    d = state.info.dispatch
    assert d.dispatched == d.harvested == 2
    assert d.host_overlap_s > 0.0                 # booking overlapped


def test_dispatch_queue_same_key_inflight_buckets():
    """Two in-flight buckets sharing one BucketKey (truncated topology
    waves / mid-drain admission produce these) must harvest cleanly out
    of order — regression test for the generated-dataclass __eq__ crash
    (list.remove comparing in-flight jax arrays elementwise)."""
    import jax
    from repro.compile import ProgramCache, dispatch_bucket, plan_buckets
    from repro.serverless import DispatchQueue, PendingBucket

    req = compile_request(*_plr(100, seed=50))
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    cache = ProgramCache()
    ents = [(0, int(i)) for i in req.ledger.pending()]
    bd1 = dispatch_bucket(bplan, cache, bkey, ents[:2])
    bd2 = dispatch_bucket(bplan, cache, bkey, ents[2:])
    q = DispatchQueue(8)
    booked = []
    book = lambda pb, res, el: booked.append(sorted(res))
    q.push(PendingBucket(dispatch=bd1), book)
    q.push(PendingBucket(dispatch=bd2), book)
    jax.block_until_ready([l.out for l in bd2.launches])
    q.harvest_ready(book)               # may book bd2 before bd1
    q.harvest_all(book)
    assert q.empty
    assert sorted(e for b in booked for e in b) == sorted(ents)


def test_dispatch_queue_inflight_cap_forces_harvest():
    """max_inflight bounds device-side liveness: pushing beyond the cap
    force-harvests the oldest bucket instead of growing the queue."""
    from repro.serverless import PoolConfig as PC
    backend = make_backend("inline", PC(max_inflight=1))
    state = backend.begin_drain()
    for i, n in enumerate((100, 300, 600)):       # three buckets
        backend.admit(state, compile_request(*_plr(n, seed=40 + i)))
    while backend.step(state):
        assert len(state.queues[-1]) <= 1
    assert all(r.ledger.complete for r in state.requests)


# ---------------------------------------------------------------------------
# partial-ledger resume after fault injection
# ---------------------------------------------------------------------------
def test_partial_ledger_resume_after_fault_abort():
    """Retry-budget exhaustion mid-drain leaves partially-complete
    ledgers; swapping in a healthy pool resumes exactly the missing
    invocations and the result matches the clean path bitwise."""
    plan, data = _plr(110, seed=13, n_rep=4)
    # seed chosen so the first wave under the identity-keyed fault plan
    # (serverless/chaos.py) mixes a success with the budget-exhausting
    # failure — the ledger is left genuinely partial
    doomed = PoolConfig(n_workers=2, memory_mb=256, failure_rate=0.5,
                        max_retries=0, seed=3)
    sess = DMLSession(backend="wave", pool=doomed)
    rid = sess.submit(plan, data)
    with pytest.raises(RuntimeError, match="retry budget"):
        sess.run()
    ledger = sess._queue[0].ledger
    n_done = ledger.n_done
    assert 0 < n_done < ledger.n_invocations       # genuinely partial
    assert not ledger.complete

    sess.backend = make_backend("wave", PoolConfig(n_workers=2,
                                                   memory_mb=256))
    res, = sess.run()
    assert res.request_id == rid
    resumed = sess.request(rid)
    assert resumed.ledger.complete
    # only the missing invocations were re-executed after the swap
    assert resumed.report.bill.n_invocations < 2 * resumed.ledger.n_invocations
    ref = compile_request(plan, data)
    InlineBackend().run_requests([ref])
    np.testing.assert_array_equal(resumed.gathered_preds(),
                                  ref.gathered_preds())
