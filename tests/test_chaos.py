"""Fault-tolerant fast path (ISSUE 10): identity-keyed fault injection,
capped-backoff retries, deadline-based hedged re-dispatch, host-loss
recovery, and crash-resumable sessions.

The load-bearing property throughout: chaos changes the SCHEDULE, never
the estimate.  Fault verdicts are drawn per (request slot, invocation,
attempt) from counter-based Philox streams (serverless/chaos.py), so
results under ANY fault schedule — any worker count, any harvest order,
any hedge race outcome, any host loss — are bitwise-identical to the
fault-free drain.
"""
import time

import numpy as np
import pytest

from repro.core import DMLData, DMLPlan, DMLSession
from repro.core.session import assemble_result, compile_request
from repro.data import make_irm_data, make_plr_data
from repro.serverless import PoolConfig, make_backend
from repro.serverless.backends import InlineBackend, WaveBackend
from repro.serverless.chaos import ChaosPlan, chaos_plan, env_chaos_rates

FAMILIES = [
    ("ridge", {"reg": 1.0}),
    ("ols", {}),
    ("lasso", {"reg": 0.01}),
    ("logistic", {"reg": 1.0}),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 32}),
    ("mlp", {"hidden": (8,), "n_steps": 20}),
]

# chaos everywhere, short synthetic tails so the suite stays fast:
# every straggler holds its bucket 40ms, hedging arms after 5ms
CHAOS = dict(failure_rate=0.3, straggler_rate=0.3, max_retries=10,
             straggler_hold_s=0.04, hedge_after_s=0.005, seed=0)


def _case(learner, params, seed=3):
    if learner == "logistic":
        data = DMLData.from_dict(make_irm_data(n_obs=130, dim_x=4,
                                               theta=0.4, seed=seed))
        plan = DMLPlan.for_model("irm", learner="ridge", n_folds=3,
                                 n_rep=2, seed=seed + 100)
        return plan, data
    data = DMLData.from_dict(make_plr_data(n_obs=120, dim_x=5, theta=0.5,
                                           seed=seed))
    plan = DMLPlan.for_model("plr", learner=learner, learner_params=params,
                             n_folds=3, n_rep=2, seed=seed + 100)
    return plan, data


def _run(backend, plan, data):
    req = compile_request(plan, data)
    info = backend.run_requests([req])
    assert req.ledger.complete
    return req, info


# ---------------------------------------------------------------------------
# the fault plan itself
# ---------------------------------------------------------------------------
def test_verdicts_are_order_independent():
    """Counter-based Philox keying: the verdict of (slot, inv, attempt)
    is a pure function of the identity — querying in any order, or
    skipping queries entirely, never changes a draw.  This is what lets
    chaos pools keep bucket-coherent fill and pipelined dispatch."""
    a = ChaosPlan(failure_rate=0.4, straggler_rate=0.3,
                  straggler_slowdown=4.0, simulate=True, seed=9)
    b = ChaosPlan(failure_rate=0.4, straggler_rate=0.3,
                  straggler_slowdown=4.0, simulate=True, seed=9)
    idents = [(s, i, t) for s in range(3) for i in range(4)
              for t in range(2)]
    fwd = {ident: a.verdict(*ident) for ident in idents}
    rev = {ident: b.verdict(*ident) for ident in reversed(idents)}
    assert fwd == rev
    # a fresh plan queried once agrees with a heavily-queried one
    c = ChaosPlan(failure_rate=0.4, straggler_rate=0.3,
                  straggler_slowdown=4.0, simulate=True, seed=9)
    assert c.verdict(2, 3, 1) == fwd[(2, 3, 1)]
    # failures fire on attempt 0 only: retries converge
    assert not any(v.failed for (s, i, t), v in fwd.items() if t > 0)


def test_backoff_is_capped_exponential():
    p = ChaosPlan(failure_rate=0.5, straggler_rate=0.0,
                  straggler_slowdown=4.0, simulate=False, seed=0,
                  backoff_base_s=0.01, backoff_cap_s=0.05)
    assert p.backoff_s(1) == pytest.approx(0.01)
    assert p.backoff_s(2) == pytest.approx(0.02)
    assert p.backoff_s(3) == pytest.approx(0.04)
    assert p.backoff_s(10) == pytest.approx(0.05)      # capped
    none = ChaosPlan(failure_rate=0.5, straggler_rate=0.0,
                     straggler_slowdown=4.0, simulate=False, seed=0)
    assert none.backoff_s(5) == 0.0                    # opt-in knob


def test_env_chaos_arms_fault_free_pools(monkeypatch):
    """REPRO_CHAOS is the CI chaos job's lever: it arms fault injection
    on pools that configured none, without touching explicit rates."""
    monkeypatch.delenv("REPRO_CHAOS", raising=False)
    assert env_chaos_rates() is None
    assert chaos_plan(PoolConfig()) is None            # fault-free stays so
    monkeypatch.setenv("REPRO_CHAOS", "1")
    assert env_chaos_rates() == (0.1, 0.1)
    armed = chaos_plan(PoolConfig())
    assert armed is not None and armed.failure_rate == 0.1
    monkeypatch.setenv("REPRO_CHAOS", "fail=0.25,strag=0.05")
    assert env_chaos_rates() == (0.25, 0.05)
    # an explicitly chaotic pool keeps its own configured rates
    own = chaos_plan(PoolConfig(failure_rate=0.4))
    assert own.failure_rate == 0.4


# ---------------------------------------------------------------------------
# the tentpole gate: bitwise parity under chaos, all learner families
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("learner,params", FAMILIES,
                         ids=[f[0] for f in FAMILIES])
def test_bitwise_parity_under_chaos(learner, params):
    """Faults + stragglers + backoff retries + hedge races on the wave
    backend: bitwise-identical predictions and theta vs the fault-free
    inline drain, for every learner family including the key-consuming
    ones (mlp, kernel_ridge)."""
    plan, data = _case(learner, params)
    ref, _ = _run(InlineBackend(PoolConfig(n_workers=3)), plan, data)
    chaotic = PoolConfig(n_workers=2, memory_mb=512, **CHAOS)
    req, info = _run(WaveBackend(chaotic), plan, data)
    assert req.report.failures > 0 or req.report.stragglers > 0
    np.testing.assert_array_equal(req.gathered_preds(),
                                  ref.gathered_preds())
    r = assemble_result(plan, data, req)
    r_ref = assemble_result(plan, data, ref)
    assert r.theta == r_ref.theta


def test_chaos_pools_stay_on_the_fast_path():
    """The deleted special case: chaos pools used to fall back to a
    wave-synchronous slow path.  Now they run the same fused, pipelined,
    bucket-coherent dispatch as fault-free pools — asserted via the
    drain's launch fusion and in-flight dispatch accounting."""
    cases = [_case("ridge", {"reg": 1.0}, seed=s) for s in (3, 4, 5)]
    # capacity spans requests so bucket-coherent fill really packs
    # cross-request blocks into fused launches
    backend = WaveBackend(PoolConfig(n_workers=4, memory_mb=512, **CHAOS))
    reqs = [compile_request(p, d) for p, d in cases]
    info = backend.run_requests(reqs)
    assert all(r.ledger.complete for r in reqs)
    assert sum(r.report.failures for r in reqs) > 0    # chaos really hit
    d = info.dispatch
    assert d is not None and d.dispatched > 0
    # every dispatched bucket is retired exactly once, by exactly one of
    # the three legal exits — booked, cancelled (hedge loser), or lost
    assert d.dispatched == d.harvested + d.cancelled + d.lost
    assert d.lost == 0
    # fused launches and multi-bucket in-flight pipelining under chaos
    assert info.compile.fused_launches >= 1
    assert d.in_flight_peak >= 2
    assert 0.0 <= d.overlap_ratio <= 1.0


def test_fault_pattern_is_schedule_independent():
    """The same chaotic pool at different worker counts — different wave
    shapes, different dispatch order — injects the SAME fault set and
    produces bitwise-identical predictions."""
    plan, data = _case("ridge", {"reg": 1.0})
    runs = []
    for n_workers in (1, 8):
        pool = PoolConfig(n_workers=n_workers, memory_mb=512,
                          failure_rate=0.3, max_retries=10, seed=2)
        req, _ = _run(WaveBackend(pool), plan, data)
        runs.append(req)
    assert runs[0].report.failures == runs[1].report.failures > 0
    np.testing.assert_array_equal(runs[0].gathered_preds(),
                                  runs[1].gathered_preds())


def test_backoff_gates_delay_but_complete():
    """Capped-backoff retries: failed invocations wait out their gate,
    re-enter the pending view, and the drain still completes bitwise."""
    plan, data = _case("ridge", {"reg": 1.0})
    ref, _ = _run(InlineBackend(PoolConfig(n_workers=3)), plan, data)
    pool = PoolConfig(n_workers=2, memory_mb=512, failure_rate=0.4,
                      max_retries=10, seed=2, retry_backoff_s=0.005,
                      retry_backoff_cap_s=0.02)
    req, _ = _run(WaveBackend(pool), plan, data)
    assert req.report.failures > 0
    np.testing.assert_array_equal(req.gathered_preds(),
                                  ref.gathered_preds())


@pytest.mark.parametrize("backend", ["inline", "sharded", "wave",
                                     "topology"])
def test_every_backend_books_the_lambda_contract(backend, monkeypatch):
    """One booking path for every backend.  Under a chaos pool each
    invocation is billed exactly once, at its simulated duration times
    ``straggler_slowdown`` when its successful attempt straggled; a
    failed row is re-dispatched only once its backoff gate matured; and
    a retry budget that runs out aborts the drain only after every
    success of that harvest is booked."""
    from dataclasses import replace

    from repro.serverless.backends import DrainState, _StreamBackend
    from repro.serverless.cost import speedup_of
    from repro.serverless.ledger import DONE, FAILED
    cases = [_case("ridge", {"reg": 1.0}, seed=s) for s in (3, 4)]
    pool = PoolConfig(n_workers=2, memory_mb=512, failure_rate=0.3,
                      straggler_rate=0.4, straggler_slowdown=4.0,
                      simulate=True, base_work_s=0.01, max_retries=10,
                      retry_backoff_s=0.003, seed=4)
    gates, early, settles = {}, [], []
    open_gates, mark, settle = (_StreamBackend._open_gates,
                                DrainState.mark_running,
                                _StreamBackend._settle)

    def open_gates_(self, state):
        gates.update(state.retry_at)
        return open_gates(self, state)

    def mark_(self, entries):
        now = time.perf_counter()
        early.extend(e for e in entries
                     if self.requests[e[0]].ledger.status[e[1]] == FAILED
                     and not now >= gates.get(e, float("inf")))
        return mark(self, entries)

    def settle_(self, state, host, by_req, *args, **kwargs):
        settles.append({ri: list(invs) for ri, invs in by_req.items()})
        return settle(self, state, host, by_req, *args, **kwargs)

    monkeypatch.setattr(_StreamBackend, "_open_gates", open_gates_)
    monkeypatch.setattr(DrainState, "mark_running", mark_)
    monkeypatch.setattr(_StreamBackend, "_settle", settle_)

    reqs = [compile_request(p, d) for p, d in cases]
    make_backend(backend, pool).run_requests(reqs)
    plan = chaos_plan(pool)
    stragglers = 0
    for ri, req in enumerate(reqs):
        assert req.ledger.complete
        recs = req.report.bill.records
        assert sorted(r.invocation for r in recs) == \
            list(range(req.ledger.n_invocations))        # billed once
        base = pool.base_work_s \
            * req.grid.tasks_per_invocation(req.scaling) \
            / speedup_of(pool.memory_mb)
        for r in recs:
            v = plan.verdict(ri, r.invocation, r.retry)
            assert not v.failed
            want = base * v.noise * (pool.straggler_slowdown
                                     if v.straggler else 1.0)
            assert r.duration_s == pytest.approx(want, rel=1e-12)
            stragglers += v.straggler
    assert stragglers > 0
    assert sum(r.report.failures for r in reqs) > 0
    assert gates and not early              # retried only past the gate

    # a spent budget: every other success of the harvest that raised —
    # the failed row's siblings and the other requests' rows — is booked
    reqs = [compile_request(p, d) for p, d in cases]
    with pytest.raises(RuntimeError, match="retry budget"):
        make_backend(backend, replace(pool, max_retries=0)).run_requests(
            reqs)
    verdicts = {(ri, inv): plan.verdict(ri, inv, 0).failed
                for ri, invs in settles[-1].items() for inv in invs}
    assert any(verdicts.values()) and not all(verdicts.values())
    for (ri, inv), failed in verdicts.items():
        assert (reqs[ri].ledger.status[inv] == DONE) != failed


# ---------------------------------------------------------------------------
# hedged re-dispatch: first landing wins, loser never double-bills
# ---------------------------------------------------------------------------
def test_hedge_race_books_once_and_attributes_waste():
    """Every invocation a straggler: each bucket's dispatch holds 40ms,
    overdue after 5ms, so a hedge duplicate launches and wins.  Exactly
    one booking per bucket (the ledger would throw on a double-book
    under the sanitizer; here we assert the bill), losers land in
    hedge_waste_s, and the result is still bitwise."""
    plan, data = _case("ridge", {"reg": 1.0})
    ref, _ = _run(InlineBackend(PoolConfig(n_workers=3)), plan, data)
    pool = PoolConfig(n_workers=2, memory_mb=512, straggler_rate=1.0,
                      straggler_hold_s=0.04, hedge_after_s=0.005,
                      max_retries=10, seed=5)
    req, info = _run(WaveBackend(pool), plan, data)
    d = info.dispatch
    assert d.hedges > 0
    assert d.hedge_wins > 0                  # the duplicate really raced
    assert d.cancelled > 0                   # and the loser was discarded
    assert d.dispatched == d.harvested + d.cancelled + d.lost
    assert d.hedge_waste_s >= 0.0
    # single-performer booking: every invocation billed exactly once
    assert req.report.bill.n_invocations == req.ledger.n_invocations
    np.testing.assert_array_equal(req.gathered_preds(),
                                  ref.gathered_preds())


def test_hedge_deadline_prices_from_roofline():
    """Without an explicit hedge_after_s the deadline comes from the
    bucket's roofline estimate — bounded below by the floor and above
    by the Lambda timeout."""
    from repro.launch.roofline import (
        HEDGE_DEADLINE_FLOOR_S, bucket_deadline_s,
    )
    d1 = bucket_deadline_s("ridge", {"reg": 1.0}, 4, 128, 8, 4,
                           n_workers=4)
    # tiny buckets clamp to the floor: never hedge sub-millisecond work
    assert d1 == HEDGE_DEADLINE_FLOOR_S
    # a bucket big enough to clear the floor prices from its roofline,
    # and more entries on the same lanes -> proportionally later deadline
    d2 = bucket_deadline_s("ridge", {"reg": 1.0}, 4, 1 << 18, 64, 512,
                           n_workers=4)
    d3 = bucket_deadline_s("ridge", {"reg": 1.0}, 4, 1 << 18, 64, 1024,
                           n_workers=4)
    assert d3 > d2 > d1


# ---------------------------------------------------------------------------
# host loss: kill a mesh mid-flight, the survivors finish everything
# ---------------------------------------------------------------------------
def test_topology_survives_host_loss_mid_flight():
    """Kill host 0 while its queue holds in-flight buckets: its pages
    are invalidated, its orphans re-route, and every admitted request
    still completes — bitwise-identical to the fault-free inline path,
    for every learner family."""
    cases = [_case(learner, params, seed=3 + i)
             for i, (learner, params) in enumerate(FAMILIES)]
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_workers=2, memory_mb=256,
                                      n_hosts=2))
    rids = [sess.submit(plan, data) for plan, data in cases]
    backend = sess.backend
    # drive the drain until host 0 has work in flight, then kill it
    killed = False
    for _ in range(400):
        sess.poll()
        state = sess._state
        if state is None:
            break
        q = state.queues.get(0)
        if q is not None and q.in_flight > 0:
            lost = backend.kill_host(state, 0)
            assert lost > 0              # genuinely orphaned in-flight work
            killed = True
            break
    assert killed, "drain finished before any in-flight work on host 0"
    sess.run()
    t = sess.topology_info
    assert t.host_losses == 1
    assert t.lost_buckets > 0
    assert t.lost_buckets == sess.last_run_info.dispatch.lost
    # zero lost invocations: every admitted request completed
    for rid, (plan, data) in zip(rids, cases):
        assert sess.request(rid).ledger.complete
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(
            sess.request(rid).gathered_preds(), ref.gathered_preds())
    # the dead host's pool is empty and unreachable via the directory
    assert backend.topology.hosts[0].pool.n_pages == 0
    assert 0 not in backend.topology.directory._pools


def test_killed_host_never_rejoins():
    """Host death is permanent for the topology's lifetime: later drains
    route and steal over the survivors only."""
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_workers=2, memory_mb=256,
                                      n_hosts=2))
    plan, data = _case("ridge", {"reg": 1.0})
    sess.submit(plan, data)
    sess.run()
    sess.backend.topology.kill(0)
    plan2, data2 = _case("ridge", {"reg": 1.0}, seed=9)
    rid = sess.submit(plan2, data2)
    sess.run()
    assert sess.request(rid).ledger.complete
    t = sess.topology_info
    assert t.hosts[0].waves == 0         # the corpse never stepped
    assert [h.host_id for h in sess.backend.topology.alive()] == [1]


# ---------------------------------------------------------------------------
# crash-resumable sessions (in-process half; subprocess: test_crash_resume)
# ---------------------------------------------------------------------------
def test_durable_session_resumes_partial_drain(tmp_path):
    """A durable session killed mid-drain resumes in a fresh session
    object: DONE invocations never re-execute, and the thetas are
    bitwise-identical to an uninterrupted run."""
    plan, data = _case("ridge", {"reg": 1.0})
    sdir = str(tmp_path / "sess")
    sess = DMLSession(backend="wave",
                      pool=PoolConfig(n_workers=1, memory_mb=256),
                      session_dir=sdir)
    sess.submit(plan, data)
    n_done = 0
    for _ in range(3):                   # partial drain, then "crash"
        sess.poll()
        if sess._queue and sess._queue[0].req is not None:
            n_done = sess._queue[0].req.ledger.n_done
    del sess                             # the crash: nothing carried over

    resumed = DMLSession.resume(sdir, backend="wave",
                                pool=PoolConfig(n_workers=1,
                                                memory_mb=256))
    res, = resumed.run()
    req = resumed.request(res.request_id)
    assert req.ledger.complete
    # only the not-DONE rows were re-executed in the resumed process
    assert res.report.bill.n_invocations == req.ledger.n_invocations - n_done
    ref = DMLSession(backend="inline").estimate(plan, data)
    np.testing.assert_array_equal(res.thetas, ref.thetas)
    assert res.theta == ref.theta


def test_resume_under_chaos_is_bitwise(tmp_path):
    """Crash-resume composed with fault injection: the resumed drain
    draws the SAME identity-keyed verdicts for the surviving rows (the
    checkpointed ledger carries the attempt counters), so even the
    retry schedule is reproducible and the estimate bitwise."""
    plan, data = _case("ridge", {"reg": 1.0})
    pool = PoolConfig(n_workers=2, memory_mb=256, failure_rate=0.3,
                      max_retries=10, seed=2)
    sdir = str(tmp_path / "sess")
    sess = DMLSession(backend="wave", pool=pool, session_dir=sdir)
    sess.submit(plan, data)
    for _ in range(2):
        sess.poll()
    del sess
    resumed = DMLSession.resume(sdir, backend="wave", pool=pool)
    res, = resumed.run()
    ref, _ = _run(InlineBackend(PoolConfig(n_workers=3)), plan, data)
    np.testing.assert_array_equal(
        resumed.request(res.request_id).gathered_preds(),
        ref.gathered_preds())
