"""Wave scheduler behaviors through the one execution path (DMLPlan +
backends): scaling equivalence, elasticity, fault tolerance,
checkpoint/restart, straggler mitigation, autoscaling, billing.

(The deprecated ``ServerlessExecutor`` raw-array facade was removed in
PR 3 and its import-compat module ``repro.serverless.executor`` — after
one release of DeprecationWarning notice — in PR 5; the behavior suite
lives on here against ``compile_request`` + the streaming
``WaveBackend``.)
"""
import os

import numpy as np
import pytest

from repro.core import DMLData, DMLPlan
from repro.core.session import compile_request
from repro.data import make_plr_data
from repro.serverless import (
    OccupancyAutoscaler, PoolConfig, TaskLedger, WaveBackend,
)
from repro.serverless.cost import speedup_of

DATA = DMLData.from_dict(make_plr_data(n_obs=120, dim_x=5, theta=0.5, seed=0))


def _plan(**kw):
    kw.setdefault("n_folds", 3)
    kw.setdefault("n_rep", 4)
    return DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0}, seed=11, **kw)


def _run(pool, ledger=None, **plan_kw):
    plan = _plan(scaling=pool.scaling, **plan_kw)
    req = compile_request(plan, DATA, ledger=ledger)
    WaveBackend(pool).run_requests([req])
    return req.gathered_preds(), req.ledger, req.report


def test_scaling_levels_identical_results():
    """Per-split and per-fold scaling must produce identical predictions —
    the paper's scaling knob is cost/latency only (§4.2)."""
    p1, _, _ = _run(PoolConfig(n_workers=2, scaling="n_rep"))
    p2, _, _ = _run(PoolConfig(n_workers=5, scaling="n_folds*n_rep"))
    np.testing.assert_array_equal(p1, p2)      # fixed-block B: bitwise


def test_worker_count_invariance():
    """Elasticity: results are bitwise independent of the pool size."""
    base, _, _ = _run(PoolConfig(n_workers=1, memory_mb=256))
    for w in (2, 7, 64):
        p, _, _ = _run(PoolConfig(n_workers=w, memory_mb=256))
        np.testing.assert_array_equal(base, p)


def test_fault_injection_and_retries_converge():
    pool = PoolConfig(n_workers=3, failure_rate=0.4, max_retries=8, seed=3)
    preds, ledger, rep = _run(pool)
    clean, _, _ = _run(PoolConfig(n_workers=3))
    assert rep.failures > 0
    assert ledger.complete
    np.testing.assert_array_equal(preds, clean)


def test_retry_budget_exhaustion_raises():
    pool = PoolConfig(n_workers=2, failure_rate=1.0, max_retries=0, seed=1)
    with pytest.raises(RuntimeError, match="retry budget"):
        _run(pool)


def test_ledger_checkpoint_restart(tmp_path):
    path = os.path.join(tmp_path, "ledger.msgpack")
    pool = PoolConfig(n_workers=1, memory_mb=256, checkpoint_path=path)
    preds, ledger, _ = _run(pool)
    # restart from the saved ledger: nothing left to do, same predictions
    restored = TaskLedger.load(path)
    assert restored.complete
    preds2, _, rep2 = _run(pool, ledger=restored)
    np.testing.assert_array_equal(preds, preds2)
    assert rep2.bill.n_invocations == 0          # no re-execution billed


def test_ledger_partial_resume():
    """Kill after some invocations; the restart must only run the rest."""
    pool = PoolConfig(n_workers=1, memory_mb=256)
    preds_full, led1, _ = _run(pool)
    # copy 3 done rows into a fresh ledger = crash-restored state
    led2 = TaskLedger.create(led1.n_invocations, led1.n_obs,
                             led1.tasks_per_invocation)
    led2.record_successes([0, 1, 2], led1.preds[[0, 1, 2]])
    preds2, led2, rep2 = _run(pool, ledger=led2)
    np.testing.assert_array_equal(preds_full, preds2)
    assert rep2.bill.n_invocations == led2.n_invocations - 3


def test_autoscaler_replaces_static_schedule():
    """Occupancy autoscaling: the wave backend derives worker counts from
    queue depth, records its decisions, and the estimate is untouched."""
    pool = PoolConfig(n_workers=2, memory_mb=256, autoscale=True,
                      min_workers=1, max_workers=16)
    plan = _plan(n_rep=8)
    req = compile_request(plan, DATA)
    backend = WaveBackend(pool)
    info = backend.run_requests([req])
    assert req.ledger.complete
    assert len(info.autoscale) == info.waves
    d0 = info.autoscale[0]
    assert d0.queue_depth == req.ledger.n_invocations
    assert d0.n_workers * pool.lanes_per_worker() == d0.capacity
    assert all(pool.min_workers <= d.n_workers <= pool.max_workers
               for d in info.autoscale)
    # bitwise invariance vs a static pool
    clean, _, _ = _run(PoolConfig(n_workers=4, memory_mb=256), n_rep=8)
    np.testing.assert_array_equal(req.gathered_preds(), clean)


def test_autoscaler_counts_in_flight_as_occupancy_not_depth():
    """Dispatched-but-unharvested work must raise occupancy, never the
    worker count — sizing for it again would double-provision the pool
    (the non-blocking-dispatch correctness rule)."""
    pool = PoolConfig(n_workers=2, memory_mb=1024, autoscale=True,
                      min_workers=1, max_workers=64)
    scaler = OccupancyAutoscaler(pool)
    base = scaler.decide(8)
    busy = scaler.decide(8, in_flight=64)
    assert busy.n_workers == base.n_workers        # no double-provision
    assert busy.in_flight == 64 and base.in_flight == 0
    assert busy.est_occupancy > base.est_occupancy
    assert busy.est_waves == base.est_waves
    assert busy.candidate_costs == base.candidate_costs


def test_autoscaler_scales_with_queue_depth():
    """Deeper queues get at least as many workers; shallow queues are not
    over-provisioned (cost-aware sizing)."""
    pool = PoolConfig(n_workers=2, memory_mb=1024, autoscale=True,
                      min_workers=1, max_workers=64)
    scaler = OccupancyAutoscaler(pool)
    shallow = scaler.decide(4)
    deep = scaler.decide(400)
    assert deep.n_workers >= shallow.n_workers
    assert shallow.capacity <= 4 * pool.lanes_per_worker()
    assert deep.est_waves < 400          # really parallelizes
    # decisions are deterministic pure functions of the observed state
    assert scaler.decide(400) == deep


def test_straggler_speculation_billed():
    pool = PoolConfig(n_workers=64, memory_mb=4096, straggler_rate=0.3,
                      simulate=True, base_work_s=0.1, seed=5)
    preds, ledger, rep = _run(pool)
    assert ledger.complete
    assert rep.stragglers > 0


def test_memory_speed_curve_diminishing_returns():
    s = [speedup_of(m) for m in (256, 512, 1024, 2048, 4096)]
    assert all(b > a for a, b in zip(s, s[1:]))          # monotone
    gains = [b / a for a, b in zip(s, s[1:])]
    assert all(g2 < g1 + 1e-9 for g1, g2 in zip(gains, gains[1:]))


def test_simulated_billing_tracks_memory():
    """Fig 3 mechanics: more memory => faster; billed GB-s is duration*mem."""
    t, c = {}, {}
    for mem in (256, 1024, 4096):
        pool = PoolConfig(n_workers=1000, memory_mb=mem, simulate=True,
                          base_work_s=0.5, seed=0)
        _, _, rep = _run(pool)
        t[mem] = rep.response_time_s
        c[mem] = rep.bill.total_gb_s
    assert t[4096] < t[1024] < t[256]
    for mem, bill in c.items():
        assert bill > 0


def test_executor_compat_module_removed():
    """PR 4 shipped the one-release DeprecationWarning notice; the
    import-compat module is now gone.  Everything it re-exported lives
    on repro.serverless / repro.core."""
    with pytest.raises(ModuleNotFoundError):
        import repro.serverless.executor  # noqa: F401
    from repro.core import DMLSession, estimate  # noqa: F401
    from repro.serverless import (                # noqa: F401
        RunReport, Segment, WaveBackend as _W, WorkRequest,
    )
    assert PoolConfig is not None
