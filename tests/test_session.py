"""DMLSession: many estimation requests fused into shared waves on one
warm backend, each returning the theta it would get running alone."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import DMLData, DMLPlan, DMLSession, estimate
from repro.core.session import assemble_result
from repro.data import make_irm_data, make_plr_data
from repro.serverless import PoolConfig


def _plr_plan(seed, **kw):
    return DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0}, n_folds=3, n_rep=2,
                             seed=seed, **kw)


def test_session_batches_two_requests_into_shared_waves():
    """The acceptance property: >= 2 concurrent requests share waves on
    one backend and every theta matches its solo run exactly."""
    data_a = DMLData.from_dict(make_plr_data(n_obs=150, dim_x=5, theta=0.5,
                                             seed=1))
    data_b = DMLData.from_dict(make_plr_data(n_obs=110, dim_x=4, theta=0.2,
                                             seed=2))
    plan_a, plan_b = _plr_plan(seed=7), _plr_plan(seed=13)
    # capacity of 2 lanes/wave forces several waves -> real interleaving
    pool = PoolConfig(n_workers=2, memory_mb=256)

    sess = DMLSession(backend="wave", pool=pool)
    rid_a = sess.submit(plan_a, data_a)
    rid_b = sess.submit(plan_b, data_b)
    res_a, res_b = sess.run()
    info = sess.last_run_info

    assert info.shared_waves >= 1                 # grids really fused
    assert info.waves >= 2                        # capacity-limited batching
    assert {rid_a, rid_b} <= {m for mm in info.wave_members for m in mm}

    # solo runs (default capacity): wave composition differs, thetas don't
    # (fused-batch shape only moves float32 reduction order, ~1e-8)
    solo_a = estimate(plan_a, data_a, backend="wave")
    solo_b = estimate(plan_b, data_b, backend="wave")
    np.testing.assert_allclose(res_a.theta, solo_a.theta, rtol=0, atol=1e-6)
    np.testing.assert_allclose(res_b.theta, solo_b.theta, rtol=0, atol=1e-6)
    np.testing.assert_allclose(res_a.se, solo_a.se, rtol=1e-5)
    assert sess.result(rid_a).theta == res_a.theta
    assert res_a.request_id == rid_a


def test_session_mixed_models_and_faults():
    """PLR + IRM co-scheduled under fault injection: schedules differ,
    estimates don't."""
    data_p = DMLData.from_dict(make_plr_data(n_obs=130, dim_x=4, theta=0.5,
                                             seed=3))
    data_i = DMLData.from_dict(make_irm_data(n_obs=170, dim_x=4, theta=0.4,
                                             seed=4))
    plan_p = _plr_plan(seed=21)
    plan_i = DMLPlan.for_model("irm", learner="ridge", n_folds=3, n_rep=2,
                               seed=22)
    chaotic = PoolConfig(n_workers=2, memory_mb=256, failure_rate=0.3,
                         max_retries=10, seed=5)
    sess = DMLSession(backend="wave", pool=chaotic)
    sess.submit(plan_p, data_p)
    sess.submit(plan_i, data_i)
    res_p, res_i = sess.run()
    assert res_p.report.failures + res_i.report.failures > 0
    clean_p = estimate(plan_p, data_p)
    clean_i = estimate(plan_i, data_i)
    np.testing.assert_allclose(res_p.theta, clean_p.theta, rtol=0, atol=1e-7)
    np.testing.assert_allclose(res_i.theta, clean_i.theta, rtol=0, atol=1e-7)


@pytest.mark.parametrize("backend", ["inline", "sharded"])
def test_session_other_backends(backend):
    data = DMLData.from_dict(make_plr_data(n_obs=120, dim_x=4, theta=0.5,
                                           seed=6))
    sess = DMLSession(backend=backend)
    sess.submit(_plr_plan(seed=31), data)
    sess.submit(_plr_plan(seed=32), data)
    res = sess.run()
    solo = estimate(_plr_plan(seed=31), data, backend=backend)
    assert res[0].theta == solo.theta


def test_session_stays_warm_across_runs():
    """The backend (and its caches) persist across run() calls."""
    data = DMLData.from_dict(make_plr_data(n_obs=100, dim_x=3, theta=0.5,
                                           seed=8))
    sess = DMLSession(backend="sharded")
    first = sess.estimate(_plr_plan(seed=41), data)
    programs = dict(sess.backend._programs)
    second = sess.estimate(_plr_plan(seed=41), data)
    assert first.theta == second.theta
    assert sess.backend._programs.keys() >= programs.keys()


def test_session_keeps_queue_and_ledgers_on_backend_abort():
    """A mid-drain backend failure (retry budget) must not discard queued
    requests: they stay queued with their ledgers and a later run()
    resumes them."""
    from repro.serverless import make_backend

    data = DMLData.from_dict(make_plr_data(n_obs=90, dim_x=3, theta=0.5,
                                           seed=10))
    doomed = PoolConfig(n_workers=2, failure_rate=1.0, max_retries=0, seed=1)
    sess = DMLSession(backend="wave", pool=doomed)
    rid = sess.submit(_plr_plan(seed=61), data)
    with pytest.raises(RuntimeError, match="retry budget"):
        sess.run()
    assert len(sess._queue) == 1                   # request not lost
    sess.backend = make_backend("wave", PoolConfig(n_workers=2))
    res, = sess.run()
    assert res.request_id == rid
    solo = estimate(_plr_plan(seed=61), data)
    np.testing.assert_allclose(res.theta, solo.theta, rtol=0, atol=1e-6)


def test_session_empty_run_and_billing_split():
    sess = DMLSession(backend="wave", pool=PoolConfig(n_workers=4))
    assert sess.run() == []
    data = DMLData.from_dict(make_plr_data(n_obs=100, dim_x=3, theta=0.5,
                                           seed=9))
    sess.submit(_plr_plan(seed=51), data)
    sess.submit(_plr_plan(seed=52), data)
    res = sess.run()
    # per-request billing: each request pays exactly its own M*L invocations
    for r in res:
        assert r.report.bill.n_invocations == 2 * 2
    assert sess.run() == []                       # queue drained


def test_assembly_touches_no_device_array_without_a_bootstrap():
    """A finished plr request assembles in numpy on the host: no explicit
    transfer under the guard, ``on_host=1`` on its span, and the result the
    session returned.  With a bootstrap it still assembles, with
    ``on_host=0``, and the guard catches its device draws."""
    data = DMLData.from_dict(make_plr_data(n_obs=120, dim_x=4, seed=2))
    sess = DMLSession(backend="inline", pool=PoolConfig(n_workers=2))
    rid = sess.submit(_plr_plan(5), data)
    sess.run()
    req, served = sess.request(rid), sess.result(rid)
    obs.clear()
    with jax.transfer_guard("disallow_explicit"), obs.recording():
        res = assemble_result(_plr_plan(5), data, req, request_id=rid)
    (sp,) = [s for s in obs.spans() if s.name == "session.assemble"]
    assert sp.args["on_host"] == 1 and sp.rid == rid
    assert (res.theta, res.se) == (served.theta, served.se)
    assert res.thetas.dtype == res.psi[0].dtype == np.float32
    assert np.array_equal(res.thetas, served.thetas)

    boot = _plr_plan(5, n_boot=50)
    with jax.transfer_guard("disallow_explicit"), \
            pytest.raises(Exception, match="transfer"):
        assemble_result(boot, data, req)
    obs.clear()
    with obs.recording():
        res = assemble_result(boot, data, req)
    (sp,) = [s for s in obs.spans() if s.name == "session.assemble"]
    assert sp.args["on_host"] == 0 and res.boot_ci is not None
    assert res.theta == served.theta
    obs.clear()
