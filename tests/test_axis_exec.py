"""ISSUE 9 — the drain executes the axis planner's data/feature layouts.

``dispatch_bucket`` lowers a data@m/feature@m ``AxisDecision`` through
the in-mesh Gram executors (sharding/gram.py) and stamps the axis it
actually ran back on the decision.  These tests pin:

  * dispatch-level parity of the executed layouts against the bitwise
    task-axis reference for every Gram family (explicit tolerance tier
    — the split reductions retile, never bitwise);
  * the fallback contract: a non-divisible layout runs task-axis,
    bitwise, and stamps ``executed == "task"``;
  * out-of-order harvest of in-flight axis launches;
  * the chunk-paged tall-N path: a bucket whose N_pad exceeds
    DEVICE_PAGE_ROWS completes under a continuous ShardedBackend drain
    via data-parallel chunk streaming (impossible on the one-page
    task layout), with the decision's ``executed`` field logged;
  * TopologyBackend routing: tall-N Gram buckets land only on hosts
    whose data axis can stream them.

All tests run on 1-device and forced 8-device platforms alike: the
decisions adapt (data@1 chunk rescue vs data@8 sharding) but the
parity and bookkeeping contracts are identical.
"""
import numpy as np
import pytest

from repro.compile import plan_buckets, run_bucket
from repro.compile.buckets import AxisDecision
from repro.compile.program import ProgramCache, dispatch_bucket
from repro.core import DMLData, DMLPlan
from repro.core.session import compile_request
from repro.data import make_plr_data
from repro.launch.mesh import make_host_mesh
from repro.launch.roofline import GRAM_FAMILIES
from repro.serverless import InlineBackend, PoolConfig, ShardedBackend
from repro.serverless.topology import TopologyBackend

#: the sharded-axis float tolerance tier (module docstring in
#: sharding/gram.py): split reductions retile, parity is ~1e-6 — the
#: gate leaves an order of magnitude of headroom
AXIS_ATOL = 5e-4

_PARAMS = {"ols": {}, "ridge": {"reg": 1.0},
           "lasso": {"reg": 0.01, "n_iter": 60}}


def _req(learner, n_obs=104, seed=0, dim_x=5):
    data = DMLData.from_dict(make_plr_data(n_obs=n_obs, dim_x=dim_x,
                                           theta=0.5, seed=seed))
    plan = DMLPlan.for_model("plr", learner=learner,
                             learner_params=_PARAMS[learner],
                             n_folds=3, n_rep=2, seed=seed + 100)
    return compile_request(plan, data)


def _decision(bkey, axis, m, n_tasks):
    return AxisDecision(bucket=bkey, axis=axis, shards=m,
                        n_tasks=n_tasks, n_pad=bkey.n_pad,
                        p_pad=bkey.p_pad, mesh_devices=m)


@pytest.mark.parametrize("family", GRAM_FAMILIES)
@pytest.mark.parametrize("axis", ["data", "feature"])
def test_dispatch_executes_planned_axis(family, axis):
    """A hand-built data/feature decision executes through the in-mesh
    Gram program and agrees with the task-axis reference to the
    explicit tolerance tier; the executed axis is stamped."""
    mesh = make_host_mesh()
    m = int(mesh.shape["data"])
    req = _req(family)
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    entries = bplan.pending_by_bucket()[bkey]
    ref, _ = run_bucket(bplan, ProgramCache(), bkey, entries)

    dec = _decision(bkey, axis, m, len(entries))
    bd = dispatch_bucket(bplan, ProgramCache(), bkey, entries,
                         axis_decision=dec, mesh=mesh)
    got = bd.harvest()
    divisible = (bkey.n_pad if axis == "data" else bkey.p_pad) % m == 0
    if divisible:
        assert dec.executed == axis
        for e in entries:
            np.testing.assert_allclose(got[e], ref[e], rtol=0,
                                       atol=AXIS_ATOL)
    else:                       # fallback is the bitwise task program
        assert dec.executed == "task"
        for e in entries:
            np.testing.assert_array_equal(got[e], ref[e])


def test_task_decision_stamps_executed():
    """A task-axis decision (and a missing mesh) keep the bitwise task
    path and stamp ``executed == "task"``."""
    req = _req("ridge")
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    entries = bplan.pending_by_bucket()[bkey]
    ref, _ = run_bucket(bplan, ProgramCache(), bkey, entries)

    for dec, mesh in [(_decision(bkey, "task", 1, len(entries)),
                       make_host_mesh()),
                      (_decision(bkey, "data", 1, len(entries)), None)]:
        bd = dispatch_bucket(bplan, ProgramCache(), bkey, entries,
                             axis_decision=dec, mesh=mesh)
        got = bd.harvest()
        assert dec.executed == "task"
        for e in entries:
            np.testing.assert_array_equal(got[e], ref[e])


def test_axis_dispatch_out_of_order_harvest():
    """Two in-flight axis launches harvest in reverse dispatch order —
    the non-blocking drain never assumes FIFO settlement."""
    mesh = make_host_mesh()
    m = int(mesh.shape["data"])
    reqs = [_req("ridge", n_obs=104, seed=0),
            _req("ridge", n_obs=144, seed=1)]
    bplan = plan_buckets(reqs)
    groups = bplan.pending_by_bucket()
    assert len(groups) == 2
    refs = {k: run_bucket(bplan, ProgramCache(), k, es)[0]
            for k, es in groups.items()}
    cache = ProgramCache()
    bds = []
    for bkey, entries in groups.items():
        dec = _decision(bkey, "data", m, len(entries))
        bds.append((bkey, dec, dispatch_bucket(
            bplan, cache, bkey, entries, axis_decision=dec, mesh=mesh)))
    for bkey, dec, bd in reversed(bds):
        got = bd.harvest()
        assert dec.executed in ("data", "task")
        ref = refs[bkey]
        for e, r in ref.items():
            if dec.executed == "data":
                np.testing.assert_allclose(got[e], r, rtol=0,
                                           atol=AXIS_ATOL)
            else:
                np.testing.assert_array_equal(got[e], r)


def test_tall_bucket_chunk_paged_drain(monkeypatch):
    """The headline path: a bucket with N_pad > DEVICE_PAGE_ROWS
    completes under a continuous ShardedBackend drain by chunk-paged
    data-parallel streaming, the planner's decision is executed, and
    the results agree with the inline reference to the tolerance
    tier."""
    from repro.launch import roofline
    monkeypatch.setattr(roofline, "DEVICE_PAGE_ROWS", 16)

    ref_req = _req("ridge", n_obs=264, seed=3)
    InlineBackend().run_requests([ref_req])

    req = _req("ridge", n_obs=264, seed=3)
    info = ShardedBackend().run_requests([req])
    assert req.ledger.complete
    np.testing.assert_allclose(req.gathered_preds(),
                               ref_req.gathered_preds(),
                               rtol=0, atol=AXIS_ATOL)
    assert len(info.axis_plans) == 1
    dec = info.axis_plans[0]
    assert dec.axis == "data"           # task layout can't hold the page
    assert dec.executed == "data"       # ...and the drain ran the plan


def test_forced_feature_decision_executes_in_drain(monkeypatch):
    """A feature@m decision injected at the planner seam executes
    through the drain (executed stamp + tolerance-tier parity) — the
    drain's wiring is axis-agnostic."""
    import repro.compile.buckets as buckets_mod

    mesh = make_host_mesh()
    m = int(mesh.shape["data"])

    def force_feature(key, *, n_tasks, n_devices):
        return _decision(key, "feature", n_devices, n_tasks)

    monkeypatch.setattr(buckets_mod, "plan_bucket_axis", force_feature)

    ref_req = _req("ols", n_obs=120, seed=5)
    InlineBackend().run_requests([ref_req])
    req = _req("ols", n_obs=120, seed=5)
    info = ShardedBackend().run_requests([req])
    assert req.ledger.complete
    dec = info.axis_plans[0]
    expect = "feature" if dec.p_pad % m == 0 else "task"
    assert dec.executed == expect
    np.testing.assert_allclose(req.gathered_preds(),
                               ref_req.gathered_preds(),
                               rtol=0, atol=AXIS_ATOL)


def test_sharded_drain_small_bucket_stays_task():
    """The serving-size pin: a small fitting bucket keeps the untaxed
    task layout and the drain stamps ``executed == "task"`` — the
    decision-vs-executed mix is auditable end to end."""
    req = _req("ridge")
    info = ShardedBackend().run_requests([req])
    assert req.ledger.complete
    assert len(info.axis_plans) == 1
    dec = info.axis_plans[0]
    assert dec.axis == "task"
    assert dec.executed == "task"


def test_topology_routes_tall_buckets_to_streaming_hosts(monkeypatch):
    """Tall-N Gram buckets are routed (and stolen) only by hosts whose
    data axis can stream them, and the drain completes them via the
    executed data layout."""
    from repro.launch import roofline
    monkeypatch.setattr(roofline, "DEVICE_PAGE_ROWS", 16)

    ref_req = _req("ridge", n_obs=280, seed=7)
    InlineBackend().run_requests([ref_req])

    backend = TopologyBackend(PoolConfig(n_workers=4), n_hosts=2)
    req = _req("ridge", n_obs=280, seed=7)
    state = backend.begin_drain()
    backend.admit(state, req)
    while backend.step(state):
        pass
    backend._finish(state)
    assert req.ledger.complete
    np.testing.assert_allclose(req.gathered_preds(),
                               ref_req.gathered_preds(),
                               rtol=0, atol=AXIS_ATOL)
    assert state.info.axis_plans
    assert all(d.executed == "data" for d in state.info.axis_plans
               if d.axis == "data")
    # every placement respected the bucket's eligible-host set
    for key, _, host, _ in state.info.topology.placements:
        assert host in backend._eligible_hosts(key)
