"""ExecutionBackend parity: the same (DMLPlan, DMLData, seed) must yield
identical (M,K,L,N) predictions and theta on every backend — including the
wave backend under fault injection, retries, and speculation."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.compile import program
from repro.core import DMLData, DMLPlan, DMLSession, estimate
from repro.core.session import assemble_result, compile_request
from repro.data import make_irm_data, make_plr_data
from repro.serverless import PoolConfig, make_backend
from repro.serverless import backends
from repro.serverless.backends import (
    BACKEND_NAMES, InlineBackend, ShardedBackend, WaveBackend,
)

POOL = PoolConfig(n_workers=3, memory_mb=512)


def _run_backend(backend, plan, data):
    """Fresh compile + drain on one backend; returns (preds, result)."""
    req = compile_request(plan, data)
    backend.run_requests([req])
    assert req.ledger.complete
    return req.gathered_preds(), assemble_result(plan, data, req)


@pytest.fixture(scope="module")
def plr_case():
    data = DMLData.from_dict(make_plr_data(n_obs=140, dim_x=5, theta=0.5,
                                           seed=3))
    plan = DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0}, n_folds=3, n_rep=2,
                             seed=7)
    return plan, data


@pytest.fixture(scope="module")
def irm_case():
    data = DMLData.from_dict(make_irm_data(n_obs=160, dim_x=4, theta=0.4,
                                           seed=6))
    plan = DMLPlan.for_model("irm", learner="ridge", n_folds=3, n_rep=2,
                             seed=11)
    return plan, data


@pytest.mark.parametrize("case", ["plr_case", "irm_case"])
@pytest.mark.parametrize("scaling", ["n_rep", "n_folds*n_rep"])
def test_backend_parity(case, scaling, request):
    plan, data = request.getfixturevalue(case)
    plan = plan.replace(scaling=scaling)
    p_inline, r_inline = _run_backend(InlineBackend(POOL), plan, data)
    p_wave, r_wave = _run_backend(WaveBackend(POOL), plan, data)
    p_shard, r_shard = _run_backend(ShardedBackend(POOL), plan, data)
    np.testing.assert_allclose(p_wave, p_inline, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(p_shard, p_inline, rtol=1e-6, atol=1e-6)
    assert r_wave.theta == pytest.approx(r_inline.theta, abs=1e-7)
    # shard_map can retile the per-lane reductions, so the sharded
    # backend agrees to float tolerance, not bitwise (it is exact on a
    # 1-device mesh; the multihost-smoke job runs this 8-way)
    assert r_shard.theta == pytest.approx(r_inline.theta, abs=1e-6)


def test_wave_parity_under_faults_and_stragglers(plr_case):
    """Fault injection + retries + speculative duplicates change the
    schedule, never the estimate."""
    plan, data = plr_case
    p_ref, r_ref = _run_backend(InlineBackend(POOL), plan, data)
    chaotic = PoolConfig(n_workers=2, memory_mb=512, failure_rate=0.4,
                         straggler_rate=0.3, max_retries=10, seed=3)
    p_wave, r_wave = _run_backend(WaveBackend(chaotic), plan, data)
    assert r_wave.report.failures > 0
    np.testing.assert_allclose(p_wave, p_ref, rtol=1e-6, atol=1e-6)
    assert r_wave.theta == pytest.approx(r_ref.theta, abs=1e-7)


def test_backend_selected_via_plan(plr_case):
    plan, data = plr_case
    thetas = {name: estimate(plan.replace(backend=name), data).theta
              for name in BACKEND_NAMES}
    # the unsharded schedulers are bitwise-identical; sharded agrees to
    # float tolerance on multi-device meshes (exact on 1 device)
    assert thetas["wave"] == thetas["inline"] == thetas["topology"]
    assert thetas["sharded"] == pytest.approx(thetas["inline"], abs=1e-6)


def test_sharded_backend_stays_warm(plr_case):
    """Compiled SPMD programs are cached by learner spec, not object
    identity — a second request with an equal spec reuses the program."""
    plan, data = plr_case
    backend = ShardedBackend(POOL)
    _run_backend(backend, plan, data)
    assert len(backend._programs) == 1
    _run_backend(backend, plan, data)        # fresh partial, same spec
    assert len(backend._programs) == 1
    other = plan.replace(
        nuisances=tuple(
            type(ns).make(ns.name, ns.target, ns.learner, {"reg": 9.0})
            for ns in plan.nuisances))
    _run_backend(backend, other, data)       # different params -> new entry
    assert len(backend._programs) == 2


def test_backends_resume_from_ledger(plr_case):
    """All backends skip pre-completed ledger rows (durable resume)."""
    plan, data = plr_case
    for name in BACKEND_NAMES:
        req = compile_request(plan, data)
        make_backend(name, POOL).run_requests([req])
        done = req.ledger
        req2 = compile_request(plan, data, ledger=done)
        make_backend(name, POOL).run_requests([req2])
        assert req2.report.bill.n_invocations == 0
        np.testing.assert_array_equal(req2.gathered_preds(),
                                      req.gathered_preds())


def test_bucketed_multi_request_parity_all_backends():
    """The compiler's acceptance property: a mixed-N, mixed-model batch of
    requests drained through shared buckets yields identical predictions
    and theta on Inline, Sharded, and Wave — including Wave under fault
    injection + speculation."""
    cases = [
        (DMLPlan.for_model("plr", learner="ridge",
                           learner_params={"reg": 1.0}, n_folds=3, n_rep=2,
                           seed=7),
         DMLData.from_dict(make_plr_data(n_obs=140, dim_x=5, theta=0.5,
                                         seed=3))),
        (DMLPlan.for_model("plr", learner="ridge",
                           learner_params={"reg": 1.0}, n_folds=3, n_rep=2,
                           seed=9),
         DMLData.from_dict(make_plr_data(n_obs=200, dim_x=5, theta=0.2,
                                         seed=4))),
        (DMLPlan.for_model("irm", learner="ridge", n_folds=3, n_rep=2,
                           seed=11),
         DMLData.from_dict(make_irm_data(n_obs=120, dim_x=4, theta=0.4,
                                         seed=6))),
    ]

    def drain(backend):
        reqs = [compile_request(p, d) for p, d in cases]
        info = backend.run_requests(reqs)
        assert all(r.ledger.complete for r in reqs)
        preds = [r.gathered_preds() for r in reqs]
        thetas = [assemble_result(p, d, r).theta
                  for (p, d), r in zip(cases, reqs)]
        return preds, thetas, info

    p_in, t_in, info_in = drain(InlineBackend(POOL))
    # sublane-aligned N buckets: the plr requests (N=140 -> 144, 200 ->
    # 200) and irm (ridge + logistic at N=120) give 4 buckets for 4
    # segments; cross-request sharing now happens at the fused-launch
    # level (equal-shape blocks), not by collapsing N onto pow2
    assert info_in.buckets == 4
    chaotic = PoolConfig(n_workers=2, memory_mb=512, failure_rate=0.3,
                         straggler_rate=0.2, max_retries=10, seed=5)
    p_wv, t_wv, info_wv = drain(WaveBackend(chaotic))
    p_sh, t_sh, _ = drain(ShardedBackend(POOL))
    for a, b in zip(p_wv, p_in):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    for a, b in zip(p_sh, p_in):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert t_wv == pytest.approx(t_in, abs=1e-7)
    assert t_sh == pytest.approx(t_in, abs=1e-6)   # shard retiling noise


def test_key_consuming_learners_identical_across_backends():
    """Per-task fold_in keys fix the PR-1 caveat: kernel_ridge (key-
    consuming) now produces bitwise-identical predictions on every
    backend and under any wave composition."""
    data = DMLData.from_dict(make_plr_data(n_obs=100, dim_x=4, theta=0.5,
                                           seed=12))
    plan = DMLPlan.for_model("plr", learner="kernel_ridge",
                             learner_params={"reg": 1.0, "n_landmarks": 32},
                             n_folds=3, n_rep=1, seed=21)
    p_in, r_in = _run_backend(InlineBackend(POOL), plan, data)
    p_wv, r_wv = _run_backend(
        WaveBackend(PoolConfig(n_workers=1, memory_mb=256)), plan, data)
    p_sh, r_sh = _run_backend(ShardedBackend(POOL), plan, data)
    np.testing.assert_array_equal(p_wv, p_in)
    np.testing.assert_allclose(p_sh, p_in, rtol=1e-6, atol=1e-6)
    assert r_wv.theta == r_in.theta
    assert r_sh.theta == pytest.approx(r_in.theta, abs=1e-6)


def _parent_key_table(seed, n_tasks):
    """A seed's key table as the key itself gives it, on the default
    device."""
    key = jax.random.key(seed)
    return np.asarray(jax.vmap(
        lambda i: jax.random.key_data(jax.random.fold_in(key, i)))(
            np.arange(n_tasks)))


@pytest.mark.parametrize("n_tasks", [10, 1000])
@pytest.mark.parametrize("seed", [0, 1, 12345, 2**30 - 1, 2**31 + 5])
def test_seed_key_table_is_the_keys_own(seed, n_tasks, monkeypatch):
    """A plan segment's table builds from its seed on the host's CPU
    device, bitwise equal to folding the seed's own key, inside one
    ``keys.table`` span."""
    monkeypatch.setattr(backends, "_KEY_TABLE_CACHE", {})
    obs.clear()
    with obs.recording():
        table = backends._segment_key_table(None, n_tasks,
                                            key_ref=("seed", seed))
        again = backends._segment_key_table(None, n_tasks,
                                            key_ref=("seed", seed))
    (sp,) = [s for s in obs.spans() if s.name == "keys.table"]
    obs.clear()
    assert sp.args == {"tasks": n_tasks, "platform": "cpu"}
    assert type(table) is np.ndarray and again is table
    np.testing.assert_array_equal(table, _parent_key_table(seed, n_tasks))


def test_seed_key_tables_give_the_parents_estimate(monkeypatch):
    """A key-consuming learner through a session: theta and SE with the
    tables built from the seed are bitwise those with each table folded
    from ``jax.random.key(seed)`` on the default device, and a table of
    another seed moves them (the learner reads its keys)."""
    data = DMLData.from_dict(make_plr_data(n_obs=150, dim_x=4, theta=0.5,
                                           seed=12))
    plan = DMLPlan.for_model("plr", learner="kernel_ridge",
                             learner_params={"reg": 1.0, "n_landmarks": 16},
                             n_folds=3, n_rep=2, seed=33)

    def run(build=None):
        monkeypatch.setattr(backends, "_KEY_TABLE_CACHE", {})
        monkeypatch.setattr(program, "_BLOCK_TENSOR_CACHE", {})
        if build is not None:
            monkeypatch.setattr(
                backends, "_build_key_table",
                lambda key, seed, n: (build(seed, n), "default"))
        sess = DMLSession(backend="wave", pool=POOL)
        sess.submit(plan, data)
        (res,) = sess.run()
        return res

    ours = run()
    parents = run(_parent_key_table)
    shifted = run(lambda seed, n: _parent_key_table(seed + 1, n))
    assert (ours.theta, ours.se) == (parents.theta, parents.se)
    np.testing.assert_array_equal(ours.thetas, parents.thetas)
    np.testing.assert_array_equal(ours.ses, parents.ses)
    assert not np.array_equal(ours.thetas, shifted.thetas)


def test_make_backend_registry():
    assert make_backend("wave", POOL).pool is POOL
    with pytest.raises(KeyError):
        make_backend("nope")
    inst = InlineBackend(POOL)
    assert make_backend(inst) is inst


# ---------------------------------------------------------------------------
# the pending view against the full scan it replaced
# ---------------------------------------------------------------------------
def _ref_wave_fill(plan, exclude, gated, capacity):
    """The wave fill as a scan over every admitted request's ledger:
    round-robin over each request's dispatchable invocations, grouped by
    bucket, whole buckets in first-appearance order, oversized buckets
    split round-robin."""
    pendings = [[int(i) for i in req.ledger.pending()
                 if (ri, int(i)) not in exclude
                 and (ri, int(i)) not in gated]
                for ri, req in enumerate(plan.requests)]
    rr, cursors = [], [0] * len(pendings)
    while True:
        progressed = False
        for ri, p in enumerate(pendings):
            if cursors[ri] < len(p):
                rr.append((ri, p[cursors[ri]]))
                cursors[ri] += 1
                progressed = True
        if not progressed:
            break
    batch, oversized = [], []
    for ents in plan.group_entries(rr).values():
        if len(ents) > capacity:
            oversized.append(ents)
        elif len(ents) <= capacity - len(batch):
            batch.extend(ents)
    cur = [0] * len(oversized)
    while len(batch) < capacity:
        progressed = False
        for gi, ents in enumerate(oversized):
            if cur[gi] < len(ents) and len(batch) < capacity:
                batch.append(ents[cur[gi]])
                cur[gi] += 1
                progressed = True
        if not progressed:
            break
    return batch


def _ref_pending_by_bucket(plan, exclude, gated):
    """Every not-DONE invocation outside ``exclude``, bucketed by a scan
    over every admitted request, then stripped of backoff-gated rows."""
    entries = [(ri, int(inv)) for ri, req in enumerate(plan.requests)
               for inv in req.ledger.pending()
               if (ri, int(inv)) not in exclude]
    out = {}
    for key, ents in plan.group_entries(entries).items():
        ents = [e for e in ents if e not in gated]
        if ents:
            out[key] = ents
    return out


@pytest.fixture
def oracle(monkeypatch):
    """Check every read of the live pending view, in real drains,
    against the full-scan reference over the same state."""
    from repro.compile.buckets import MegabatchPlan, PendingView
    seen = {"fill": 0, "groups": 0}
    admit, sync = MegabatchPlan.admit, PendingView.sync
    fill, by_bucket = PendingView.fill, MegabatchPlan.pending_by_bucket

    def admit_(self, req):
        self.pending.plan = self            # the reference scans the plan
        return admit(self, req)

    def sync_(self, *, exclude, gated=None):
        self.synced = (set(exclude or ()), set(gated or ()))
        return sync(self, exclude=exclude, gated=gated)

    def fill_(self, capacity):
        got = fill(self, capacity)
        assert got == _ref_wave_fill(self.plan, *self.synced, capacity)
        seen["fill"] += 1
        return got

    def by_bucket_(self, exclude=None, gated=None):
        got = by_bucket(self, exclude=exclude, gated=gated)
        ref = _ref_pending_by_bucket(self, set(exclude or ()),
                                     set(gated or ()))
        assert list(got.items()) == list(ref.items())
        seen["groups"] += 1
        return got

    monkeypatch.setattr(MegabatchPlan, "admit", admit_)
    monkeypatch.setattr(PendingView, "sync", sync_)
    monkeypatch.setattr(PendingView, "fill", fill_)
    monkeypatch.setattr(MegabatchPlan, "pending_by_bucket", by_bucket_)
    return seen


def _mixed_cases(n=4):
    """Requests over several buckets each (IRM: ridge + logistic) and
    several N, so a wave holds whole and oversized buckets."""
    out = []
    for i in range(n):
        if i % 2:
            data = DMLData.from_dict(make_irm_data(
                n_obs=120 + 8 * i, dim_x=4, theta=0.4, seed=60 + i))
            plan = DMLPlan.for_model("irm", learner="ridge", n_folds=3,
                                     n_rep=2, seed=11 + i)
        else:
            data = DMLData.from_dict(make_plr_data(
                n_obs=140 + 60 * (i % 4), dim_x=5, theta=0.5, seed=3 + i))
            plan = DMLPlan.for_model(
                "plr", learner="ridge", learner_params={"reg": 1.0},
                n_folds=3, n_rep=2, seed=7 + i,
                scaling="n_folds*n_rep" if i % 4 == 0 else "n_rep")
        out.append((plan, data))
    return out


CHAOS_POOL = dict(failure_rate=0.3, straggler_rate=0.3, max_retries=10,
                  seed=4)


@pytest.mark.parametrize("scenario", [
    "wave_depth2", "wave_backoff", "wave_hedges", "wave_resumed_running",
    "inline_backoff", "sharded_backoff", "topology_kill_host",
    "topology_backoff", "topology_hedges"])
def test_pending_view_matches_full_scan(oracle, scenario):
    """The view's wave fill and its bucketed view give the reference's
    batch, entry for entry and in order, at every step of real drains:
    several buckets a request, pipelined waves (depth 2), backoff-gated
    retries under a chaos pool on every backend, hedges on one stream
    and across hosts, resumed ledgers with RUNNING rows, and a topology
    drain that loses a host."""
    from repro.serverless import TaskLedger
    from repro.serverless.ledger import RUNNING
    cases = _mixed_cases()
    pools = {
        "wave_depth2": PoolConfig(n_workers=2, memory_mb=512,
                                  pipeline_depth=2),
        "wave_backoff": PoolConfig(n_workers=2, memory_mb=512,
                                   retry_backoff_s=0.003, **CHAOS_POOL),
        "wave_hedges": PoolConfig(n_workers=3, memory_mb=256,
                                  straggler_hold_s=0.02,
                                  hedge_after_s=0.002, **CHAOS_POOL),
        "wave_resumed_running": PoolConfig(n_workers=2, memory_mb=512),
        "inline_backoff": PoolConfig(retry_backoff_s=0.003, **CHAOS_POOL),
        "sharded_backoff": PoolConfig(retry_backoff_s=0.003, **CHAOS_POOL),
        "topology_kill_host": PoolConfig(n_workers=1, memory_mb=512,
                                         n_hosts=2, **CHAOS_POOL),
        "topology_backoff": PoolConfig(n_workers=1, memory_mb=512,
                                       n_hosts=2, retry_backoff_s=0.003,
                                       **CHAOS_POOL),
        "topology_hedges": PoolConfig(n_workers=1, memory_mb=256,
                                      n_hosts=2, straggler_hold_s=0.02,
                                      hedge_after_s=0.002, **CHAOS_POOL),
    }
    backend = make_backend(scenario.split("_")[0], pools[scenario])
    reqs = [compile_request(p, d) for p, d in cases]
    if scenario == "wave_resumed_running":
        # ledgers of a drain that died: some rows DONE, some RUNNING
        full = [compile_request(p, d) for p, d in cases]
        InlineBackend(POOL).run_requests(full)
        rng = np.random.default_rng(5)
        resumed = []
        for (p, d), done in zip(cases, full):
            led = TaskLedger.create(done.ledger.n_invocations,
                                    done.ledger.n_obs,
                                    done.ledger.tasks_per_invocation)
            draw = rng.random(led.n_invocations)
            ok = np.flatnonzero(draw < 0.4)
            led.record_successes(ok, done.ledger.preds[ok])
            led.status[(draw >= 0.4) & (draw < 0.7)] = RUNNING
            resumed.append(compile_request(p, d, ledger=led))
        reqs = resumed
    state = backend.begin_drain()
    for req in reqs[:2]:
        backend.admit(state, req)
    steps = 0
    killed = False
    while backend.step(state):
        steps += 1
        if steps == 2:                      # admission mid-drain
            for req in reqs[2:]:
                backend.admit(state, req)
        q = state.queues.get(0)
        if scenario == "topology_kill_host" and not killed \
                and q is not None and q.in_flight > 0:
            assert backend.kill_host(state, 0) > 0
            killed = True
    backend._finish(state)
    assert all(r.ledger.complete for r in reqs)
    assert oracle["fill"] + oracle["groups"] >= steps // 2
    if scenario.startswith("wave"):
        assert oracle["fill"] > 0
    else:
        assert oracle["groups"] > 0
    if scenario == "topology_kill_host":
        assert killed
    if scenario.endswith("_backoff"):
        assert any(r.report.failures for r in reqs)
    if scenario.endswith("_hedges"):
        assert state.info.dispatch.hedges > 0


@pytest.mark.parametrize("seed", range(6))
def test_pending_view_matches_full_scan_on_random_states(seed):
    """Seeded random drain states: resumed ledgers with DONE and RUNNING
    rows, arbitrary dispatched subsets, some still in flight, some
    booked DONE, some gated by a retry backoff, completed requests
    retired.  Every capacity gets the reference's wave, and the
    bucketed view the reference's groups."""
    from repro.compile.buckets import plan_buckets
    from repro.serverless.ledger import DONE, RUNNING
    rng = np.random.default_rng(seed)
    cases = _mixed_cases(3 + seed % 3)
    reqs = [compile_request(p, d) for p, d in cases]
    for req in reqs:
        draw = rng.random(req.ledger.n_invocations)
        req.ledger.status[draw < 0.2] = DONE
        req.ledger.status[(draw >= 0.2) & (draw < 0.35)] = RUNNING
    plan = plan_buckets(reqs)
    view = plan.pending
    out: set = set()
    for _ in range(4):
        # dispatch a random subset of what the view holds
        owed = [e for es in view.groups().values() for e in es]
        pick = [e for e in owed if rng.random() < 0.45]
        by_req = {}
        for ri, inv in pick:
            by_req.setdefault(ri, []).append(inv)
        for ri, invs in by_req.items():
            view.take(ri, invs)
        out |= set(pick)
        # some land and book, some stay in flight, some fail into a gate
        booked = {e for e in out if rng.random() < 0.3}
        for ri, inv in booked:
            reqs[ri].ledger.status[inv] = DONE
        out -= booked
        exclude = {e for e in out if rng.random() < 0.5}
        gated = {e: 0.0 for e in out - exclude if rng.random() < 0.5}
        out = exclude | set(gated)
        for ri, req in enumerate(reqs):
            if req.ledger.complete:
                view.retire(ri)
        view.sync(exclude=exclude, gated=gated)
        for capacity in (1, 2, 3, 5, 8, 13, 32, 200):
            assert view.fill(capacity) == _ref_wave_fill(
                plan, exclude, gated, capacity)
        assert list(plan.pending_by_bucket(exclude, gated).items()) == \
            list(_ref_pending_by_bucket(plan, exclude, gated).items())


def _fill_args(backend, state):
    """The args of the next wave's ``planner.fill`` span."""
    from repro import obs
    obs.clear()
    with obs.recording():
        backend.step(state)
    fills = [s.args for s in obs.spans() if s.name == "planner.fill"]
    assert len(fills) == 1
    return fills[0]


def test_fill_scan_does_not_grow_with_completed_requests():
    """A wave drain that has completed 200 small requests reads exactly
    what a fresh drain with the same live requests reads on its next
    fill: the completed ones left the pending view."""
    data = DMLData.from_dict(make_plr_data(n_obs=40, dim_x=3, theta=0.5,
                                           seed=0))
    plan = DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0}, n_folds=2,
                             n_rep=1, seed=7)
    pool = PoolConfig(n_workers=2, memory_mb=512)
    old, fresh = WaveBackend(pool), WaveBackend(pool)
    state = old.begin_drain()
    for _ in range(200):
        old.admit(state, compile_request(plan, data))
    while old.step(state):
        pass
    assert all(r.ledger.complete for r in state.requests)
    assert state.plan.pending.live == 0
    new_state = fresh.begin_drain()
    for backend, st in ((old, state), (fresh, new_state)):
        for _ in range(3):
            backend.admit(st, compile_request(plan, data))
    a, b = _fill_args(old, state), _fill_args(fresh, new_state)
    assert a["live"] == b["live"] == 3
    assert a["scanned"] == b["scanned"] > 0
    assert a["entries"] == b["entries"] > 0
