"""Topology-aware drain: the placement of each request's share of a
bucket follows page residency, one bucket of fresh-data requests
spreads over every host, idle hosts steal work, per-mesh streams step
round-robin from the session's event loop, the autoscaler prices each
host's waves with roofline FLOP estimates, and the whole thing is
bitwise-identical to the single-host inline drain for every learner
family.

CI additionally runs this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the
multihost-smoke job), where each simulated host's page pool pins pages
to a distinct device; on a single-device run the hosts share the device
but keep disjoint pools, so every assertion below still holds."""
import importlib.util
from collections import Counter
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.compile import PagePool, plan_buckets
from repro.core import DMLData, DMLPlan, DMLSession
from repro.core.session import compile_request
from repro.data import make_irm_data, make_plr_data
from repro.serverless import (
    InlineBackend, PoolConfig, Topology, TopologyBackend,
)
from repro.sharding.policy import place_unit, steal_choice

# the chip benchmark's plain float64 reference, independent of repro
_REF_PATH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" \
    / "references" / "plr.py"


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


def _family_cases():
    cases = [_plr(100 + 7 * i, seed=i, learner=name, learner_params=params)
             for i, (name, params) in enumerate(FAMILIES)]
    cases.append((DMLPlan.for_model("irm", learner="ridge", n_folds=3,
                                    n_rep=2, seed=77),
                  DMLData.from_dict(make_irm_data(n_obs=130, dim_x=4,
                                                  theta=0.4, seed=9))))
    return cases


# ---------------------------------------------------------------------------
# bitwise parity vs the single-host inline path
# ---------------------------------------------------------------------------
def test_topology_bitwise_parity_all_families():
    """Every learner family (logistic rides along via IRM) drained over
    two host streams — with placement, stealing, and per-host
    autoscaling live — matches a solo single-host inline drain bitwise."""
    cases = _family_cases()
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_workers=2, memory_mb=256,
                                      autoscale=True, n_hosts=2))
    rids = [sess.submit(plan, data) for plan, data in cases]
    sess.run()
    t = sess.topology_info
    assert t is not None and t.n_hosts == 2
    assert sum(h.waves for h in t.hosts) == sess.last_run_info.waves
    assert all(h.waves > 0 for h in t.hosts)       # both streams really ran
    for rid, (plan, data) in zip(rids, cases):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(
            sess.request(rid).gathered_preds(), ref.gathered_preds())


# ---------------------------------------------------------------------------
# placement follows residency
# ---------------------------------------------------------------------------
def test_routing_follows_page_residency():
    """Round 1 seeds residency (cold placement balances load); every
    later round routes each bucket back to the host holding its pages:
    steady-state hit rate 1.0, zero h2d bytes, zero cross-host fetches."""
    cases = [_plr(100 + i, seed=i) for i in range(2)] + \
            [_plr(300, seed=5), _plr(310, seed=6)]   # two N-buckets
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_hosts=2, n_workers=8))
    for plan, data in cases:
        sess.submit(plan, data)
    sess.run()                                      # warmup: cold placement
    cold = {(key, ri): host
            for key, ri, host, _ in sess.topology_info.placements}
    topo = sess.backend.topology
    warm0 = topo.page_stats().snapshot()
    fetches0 = topo.directory.fetches
    for _ in range(3):                              # steady state
        for plan, data in cases:
            sess.submit(plan, data)
        sess.run()
        warm = {(key, ri): host for key, ri, host, _
                in sess.topology_info.placements}
        assert warm == cold                         # residency-stable routes
    d = topo.page_stats().delta(warm0)
    assert d.bytes_h2d == 0 and d.misses == 0
    assert d.hit_rate == 1.0
    assert topo.directory.fetches == fetches0      # no cross-host traffic
    # warm placements scored resident (>0), cold ones didn't
    assert all(s > 0 for *_, s in sess.topology_info.placements)


def test_place_bucket_scoring_and_determinism():
    """Unit-level policy: stack-cached beats pages-resident beats cold;
    ties break to the least-loaded host, then the lowest id."""
    class FakePool:
        def __init__(self, pages=(), stacks=()):
            self._p, self._s = set(pages), set(stacks)

        def resident(self, pk):
            return pk in self._p

        def stack_cached(self, pkeys):
            return tuple(pkeys) in self._s

    pk = ("fp", 128, 8)
    cold = FakePool()
    resident = FakePool(pages=[pk])
    stacked = FakePool(pages=[pk], stacks=[(pk,)])
    p = place_unit(pk, [cold, resident, stacked], loads=[0, 0, 0])
    assert p.host == 2 and p.score == 2.0
    p = place_unit(pk, [cold, resident], loads=[0, 100])
    assert p.host == 1 and p.score == 1.0   # residency outweighs load
    p = place_unit(pk, [cold, cold], loads=[5, 3])
    assert p.host == 1 and p.score == 0.0   # cold tie -> least loaded
    p = place_unit(pk, [cold, cold], loads=[3, 3])
    assert p.host == 0                  # full tie -> lowest id


def test_steal_choice_picks_least_local_from_most_loaded():
    class FakePool:
        def __init__(self, pages=()):
            self._p = set(pages)

        def resident(self, pk):
            return pk in self._p

        def stack_cached(self, pkeys):
            return False

    pools = [FakePool(pages=["a"]), FakePool(), FakePool()]
    one = lambda u: 1
    queues = {0: ["ka", "kb", "kc"]}
    pick = steal_choice(queues, pools,
                        lambda u: "a" if u == "ka" else u, one)
    # kb/kc cold on the donor go first, until the thief holds half
    assert pick == (0, ["kb", "kc"])
    # never the donor's last unit, however large
    assert steal_choice({0: ["ka"]}, pools, lambda u: u,
                        lambda u: 9) is None
    assert steal_choice({}, pools, lambda u: u, one) is None
    # the donor is the host with the most queued invocations, not units
    sizes = {"a1": 1, "a2": 1, "a3": 1, "b1": 4, "b2": 4}
    pick = steal_choice({1: ["a1", "a2", "a3"], 2: ["b1", "b2"]}, pools,
                        lambda u: u, sizes.__getitem__)
    assert pick == (2, ["b1"])


# ---------------------------------------------------------------------------
# work stealing
# ---------------------------------------------------------------------------
def _same_data_cases():
    """Multiple learner families over ONE dataset: distinct buckets that
    all share one feature page."""
    data = DMLData.from_dict(make_plr_data(n_obs=100, dim_x=5, theta=0.5,
                                           seed=3))
    return [(DMLPlan.for_model("plr", learner=name, learner_params=params,
                               n_folds=3, n_rep=2, seed=50 + i), data)
            for i, (name, params) in enumerate(FAMILIES)]


def _seed_host0_residency(backend, cases):
    """Pre-warm host 0's pool with every page the cases need, so
    residency scoring routes ALL their buckets to host 0 and leaves
    host 1 idle — the stealing scenario."""
    pool = backend.topology.hosts[0].pool
    for plan, data in cases:
        req = compile_request(plan, data)
        for key in plan_buckets([req]).buckets:
            pool._page(PagePool.page_key(req, key.n_pad, key.p_pad),
                       req, key.n_pad, key.p_pad)


def test_work_stealing_triggers_on_idle_host():
    cases = _same_data_cases()
    backend = TopologyBackend(PoolConfig(n_hosts=2, n_workers=1,
                                         memory_mb=256))
    _seed_host0_residency(backend, cases)
    reqs = [compile_request(p, d) for p, d in cases]
    info = backend.run_requests(reqs)
    t = info.topology
    # every bucket was *placed* on the resident host...
    assert all(host == 0 for _, _, host, _ in t.placements)
    # ...so the idle second host stole some of the queue
    assert t.steals >= 1
    assert t.hosts[1].steals >= 1 and t.hosts[1].waves >= 1
    # the stolen bucket's page arrived device-to-device, not via host
    topo = backend.topology
    assert topo.directory.fetches >= 1
    assert topo.page_stats().cross_host_fetches >= 1
    # and stealing never moved an estimate
    for req, (plan, data) in zip(reqs, cases):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(req.gathered_preds(),
                                      ref.gathered_preds())


def test_fusion_under_placement_and_stealing():
    """Same-shape block fusion stays bitwise-correct when the fused
    bucket is placed by residency, stolen by an idle host, and harvested
    out of order across host streams: four same-bucket ridge requests
    fuse into multi-block launches wherever they land."""
    cases = [_plr(100 + i, seed=40 + i) for i in range(4)]  # one bucket
    # capacity 8 = half the bucket: a wave spans 2+ requests (so their
    # equal-shape blocks fuse) while the rest stays stealable
    backend = TopologyBackend(PoolConfig(n_hosts=2, n_workers=2,
                                         memory_mb=1024))
    _seed_host0_residency(backend, cases)
    reqs = [compile_request(p, d) for p, d in cases]
    info = backend.run_requests(reqs)
    assert backend.compiler.stats.fused_launches >= 1
    assert info.dispatch is not None
    assert info.dispatch.harvested == info.dispatch.dispatched
    for req, (plan, data) in zip(reqs, cases):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(req.gathered_preds(),
                                      ref.gathered_preds())


def test_steal_disabled_keeps_buckets_on_resident_host():
    cases = _same_data_cases()
    backend = TopologyBackend(PoolConfig(n_hosts=2, n_workers=1,
                                         memory_mb=256, steal=False))
    _seed_host0_residency(backend, cases)
    reqs = [compile_request(p, d) for p, d in cases]
    info = backend.run_requests(reqs)               # pileup, no stealing
    assert info.topology.steals == 0
    assert backend.topology.directory.fetches == 0
    busy = [h for h in info.topology.hosts if h.waves > 0]
    assert [h.host_id for h in busy] == [0]         # the other stayed idle


# ---------------------------------------------------------------------------
# per-mesh streams from the session event loop
# ---------------------------------------------------------------------------
def test_poll_steps_host_streams_round_robin():
    """poll() advances one host stream per call; ledgers complete out of
    order across hosts; completion set matches a blocking run()."""
    # four distinct N-buckets so cold placement spreads over both hosts
    cases = [_plr(n, seed=i, n_rep=2)
             for i, n in enumerate((100, 300, 600, 1200))]
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_hosts=2, n_workers=1,
                                      memory_mb=256))
    rids = [sess.submit(p, d) for p, d in cases]
    done = []
    for _ in range(200):
        done += sess.poll()
        if len(done) == len(rids):
            break
    assert sorted(done) == sorted(rids)
    t = sess.topology_info
    assert all(h.waves > 0 for h in t.hosts)


def test_worker_schedule_honored_per_host_stream():
    """The legacy static ramp sizes each host stream's waves by that
    host's own wave count (parity with the wave backend's contract), and
    the estimate is untouched."""
    backend = TopologyBackend(PoolConfig(n_hosts=2, memory_mb=256,
                                         worker_schedule=[1, 2, 8, 8]))
    plan, data = _plr(100, seed=31, n_rep=4)
    req = compile_request(plan, data)
    info = backend.run_requests([req])
    assert req.ledger.complete
    busy = [h for h in info.topology.hosts if h.waves > 0]
    assert busy and busy[0].waves >= 2          # the ramp really waved
    ref = compile_request(plan, data)
    InlineBackend().run_requests([ref])
    np.testing.assert_array_equal(req.gathered_preds(),
                                  ref.gathered_preds())


def test_topology_from_pod_mesh():
    """A multi-pod production-style mesh splits into one host stream per
    pod, each pinned to its own device set."""
    if jax.device_count() < 8:
        pytest.skip("needs the forced 8-device host platform")
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    topo = Topology.from_mesh(mesh)
    assert len(topo) == 2
    assert topo.hosts[0].n_devices == 4
    devs0 = {d.id for d in np.asarray(topo.hosts[0].mesh.devices).flat}
    devs1 = {d.id for d in np.asarray(topo.hosts[1].mesh.devices).flat}
    assert devs0.isdisjoint(devs1)
    assert topo.hosts[0].device.id != topo.hosts[1].device.id

    backend = TopologyBackend(PoolConfig(n_workers=4), topology=topo)
    plan, data = _plr(100, seed=21)
    req = compile_request(plan, data)
    backend.run_requests([req])
    ref = compile_request(plan, data)
    InlineBackend().run_requests([ref])
    np.testing.assert_array_equal(req.gathered_preds(),
                                  ref.gathered_preds())


# ---------------------------------------------------------------------------
# roofline-priced autoscaling
# ---------------------------------------------------------------------------
def test_autoscaler_first_decision_roofline_priced():
    """Before any duration is observed, candidates are priced by the
    compiler's per-bucket FLOP estimates (not the unit-work model), the
    full candidate cost table is logged, and later waves switch to the
    measured EMA."""
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_hosts=2, n_workers=2,
                                      memory_mb=256, autoscale=True,
                                      max_workers=4))
    for i, n in enumerate((100, 300, 600)):        # distinct buckets
        sess.submit(*_plr(n, seed=i, n_rep=4))
    sess.run()
    decisions = sess.last_run_info.autoscale
    assert decisions
    assert decisions[0].priced_by == "roofline"
    assert len(decisions[0].candidate_costs) >= 2
    for w, time_s, gb_s, score in decisions[0].candidate_costs:
        assert w >= 1 and time_s > 0 and gb_s > 0 and score > 0
    assert any(d.priced_by == "ema" for d in decisions[1:])
    assert {d.host for d in decisions} == {0, 1}   # each mesh sized itself


def test_roofline_task_models_scale_sanely():
    from repro.launch.roofline import (
        invocation_roofline_s, megabatch_task_flops,
    )
    for fam, params in [("ridge", {}), ("lasso", {"n_iter": 50}),
                        ("logistic", {}), ("mlp", {"hidden": (8,)}),
                        ("kernel_ridge", {"n_landmarks": 16})]:
        small = megabatch_task_flops(fam, 128, 8, params)
        big = megabatch_task_flops(fam, 512, 8, params)
        assert 0 < small < big
    assert invocation_roofline_s("ridge", {}, 6, 128, 8) == \
        2 * invocation_roofline_s("ridge", {}, 3, 128, 8)


# ---------------------------------------------------------------------------
# one bucket of fresh-data requests over four hosts (the coverage study)
# ---------------------------------------------------------------------------
# θ and SE against the float64 reference, in units of SE_ref and
# relative: float32 Gram sums and solves at N=64 land within ~1e-6 of
# float64; the tolerance leaves room for BLAS summation order, while a
# wrong fold, a wrong nuisance or a dropped repetition moves θ by a
# sizeable fraction of its SE
REF_TOL = 1e-4


def _reference():
    spec = importlib.util.spec_from_file_location("plr_reference", _REF_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _study(n_requests=16, n_obs=64, dim_x=6, n_folds=5):
    """A coverage study's replications: one PLR ridge request per fresh
    dataset, all in one bucket (M=1 at the ``n_rep`` scaling level, as
    the plr_mc500 deployment runs them)."""
    cases = []
    for i in range(n_requests):
        data = DMLData.from_dict(make_plr_data(n_obs=n_obs, dim_x=dim_x,
                                               theta=0.5, seed=1000 + i))
        plan = DMLPlan.for_model("plr", learner="ridge",
                                 learner_params={"reg": 1.0},
                                 n_folds=n_folds, n_rep=1, seed=2000 + i,
                                 scaling="n_rep")
        cases.append((plan, data))
    return cases


def _busiest_share(spans) -> float:
    """Largest share of the dispatched invocations one host's
    ``topology.wave`` spans took."""
    per_host = Counter()
    for s in spans:
        if s.name == "topology.wave":
            per_host[s.args["host"]] += s.args["invocations"]
    return max(per_host.values()) / sum(per_host.values())


def _wave_results(cases):
    sess = DMLSession(backend="wave")
    rids = [sess.submit(p, d) for p, d in cases]
    sess.run()
    return [sess.result(r) for r in rids]


def test_one_bucket_spreads_over_four_hosts():
    """Sixteen fresh-data requests share one bucket; each is its own
    unit, so cold placement spreads them over all four hosts.  θ and SE
    match the float64 reference within REF_TOL and the wave backend
    bitwise."""
    cases = _study()
    sess = DMLSession(backend="topology", pool=PoolConfig(n_hosts=4))
    rids = [sess.submit(p, d) for p, d in cases]
    with obs.recording():
        obs.clear()
        sess.run()
        spans = obs.spans()
    t = sess.topology_info
    assert len(sess.last_run_info.axis_plans) == 1     # one bucket
    assert all(h.invocations > 0 for h in t.hosts)
    assert all(h.units_placed == 4 for h in t.hosts)
    assert _busiest_share(spans) <= 0.375
    assert max(h.invocations for h in t.hosts) \
        / sum(h.invocations for h in t.hosts) == _busiest_share(spans)
    routes = [s.args for s in spans if s.name == "topology.route"]
    assert sum(a["units"] for a in routes) == len(cases)
    assert sum(a["cold"] for a in routes) == len(cases)  # fresh data
    fetches = [s.args for s in spans if s.name == "pages.fetch"]
    assert [a["source"] for a in fetches] == ["h2d"] * len(cases)
    assert sum(a["bytes"] for a in fetches) \
        == sess.backend.pages.stats.bytes_h2d

    ref = _reference()
    wave = _wave_results(cases)
    for rid, (plan, data), w in zip(rids, cases, wave):
        res = sess.result(rid)
        assert res.theta == w.theta and res.se == w.se
        np.testing.assert_array_equal(np.asarray(res.thetas),
                                      np.asarray(w.thetas))
        masks = ref.fold_masks(data.n_obs, plan.resampling.n_folds, 1,
                               plan.resampling.seed)
        r = ref.reference(np.asarray(data.x), np.asarray(data.y),
                          np.asarray(data.d), masks, 1.0)
        assert abs(res.theta - r["theta"]) <= REF_TOL * r["se"]
        assert abs(res.se - r["se"]) <= REF_TOL * r["se"]


def test_steal_moves_requests_out_of_single_bucket_donor():
    """Every request of one bucket is resident on host 0, so all are
    placed there; the idle host 1 steals some of them — a single-bucket
    donor still gives up work — and their pages cross device-to-device.
    Stealing moves no estimate."""
    cases = _study(n_requests=8)
    backend = TopologyBackend(PoolConfig(n_hosts=2, n_workers=1,
                                         memory_mb=256))
    _seed_host0_residency(backend, cases)
    reqs = [compile_request(p, d) for p, d in cases]
    with obs.recording():
        obs.clear()
        info = backend.run_requests(reqs)
        spans = obs.spans()
    t = info.topology
    assert len({key for key, *_ in t.placements}) == 1
    assert all(host == 0 for _, _, host, _ in t.placements)
    assert t.steals >= 1 and t.hosts[1].steals == t.steals
    assert t.hosts[1].invocations > 0
    steals = [s.args for s in spans
              if s.name == "topology.steal" and s.args["donor"] >= 0]
    assert steals and all(a["donor"] == 0 and a["thief"] == 1
                          for a in steals)
    # host 1 placed nothing: it ran exactly what it stole
    assert sum(a["invocations"] for a in steals) == t.hosts[1].invocations
    d2d = [s.args for s in spans
           if s.name == "pages.fetch" and s.args["source"] == "d2d"]
    assert len(d2d) == backend.topology.directory.fetches >= 1
    assert sum(a["bytes"] for a in d2d) \
        == backend.pages.stats.bytes_d2d > 0
    for req, (plan, data) in zip(reqs, cases):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        np.testing.assert_array_equal(req.gathered_preds(),
                                      ref.gathered_preds())


def test_kill_host_replaces_its_units():
    """Losing host 0 with work in flight: its units leave the
    assignment, are placed again on the survivors, and every request of
    the bucket completes bitwise as on the wave backend."""
    cases = _study()
    sess = DMLSession(backend="topology",
                      pool=PoolConfig(n_hosts=4, n_workers=1,
                                      memory_mb=256))
    rids = [sess.submit(p, d) for p, d in cases]
    backend = sess.backend
    killed_at = None
    for _ in range(400):
        sess.poll()
        state = sess._state
        if state is None:
            break
        q = state.queues.get(0)
        if q is not None and q.in_flight > 0:
            assert backend.kill_host(state, 0) > 0
            assert 0 not in state.assignment.values()
            killed_at = len(state.info.topology.placements)
            break
    assert killed_at is not None, "host 0 never held work in flight"
    sess.run()
    t = sess.topology_info
    replaced = t.placements[killed_at:]
    assert replaced and all(host != 0 for _, _, host, _ in replaced)
    for rid, w in zip(rids, _wave_results(cases)):
        res = sess.result(rid)
        assert res.theta == w.theta and res.se == w.se


def test_topology_pages_stats_are_cluster_wide():
    """``backend.pages.stats`` on the topology backend is the sum over
    the host pools, as on a single-stream backend it is its one pool's,
    and the drain's ``last_run_info.pages`` agrees with it."""
    cases = _study(n_requests=8)
    sess = DMLSession(backend="topology", pool=PoolConfig(n_hosts=4))
    for plan, data in cases:
        sess.submit(plan, data)
    sess.run()
    stats = sess.backend.pages.stats
    per_host = [h.pool.stats for h in sess.backend.topology.hosts]
    assert stats.bytes_h2d == sum(s.bytes_h2d for s in per_host) > 0
    assert stats.bytes_h2d == 8 * 64 * 8 * 4      # one page a dataset
    assert sess.last_run_info.pages == stats
