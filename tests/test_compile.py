"""Megabatch compiler: bucket planning edge cases, padding parity, and the
warm spec-keyed program cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compile import plan_buckets, run_bucket
from repro.core import DMLData, DMLPlan, DMLSession, TaskGrid, estimate
from repro.core.crossfit import PaddingStats, pow2_bucket
from repro.core.session import compile_raw_request, compile_request
from repro.data import make_irm_data, make_plr_data
from repro.learners import get_batched_learner, get_learner
from repro.serverless import InlineBackend, PoolConfig, WaveBackend


def _plr(n_obs, seed, *, n_folds=3, n_rep=2, learner="ridge", **kw):
    data = DMLData.from_dict(make_plr_data(n_obs=n_obs, dim_x=5, theta=0.5,
                                           seed=seed))
    plan = DMLPlan.for_model("plr", learner=learner,
                             learner_params=kw.pop("learner_params",
                                                   {"reg": 1.0}),
                             n_folds=n_folds, n_rep=n_rep, seed=seed + 100,
                             **kw)
    return plan, data


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------
def test_pow2_bucket_rule():
    assert pow2_bucket(1) == 8           # floor
    assert pow2_bucket(8) == 8
    assert pow2_bucket(9) == 16
    assert pow2_bucket(100) == 128
    assert pow2_bucket(128) == 128


def test_mixed_n_requests_share_one_bucket():
    """Requests with different N in the same sublane-aligned bucket share
    a program; mixed learner families (IRM ridge + logistic) split
    buckets.  (N buckets are aligned to the 8-row sublane quantum since
    ISSUE 5 — the pow2 rule left N as the dominant waste axis.)"""
    reqs = [compile_request(*_plr(n, seed=i))
            for i, n in enumerate((97, 100, 104))]
    plan = plan_buckets(reqs)
    assert len(plan.buckets) == 1                      # all align to N=104
    key = plan.buckets[0]
    assert key.n_pad == 104 and key.p_pad == 8
    assert plan.page(0, key).shape == (104, 8)

    irm_data = DMLData.from_dict(make_irm_data(n_obs=100, dim_x=4, theta=0.4,
                                               seed=5))
    irm_plan = DMLPlan.for_model("irm", learner="ridge",
                                 learner_params={"reg": 1.0}, n_folds=3,
                                 n_rep=2, seed=9)
    plan2 = plan_buckets(reqs + [compile_request(irm_plan, irm_data)])
    # ridge buckets fuse across PLR+IRM (both N=104); logistic is its own
    assert len(plan2.buckets) == 2


def test_pending_by_bucket_skips_done_rows():
    req = compile_request(*_plr(100, seed=0))
    InlineBackend().run_requests([req])
    assert req.ledger.complete
    plan = plan_buckets([req])
    assert plan.pending_by_bucket() == {}


def test_opaque_callable_buckets_use_exact_shapes():
    grid = TaskGrid(2, 3, 2)
    n, p = 101, 5
    rng = np.random.default_rng(0)
    from repro.core.crossfit import draw_fold_masks
    masks = draw_fold_masks(n, 3, 2, 0)
    train_w = np.repeat((~masks).astype(np.float32)[:, :, None], 2, axis=2)
    req = compile_raw_request(
        grid, "n_rep", rng.normal(size=(n, p)).astype(np.float32),
        rng.normal(size=(2, n)).astype(np.float32), train_w,
        get_learner("ridge", {"reg": 1.0}), jax.random.key(0))
    plan = plan_buckets([req])
    key = plan.buckets[0]
    assert (key.n_pad, key.p_pad) == (n, p)            # no padding proof


def test_single_task_buckets_execute():
    """Per-fold scaling with n_rep=1: every invocation is a single task;
    buckets of size 1 still pad, compile, and round-trip correctly."""
    plan, data = _plr(60, seed=3, n_rep=1, scaling="n_folds*n_rep")
    req = compile_request(plan, data)
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    from repro.compile import ProgramCache
    cache = ProgramCache()
    results, _ = run_bucket(bplan, cache, bkey, [(0, 0)])
    assert results[(0, 0)].shape == (1, data.n_obs)
    ref = estimate(plan, data, backend="inline")
    wav = estimate(plan, data, backend="wave")
    np.testing.assert_allclose(ref.theta, wav.theta, rtol=0, atol=1e-7)


def test_ragged_folds_parity():
    """K does not divide N: fold sizes differ by one; bucketed execution
    must agree with the inline reference exactly."""
    plan, data = _plr(101, seed=4, n_folds=3)
    req_i = compile_request(plan, data)
    InlineBackend().run_requests([req_i])
    req_w = compile_request(plan, data)
    WaveBackend(PoolConfig(n_workers=2, memory_mb=256)).run_requests([req_w])
    np.testing.assert_allclose(req_w.gathered_preds(),
                               req_i.gathered_preds(), rtol=1e-6, atol=1e-6)


def test_irm_subset_masks_shrink_effective_n():
    """IRM's d0/d1 nuisances train on strict subsets; the padded-masked
    bucket fits must agree with the inline reference."""
    data = DMLData.from_dict(make_irm_data(n_obs=150, dim_x=4, theta=0.4,
                                           seed=6))
    plan = DMLPlan.for_model("irm", learner="ridge", n_folds=3, n_rep=2,
                             seed=11)
    req = compile_request(plan, data)
    # subset weights really shrink the training rows
    w_all = req.train_w[0, 0, 2]                     # ml_m: subset "all"
    w_d1 = req.train_w[0, 0, 1]                      # ml_g1: subset d1
    assert w_d1.sum() < w_all.sum()
    req_i = compile_request(plan, data)
    InlineBackend().run_requests([req_i])
    req_w = compile_request(plan, data)
    WaveBackend(PoolConfig(n_workers=3, memory_mb=256)).run_requests([req_w])
    np.testing.assert_allclose(req_w.gathered_preds(),
                               req_i.gathered_preds(), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# padded-masked fit parity, every learner family
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name,params,tol", [
    ("ridge", {"reg": 1.0}, 1e-5),
    ("ols", {}, 1e-5),
    ("lasso", {"reg": 0.01}, 1e-4),
    ("logistic", {"reg": 1.0}, 1e-5),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 32, "gamma": 0.2}, 1e-5),
    ("mlp", {"hidden": (8,), "n_steps": 30}, 1e-4),
])
def test_padded_masked_fit_matches_unpadded(name, params, tol):
    """The compiler's contract: padding rows (valid=0, w=0) and padded
    feature lanes never move a fit.  Exact parity (to float reduction
    order) on every learner family, key-consuming ones included."""
    rng = np.random.default_rng(0)
    B, N, P = 6, 100, 5
    xs = rng.normal(size=(B, N, P)).astype(np.float32)
    y = rng.normal(size=(B, N)).astype(np.float32)
    w = (rng.random((B, N)) > 0.3).astype(np.float32)
    if name == "logistic":
        y = (y > 0).astype(np.float32)
    valid = np.ones((B, N), np.float32)
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.key(7), i))(
        jnp.arange(B))
    fn = get_batched_learner(name, params)

    def pad(a, n_extra, p_extra=0):
        if a.ndim == 3:
            return np.pad(a, ((0, 0), (0, n_extra), (0, p_extra)))
        return np.pad(a, ((0, 0), (0, n_extra)))

    p_extra = 0 if name == "mlp" else 3      # mlp buckets at exact P
    out = np.asarray(fn(jnp.asarray(xs), jnp.asarray(y), jnp.asarray(w),
                        jnp.asarray(valid), keys))
    outp = np.asarray(fn(jnp.asarray(pad(xs, 28, p_extra)),
                         jnp.asarray(pad(y, 28)), jnp.asarray(pad(w, 28)),
                         jnp.asarray(pad(valid, 28)), keys))
    np.testing.assert_allclose(outp[:, :N], out, rtol=tol, atol=tol)
    assert float(np.abs(outp[:, N:]).max()) == 0.0   # masked tail exact 0


# ---------------------------------------------------------------------------
# warm program cache + padding accounting
# ---------------------------------------------------------------------------
def test_program_cache_hits_on_repeat_traffic():
    """Repeat traffic through a session re-uses compiled programs: the
    second run() of same-bucket requests traces nothing new."""
    sess = DMLSession(backend="wave", pool=PoolConfig(n_workers=8))
    sess.submit(*_plr(98, seed=1))
    sess.submit(*_plr(100, seed=2))
    sess.run()
    stats = sess.backend.compiler.stats
    misses_first = stats.misses
    assert misses_first >= 1
    assert sess.last_run_info.buckets == 1           # N=98/100 align to 104
    sess.submit(*_plr(99, seed=3))                   # new N, same bucket
    sess.submit(*_plr(104, seed=4))                  # aligns to 104 too
    sess.run()
    assert stats.misses == misses_first              # zero new traces
    assert stats.hits > 0
    assert 0.0 < stats.hit_rate <= 1.0


def test_padding_stats_accounting():
    s = PaddingStats(true_cells=80, padded_cells=100, tasks=8,
                     padded_tasks=16)
    assert s.waste_frac == pytest.approx(0.2)
    merged = s.merge(PaddingStats(20, 100, 2, 4))
    assert merged.true_cells == 100 and merged.padded_cells == 200
    assert PaddingStats().waste_frac == 0.0


def test_padding_stats_per_axis_breakdown():
    """B/N/P waste decompose independently: padded lanes, padded rows
    inside real lanes, padded feature columns inside real lanes."""
    s = PaddingStats(true_cells=600, padded_cells=2048, tasks=8,
                     padded_tasks=16, lane_cells=8 * 128,
                     true_feats=8 * 5, padded_feats=8 * 8)
    assert s.b_waste_frac == pytest.approx(0.5)
    assert s.n_waste_frac == pytest.approx(1 - 600 / 1024)
    assert s.p_waste_frac == pytest.approx(1 - 5 / 8)
    assert PaddingStats().n_waste_frac == 0.0
    assert PaddingStats().p_waste_frac == 0.0


def test_small_bucket_launches_at_aligned_tail_size():
    """The ISSUE 4 padding fix: a bucket with fewer tasks than B_BLOCK
    launches at its sublane-aligned size instead of padding to the full
    block (the regression that put asyncdrain B-waste at ~65%)."""
    from repro.compile import ProgramCache
    plan, data = _plr(100, seed=6)                 # 4 inv x 3 tasks = 12
    req = compile_request(plan, data)
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    cache = ProgramCache()
    entries = [(0, int(i)) for i in req.ledger.pending()]
    run_bucket(bplan, cache, bkey, entries)
    pad = cache.stats.padding
    assert pad.tasks == 12
    assert pad.padded_tasks == 16                  # aligned, not 32
    assert pad.b_waste_frac <= 0.25


@pytest.mark.parametrize("name,params", [
    ("ridge", {"reg": 1.0}),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 16}),
    ("mlp", {"hidden": (8,), "n_steps": 10}),
])
def test_tail_launch_b_invariance(name, params):
    """Canonical launch blocks make chunking invisible: executing a
    bucket whole, one invocation at a time, or in ragged slices yields
    bitwise-identical predictions, because every task always launches
    at its canonical block's compiled B (missing lanes ride as padding
    and lane contents don't couple)."""
    from repro.compile import ProgramCache
    plan, data = _plr(100, seed=7, learner=name, learner_params=params,
                      n_rep=4)                     # 8 inv x 3 = 24 tasks
    req = compile_request(plan, data)
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    entries = [(0, int(i)) for i in req.ledger.pending()]

    whole, _ = run_bucket(bplan, ProgramCache(), bkey, entries)
    one_at_a_time = {}
    for e in entries:                   # out-of-order, one invocation each
        res, _ = run_bucket(bplan, ProgramCache(), bkey, [e])
        one_at_a_time.update(res)
    ragged = {}
    for sl in (entries[:3], entries[3:4], entries[4:]):
        res, _ = run_bucket(bplan, ProgramCache(), bkey, sl)
        ragged.update(res)
    for e in entries:
        np.testing.assert_array_equal(whole[e], one_at_a_time[e])
        np.testing.assert_array_equal(whole[e], ragged[e])


# ---------------------------------------------------------------------------
# same-shape block fusion (ISSUE 5): bitwise parity, every learner family
# ---------------------------------------------------------------------------
FUSION_FAMILIES = [
    ("ridge", {"reg": 1.0}),
    ("ols", {}),
    ("lasso", {"reg": 0.01}),
    ("logistic", {"reg": 1.0}),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 16}),
    ("mlp", {"hidden": (8,), "n_steps": 10}),
]


@pytest.mark.parametrize("name,params", FUSION_FAMILIES)
def test_fused_multi_request_launch_bitwise_parity(name, params):
    """The fusion invariance contract: packing equal-canonical-B blocks
    of DIFFERENT requests into one launch (leading block axis, shared
    union page stack) yields bitwise the predictions each request gets
    from its own single-block launch — for every learner family."""
    from repro.compile import ProgramCache
    cases = [_plr(97 + i, seed=10 + i, learner=name, learner_params=params)
             for i in range(3)]                    # all align to N=104
    reqs = [compile_request(p, d) for p, d in cases]

    # solo single-block launches, one fresh cache per request
    solo = {}
    for ri, req in enumerate(reqs):
        bplan = plan_buckets([req])
        (bkey,) = bplan.buckets
        res, _ = run_bucket(bplan, ProgramCache(), bkey,
                            [(0, int(i)) for i in req.ledger.pending()],
                            fuse=False)
        solo[ri] = res

    # one fused multi-request drain
    reqs2 = [compile_request(p, d) for p, d in cases]
    bplan = plan_buckets(reqs2)
    (bkey,) = bplan.buckets                        # one shared bucket
    cache = ProgramCache()
    entries = [(ri, int(i)) for ri, req in enumerate(reqs2)
               for i in req.ledger.pending()]
    fused, _ = run_bucket(bplan, cache, bkey, entries, fuse=True)
    assert cache.stats.fused_launches >= 1
    assert cache.stats.launches < cache.stats.blocks   # really packed
    for ri in range(len(reqs2)):
        for inv in solo[ri]:
            np.testing.assert_array_equal(fused[(ri, inv[1])],
                                          solo[ri][inv])


def test_fusion_off_matches_fused_and_launch_counts():
    """coalesce=False + fuse=False is the canonical baseline (one launch
    per canonical block); fusion and cross-shape coalescing each
    strictly reduce the launch count with bitwise-identical results."""
    from repro.compile import ProgramCache
    reqs = [compile_request(*_plr(100 + i, seed=i)) for i in range(3)]
    bplan = plan_buckets(reqs)
    (bkey,) = bplan.buckets
    entries = [(ri, int(i)) for ri, req in enumerate(reqs)
               for i in req.ledger.pending()]
    cache_f, cache_u = ProgramCache(), ProgramCache()
    cache_b = ProgramCache()
    res_f, _ = run_bucket(bplan, cache_f, bkey, entries, fuse=True)
    res_u, _ = run_bucket(bplan, cache_u, bkey, entries, fuse=False)
    res_b, _ = run_bucket(bplan, cache_b, bkey, entries, fuse=False,
                          coalesce=False)
    # canonical baseline: one launch per canonical block, none coalesced
    assert cache_b.stats.launches == cache_b.stats.blocks
    assert cache_b.stats.coalesced_blocks == 0
    # coalescing packs tail blocks even unfused; fusion cuts further
    assert cache_u.stats.launches < cache_b.stats.launches
    assert cache_f.stats.launches < cache_u.stats.launches
    for e in entries:
        np.testing.assert_array_equal(res_f[e], res_u[e])
        np.testing.assert_array_equal(res_f[e], res_b[e])


@pytest.mark.parametrize("name,params", FUSION_FAMILIES)
def test_morphed_tail_launch_bitwise_parity(name, params):
    """The cross-shape coalescing contract (ISSUE 7): padding a tail
    block up to a neighbor's canonical B and fusing across the formerly
    different shapes yields BITWISE the per-block results — for every
    family in MORPH_BITWISE_FAMILIES (all six; zero-padded lanes are
    proven not to perturb real lanes on this platform).  Three 6-entry
    requests force the interesting shape mix: two tails pack to a
    16-lane launch block, the third rides an 8-lane block that must be
    MORPHED up to 16 before the shapes can fuse."""
    from repro.compile import ProgramCache
    from repro.compile.program import MORPH_BITWISE_FAMILIES, bucket_family
    cases = [_plr(97 + i, seed=20 + i, learner=name, learner_params=params)
             for i in range(3)]                     # 6 entries/request
    reqs = [compile_request(p, d) for p, d in cases]
    bplan = plan_buckets(reqs)
    (bkey,) = bplan.buckets
    assert bucket_family(bkey) in MORPH_BITWISE_FAMILIES
    entries = [(ri, int(i)) for ri, req in enumerate(reqs)
               for i in req.ledger.pending()]

    cache_m = ProgramCache()
    res_m, _ = run_bucket(bplan, cache_m, bkey, entries,
                          fuse=True, coalesce=True)
    # the morph really happened: tails were packed into shared launches
    assert cache_m.stats.coalesced_blocks >= 2
    assert cache_m.stats.launches < cache_m.stats.blocks

    reqs_b = [compile_request(p, d) for p, d in cases]
    bplan_b = plan_buckets(reqs_b)
    (bkey_b,) = bplan_b.buckets
    res_b, _ = run_bucket(bplan_b, ProgramCache(), bkey_b, entries,
                          fuse=False, coalesce=False)
    for e in entries:
        np.testing.assert_array_equal(res_m[e], res_b[e])


def test_morph_tolerance_gate():
    """A family outside MORPH_BITWISE_FAMILIES only morphs under an
    explicit opt-in tolerance (PoolConfig.morph_tolerance > 0); the
    default 0.0 keeps it on canonical shapes."""
    from repro.compile.program import (MORPH_TOLERANCE_FAMILIES,
                                       morph_allowed)
    from repro.compile.buckets import BucketKey
    # every current family is bitwise-proven, so synthesize the key of a
    # hypothetical tolerance-tier family to pin the gate's behavior
    key = BucketKey(learner=("hypothetical", ()), n_pad=8, p_pad=8)
    assert "hypothetical" not in MORPH_TOLERANCE_FAMILIES
    assert not morph_allowed(key, 0.0)
    assert not morph_allowed(key, 1e-6)    # not registered: never morphs
    ridge = BucketKey(learner=("ridge", (("reg", 1.0),)), n_pad=8, p_pad=8)
    assert morph_allowed(ridge, 0.0)       # bitwise tier needs no opt-in


@pytest.mark.parametrize("name,params", FUSION_FAMILIES)
def test_sharded_fused_launch_bitwise_parity(name, params):
    """The ISSUE 8 sharded-fusion contract (the B_BLOCK caveat in
    compile/program.py points here): a partitioned cache with a fused
    partition hook launches shard_map(lax.map body) and reproduces the
    unsharded fused launch — BITWISE on a 1-device mesh, and to the
    established sharded float tier (1e-6, same as the unfused sharded
    path) on an m-way mesh, where each shard compiles the body at B/m
    lanes and XLA may retile small-B reductions.  The multihost-smoke
    job runs this 8-way where the shard really splits."""
    from repro.compile import ProgramCache
    from repro.launch.mesh import make_host_mesh
    from repro.serverless.backends import make_sharded_compiler
    cases = [_plr(97 + i, seed=30 + i, learner=name, learner_params=params)
             for i in range(2)]                    # all align to N=104
    reqs = [compile_request(p, d) for p, d in cases]
    bplan = plan_buckets(reqs)
    (bkey,) = bplan.buckets
    entries = [(ri, int(i)) for ri, req in enumerate(reqs)
               for i in req.ledger.pending()]

    base, _ = run_bucket(bplan, ProgramCache(), bkey, entries, fuse=True)

    mesh = make_host_mesh()
    sharded = make_sharded_compiler(mesh)
    assert sharded.partition_fused is not None
    res, _ = run_bucket(bplan, sharded, bkey, entries, fuse=True,
                        b_align=mesh.shape["data"])
    assert sharded.stats.fused_launches >= 1       # really took the path
    for e in entries:
        if mesh.shape["data"] == 1:
            np.testing.assert_array_equal(res[e], base[e])
        else:
            np.testing.assert_allclose(res[e], base[e], rtol=1e-6,
                                       atol=2e-6)


# ---------------------------------------------------------------------------
# per-bucket parallelization-axis planner (ISSUE 8)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kind,flops", [("TPU v5 lite", 197e12),
                                        ("cpu", 197e12), ("TPU v4", None)])
def test_device_peaks_table(kind, flops):
    """Pricing reads one peak row per device kind; a kind with no row
    is an error, never a default."""
    from repro.launch import roofline
    if flops is None:
        with pytest.raises(ValueError, match="no peak row"):
            roofline.device_peaks(kind)
    else:
        assert roofline.device_peaks(kind).flops == flops
    # this process prices with its own device's row
    assert roofline.device_peaks() is roofline.DEVICE_PEAKS[
        jax.devices()[0].device_kind]


def test_axis_planner_pinned_decisions(monkeypatch):
    """The roofline planner's choices on the canonical shapes, pinned so
    a pricing-model edit that flips a layout is a visible diff:

      * tall-N Gram bucket (N_pad exceeds one device page): only the
        data-parallel blocked-Gram layout is executable — data@8;
      * wide-P lasso (huge P, many sweeps, one task): the column split
        amortizes its all-gather — feature@8;
      * many small tasks: per-task work is below the shard tax —
        task@1 (classic serverless task parallelism);
      * compute-heavy mlp bucket (non-Gram): only the task axis exists,
        and the per-task work amortizes the multi-shard launch —
        task@8.

    Pins price against the analytic SHARD_OVERHEAD_FRAC: an earlier
    test constructing a DMLSession memoizes a *measured* fraction
    (honest at runtime, unpinnable under CI load), so it is cleared
    here — the absolute choices, not the argmin invariant, are what
    this test owns."""
    from repro.compile.buckets import BucketKey, plan_bucket_axis
    from repro.launch import roofline
    monkeypatch.setattr(roofline, "_MEASURED_SHARD_OVERHEAD_FRAC", None)

    def decide(learner, ptuple, n_pad, p_pad, b):
        key = BucketKey(learner=(learner, ptuple), n_pad=n_pad, p_pad=p_pad)
        return plan_bucket_axis(key, n_tasks=b, n_devices=8)

    tall = decide("ridge", (("reg", 1.0),), 1 << 17, 8, 4)
    assert (tall.axis, tall.shards) == ("data", 8)
    # the task candidates really were inexecutable, not merely pricier
    assert all(not ok for ax, _, _, ok in tall.candidate_costs
               if ax == "task")

    wide = decide("lasso", (("reg", 0.01), ("n_iter", 500)), 4096, 16384, 1)
    assert (wide.axis, wide.shards) == ("feature", 8)

    small = decide("ols", (), 256, 16, 64)
    assert (small.axis, small.shards) == ("task", 1)

    mlp = decide("mlp", (("hidden", (32,)), ("n_steps", 300)), 2048, 32, 32)
    assert (mlp.axis, mlp.shards) == ("task", 8)


def test_axis_planner_never_picks_strictly_worse():
    """By construction the decision is the argmin over executable
    candidates — sweep a shape grid and verify no executable candidate
    is priced strictly cheaper than the chosen one."""
    from repro.compile.buckets import BucketKey, plan_bucket_axis
    shapes = [("ridge", (("reg", 1.0),)), ("ols", ()),
              ("lasso", (("reg", 0.01), ("n_iter", 200))),
              ("logistic", (("reg", 1.0), ("n_iter", 100))),
              ("mlp", (("hidden", (8,)), ("n_steps", 100)))]
    for learner, ptuple in shapes:
        for n_pad in (256, 4096, 1 << 17):
            for b in (1, 16, 64):
                key = BucketKey((learner, ptuple), n_pad, 32)
                d = plan_bucket_axis(key, n_tasks=b, n_devices=8)
                best = d.est_s
                for ax, sh, est, ok in d.candidate_costs:
                    if ok:
                        assert est >= best or (ax, sh) == (d.axis, d.shards)


def test_axis_planner_opaque_and_nongram_fallbacks():
    """Opaque buckets get no decision (they always run task-parallel
    unsharded); a tall-N non-Gram family has NO executable candidate and
    falls back to the task axis rather than crashing."""
    from repro.compile.buckets import BucketKey, plan_bucket_axis
    assert plan_bucket_axis(BucketKey(("opaque", 123), 256, 8),
                            n_tasks=4, n_devices=8) is None
    tallmlp = plan_bucket_axis(
        BucketKey(("mlp", (("hidden", (8,)), ("n_steps", 100))),
                  1 << 17, 8), n_tasks=4, n_devices=8)
    assert tallmlp.axis == "task"
    assert all(not ok for _, _, _, ok in tallmlp.candidate_costs)


def test_sharded_backend_logs_axis_plans():
    """The drain engine prices each spec-identified bucket once per mesh
    and logs the decision on BackendRunInfo.axis_plans, autoscale-style."""
    from repro.serverless.backends import ShardedBackend
    plan, data = _plr(100, seed=40)
    req = compile_request(plan, data)
    info = ShardedBackend(PoolConfig(n_workers=3, memory_mb=512)) \
        .run_requests([req])
    assert len(info.axis_plans) >= 1
    d = info.axis_plans[0]
    assert d.axis in ("task", "data", "feature")
    assert d.priced_by == "roofline"
    assert d.candidate_costs                     # full table logged
    # serving-size ridge buckets stay classic task-parallel
    assert d.axis == "task"


def test_out_of_order_harvest_parity():
    """Non-blocking dispatch: buckets harvested in reverse dispatch
    order return exactly what the synchronous path returns."""
    from repro.compile import ProgramCache, dispatch_bucket
    reqs = [compile_request(*_plr(100, seed=0)),
            compile_request(*_plr(300, seed=1))]   # two distinct buckets
    bplan = plan_buckets(reqs)
    groups = bplan.pending_by_bucket()
    assert len(groups) == 2
    cache = ProgramCache()
    dispatched = [dispatch_bucket(bplan, cache, key, ents)
                  for key, ents in groups.items()]
    harvested = {}
    for bd in reversed(dispatched):                # out-of-order harvest
        harvested.update(bd.harvest())
    cache2 = ProgramCache()
    expected = {}
    for key, ents in groups.items():
        res, _ = run_bucket(bplan, cache2, key, ents)
        expected.update(res)
    assert set(harvested) == set(expected)
    for e, v in expected.items():
        np.testing.assert_array_equal(harvested[e], v)


def test_block_tensor_cache_keys_on_full_data_content():
    """Two datasets sharing one X but different y must never share
    cached block tensors (work_key is the FULL content identity, not
    just the feature-page fingerprint) — regression test for the
    stale-prediction bug a fingerprint-only key produces."""
    plan, data1 = _plr(100, seed=21)
    data2 = DMLData(x=np.array(data1.x), y=np.array(data1.y) + 1.0,
                    d=np.array(data1.d))
    assert data1.fingerprint() == data2.fingerprint()     # same X page
    assert data1.content_key() != data2.content_key()     # different y
    backend = InlineBackend()
    r1 = compile_request(plan, data1)
    backend.run_requests([r1])
    r2 = compile_request(plan, data2)
    backend.run_requests([r2])
    assert not np.array_equal(r1.gathered_preds(), r2.gathered_preds())
    # and a solo fresh-backend run of data2 agrees bitwise
    ref = compile_request(plan, data2)
    InlineBackend().run_requests([ref])
    np.testing.assert_array_equal(r2.gathered_preds(),
                                  ref.gathered_preds())


def test_n_buckets_sublane_aligned():
    """The ISSUE 5 N rule: buckets align N to the 8-row sublane quantum
    (mirroring the B tail rule) instead of pow2 — 100 pads to 104, not
    128 — and the pow2 comparator is tracked in the padding stats."""
    from repro.compile import ProgramCache
    req = compile_request(*_plr(100, seed=3))
    bplan = plan_buckets([req])
    (bkey,) = bplan.buckets
    assert bkey.n_pad == 104
    cache = ProgramCache()
    run_bucket(bplan, cache, bkey,
               [(0, int(i)) for i in req.ledger.pending()])
    pad = cache.stats.padding
    assert pad.n_waste_frac < pad.n_waste_frac_pow2
    assert pad.lane_cells_pow2 == pad.tasks * 128


def test_scaling_levels_share_launch_shapes():
    """Canonical blocks are built over flat task ids, which both scaling
    levels share — so per-split and per-fold runs compile the same B and
    agree bitwise even when the segment spans multiple blocks."""
    from repro.core import estimate
    plan_a, data = _plr(90, seed=11, n_rep=6)      # 36 tasks: 32 + tail 4
    plan_b = DMLPlan.for_model("plr", learner="ridge",
                               learner_params={"reg": 1.0}, n_folds=3,
                               n_rep=6, seed=111, scaling="n_folds*n_rep")
    ra = estimate(plan_a, data, backend="inline")
    rb = estimate(plan_b, data, backend="inline")
    np.testing.assert_array_equal(ra.thetas, rb.thetas)


def test_multi_request_checkpoints_do_not_clobber(tmp_path):
    """Batched inline/sharded drains write one checkpoint per request
    (same .r{i} layout as the wave backend), never one shared file."""
    import os
    path = os.path.join(tmp_path, "ck")
    from repro.serverless import TaskLedger
    reqs = [compile_request(*_plr(n, seed=i)) for i, n in enumerate((90, 70))]
    InlineBackend(PoolConfig(checkpoint_path=path)).run_requests(reqs)
    for i, req in enumerate(reqs):
        led = TaskLedger.load(f"{path}.r{i}")
        assert led.complete and led.n_obs == req.ledger.n_obs
    # single request: bare path, as before
    req = compile_request(*_plr(80, seed=9))
    InlineBackend(PoolConfig(checkpoint_path=path)).run_requests([req])
    assert TaskLedger.load(path).n_obs == 80


def test_backend_info_reports_compile_stats():
    req = compile_request(*_plr(100, seed=8))
    backend = InlineBackend()
    info = backend.run_requests([req])
    assert info.compile is not None
    assert info.compile.launches >= 1
    assert info.compile.padding.padded_tasks >= info.compile.padding.tasks
    assert 0.0 <= info.compile.padding.waste_frac < 1.0
