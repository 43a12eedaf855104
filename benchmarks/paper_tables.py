"""Benchmarks mapped one-to-one to the paper's empirical artifacts.

  table1    — §5.2 Table 1: fit time / billed GB-s / per-invocation duration
              / response time (mean, min, max over repeats), 1024 MB,
              per-split scaling, bonus data, K=5 x M=100 x L=2.
  figure3   — §5.2 Fig. 3(a-d): time & cost vs memory x scaling level.
  fusion    — DESIGN.md §2: fused task-batch vs sequential per-invocation
              loop (the TPU-native replacement for FaaS concurrency).
  kernelcmp — crossfit_gram Pallas (interpret) vs jnp oracle agreement +
              oracle timing (the real-time path on CPU).
  session   — multi-request DMLSession (shared waves) vs sequential
              one-shot estimation on the same pool.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np


def table1(n_rep: int = 100, repeats: int = 5, memory_mb: int = 1024) -> Dict:
    from repro.core import DMLData, DMLPlan, estimate
    from repro.configs.dml_plr_bonus import PAPER_TABLE1, USD_PER_GB_S
    from repro.data import make_bonus_data
    from repro.serverless import PoolConfig

    data = DMLData.from_dict(make_bonus_data())
    fit, billed, per_inv, resp = [], [], [], []
    for r in range(repeats):
        plan = DMLPlan.for_model(
            "plr", n_folds=5, n_rep=n_rep, learner="ridge",
            learner_params={"reg": 1.0}, scaling="n_rep", seed=42 + r,
            pool=PoolConfig(n_workers=8, memory_mb=memory_mb))
        res = estimate(plan, data)
        s = res.report.summary()
        fit.append(s["fit_time_s"])
        billed.append(s["billed_gb_s"])
        per_inv.append(s["avg_duration_s"])
        resp.append(s["response_time_s"])

    def stats(v):
        return {"mean": float(np.mean(v)), "min": float(np.min(v)),
                "max": float(np.max(v))}

    out = {
        "fit_time_s": stats(fit),
        "billed_gb_s": stats(billed),
        "avg_duration_per_invocation_s": stats(per_inv),
        "total_response_time_s": stats(resp),
        "usd": stats([b * USD_PER_GB_S for b in billed]),
        "paper_reference": PAPER_TABLE1,
        "n_invocations": 2 * n_rep,
    }
    return out


def figure3(n_rep: int = 20, repeats: int = 3) -> List[Dict]:
    """Delegates to the example's sweep (one source of truth for the
    Fig. 3 grid); benchmarks run from the repo root, so ``examples`` is
    importable as a namespace package."""
    from examples.serverless_scaling import run_sweep
    rows = run_sweep(n_rep=n_rep, repeats=repeats, simulate=True)
    return [{"scaling": s, "memory_mb": m, "time_s": t, "gb_s": c}
            for s, m, t, c in rows]


def session_throughput(n_requests: int = 4, n_rep: int = 10) -> Dict:
    """Batched multi-request serving vs sequential one-shot estimation:
    wall time and wave counts for the same request set on one wave pool."""
    from repro.core import DMLData, DMLPlan, DMLSession, estimate
    from repro.data import make_plr_data
    from repro.serverless import PoolConfig

    pool = PoolConfig(n_workers=4, memory_mb=1024)
    reqs = [(DMLPlan.for_model("plr", n_folds=5, n_rep=n_rep,
                               learner="ridge", learner_params={"reg": 1.0},
                               seed=100 + i, pool=pool),
             DMLData.from_dict(make_plr_data(n_obs=500, dim_x=10,
                                             theta=0.5, seed=i)))
            for i in range(n_requests)]

    def run_batched():
        sess = DMLSession(backend="wave", pool=pool)
        for plan, data in reqs:
            sess.submit(plan, data)
        return sess.run(), sess.last_run_info

    def run_solo():
        return [estimate(plan, data) for plan, data in reqs]

    run_batched()                       # warm the jit caches for both paths
    run_solo()
    t0 = time.perf_counter()
    batched, info = run_batched()
    batched_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solo = run_solo()
    solo_s = time.perf_counter() - t0
    assert all(abs(b.theta - s.theta) < 1e-5
               for b, s in zip(batched, solo))
    return {"n_requests": n_requests, "batched_s": batched_s,
            "sequential_s": solo_s,
            "fused_waves": info.waves,
            "shared_waves": info.shared_waves,
            "sequential_waves": sum(r.report.waves for r in solo),
            "speedup": solo_s / batched_s}


def _pr1_per_segment_drain(reqs) -> None:
    """Replica of the PR-1 execution path: one fused jit call per
    (request, segment) at *exact* array shapes — so every distinct
    (tasks, N, P) combination retraces, which is precisely the cost the
    megabatch compiler removes.  Kept here (not in the library) as the
    "before" baseline for the session-throughput comparison."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.learners import get_learner

    for req in reqs:
        for seg in req.segments:
            inv = req.grid.segment_invocations(seg.l_ids, req.scaling)
            flat = np.concatenate([req.invocation_tasks(i) for i in inv])
            y, w = req.wave_arrays(flat)
            fn = get_learner(seg.learner, dict(seg.params))
            key = jax.random.key(seg.key_ref[1])  # ("seed", s)
            preds = fn(req.x, jnp.asarray(y), jnp.asarray(w), key)
            jax.block_until_ready(preds)


def megabatch_compile(n_requests: int = 32, n_rep: int = 2,
                      repeats: int = 3) -> Dict:
    """Megabatch compiler vs the PR-1 per-segment path on the
    session-throughput workload: many small concurrent PLR requests, every
    one with a *different* N (the serving reality), drained by one warm
    wave pool.  Both paths start from identical pre-compiled WorkRequests;
    only the drain is timed.

    before  — per-(request, segment) fused calls at exact shapes: every
              distinct N re-traces its own gram program and every request
              pays its own eager linear-algebra dispatch chain.
    after   — the wave backend over the megabatch compiler: all requests'
              tasks bucketed by (learner, N-bucket, P-bucket) and served
              by one cached program.

    Emits tasks/sec (cold = first drain incl. compiles, warm = steady
    state), waves, padding waste, and compile-cache hit rate — the
    numbers BENCH_megabatch.json tracks across PRs.
    """
    import time as _time

    from repro.core import DMLData, DMLPlan
    from repro.core.session import compile_request
    from repro.data import make_plr_data
    from repro.serverless import PoolConfig, WaveBackend

    pool = PoolConfig(n_workers=16, memory_mb=1024)
    sizes = [100 + i for i in range(n_requests)]       # all pad to N=128/256
    cases = [(DMLPlan.for_model("plr", n_folds=3, n_rep=n_rep,
                                learner="ridge", learner_params={"reg": 1.0},
                                seed=100 + i, pool=pool),
              DMLData.from_dict(make_plr_data(n_obs=n, dim_x=8, theta=0.5,
                                              seed=i)))
             for i, n in enumerate(sizes)]
    n_tasks = sum(p.resampling.n_rep * p.resampling.n_folds * p.n_nuisance
                  for p, _ in cases)

    def run_before():
        reqs = [compile_request(p, d) for p, d in cases]
        t0 = _time.perf_counter()
        _pr1_per_segment_drain(reqs)
        return _time.perf_counter() - t0

    def run_after(backend):
        reqs = [compile_request(p, d) for p, d in cases]
        t0 = _time.perf_counter()
        info = backend.run_requests(reqs)
        return _time.perf_counter() - t0, info

    # cold: fresh jit caches for both paths (first pass in this process),
    # then warm repeats — burst traffic sees cold, steady serving warm.
    # Both paths take min() over the SAME sample count (>= 6: a warm
    # drain is single-digit ms and the baseline ~100 ms, so the extra
    # samples are cheap) — equal counts keep the speedup_warm CI gate
    # stable against scheduler noise without biasing either side.
    warm_samples = max(repeats, 6)
    before_cold = run_before()
    before_warm = min(run_before() for _ in range(warm_samples))
    backend = WaveBackend(pool)
    after_cold, info = run_after(backend)
    after_warm, _ = min(
        (run_after(backend) for _ in range(warm_samples)),
        key=lambda t: t[0])
    stats = backend.compiler.stats
    return {
        "n_requests": n_requests,
        "n_tasks": n_tasks,
        "before_cold_s": before_cold,
        "before_warm_s": before_warm,
        "after_cold_s": after_cold,
        "after_warm_s": after_warm,
        "tasks_per_sec": n_tasks / after_cold,
        "tasks_per_sec_warm": n_tasks / after_warm,
        "baseline_tasks_per_sec": n_tasks / before_cold,
        "baseline_tasks_per_sec_warm": n_tasks / before_warm,
        "speedup_cold": before_cold / after_cold,
        "speedup_warm": before_warm / after_warm,
        "waves": info.waves,
        "buckets": info.buckets,
        "shared_waves": info.shared_waves,
        "padding_waste_pct": 100.0 * stats.padding.waste_frac,
        # per-axis breakdown: B lanes (canonical blocks vs the old pow2
        # rule), N rows inside real lanes (sublane-aligned vs pow2), P
        # feature columns
        "padding_waste_b_pct": 100.0 * stats.padding.b_waste_frac,
        "padding_waste_b_pow2_pct": 100.0 * stats.padding.b_waste_frac_pow2,
        "padding_waste_b_morphed_pct":
            100.0 * stats.padding.b_waste_frac_morphed,
        "padding_waste_n_pct": 100.0 * stats.padding.n_waste_frac,
        "padding_waste_n_pow2_pct": 100.0 * stats.padding.n_waste_frac_pow2,
        "padding_waste_p_pct": 100.0 * stats.padding.p_waste_frac,
        "compile_cache_hit_rate": stats.hit_rate,
        "programs_compiled": stats.misses,
        "launches": stats.launches,
        "blocks": stats.blocks,
        "fused_launches": stats.fused_launches,
    }


def fusion_block_launch(n_requests: int = 12, n_rep: int = 2,
                        warm_rounds: int = 5) -> Dict:
    """Block fusion + cross-shape coalescing + persistent compile cache
    + pipelined dispatch (ISSUE 5/7 -> BENCH_fusion.json): the megabatch
    serving workload drained on a fused/coalesced wave pool vs the
    canonical per-block baseline (fuse=False, coalesce=False).

    Each arm runs THREE temperatures:

      * ``cold_trace_s`` — a seeder backend traces + compiles everything
        from nothing, populating the persistent stores (the AOT program
        store for portable programs, JAX's XLA compilation cache for
        the rest);
      * ``cold_s`` — a FRESH backend with fresh in-memory caches drains
        the same workload against the seeded disk stores: the
        disk-warm cold start a recycled serverless container sees.
        This is the gated cold metric — fused must beat unfused here
        (fusion compiles bigger programs; the persistent cache is what
        pays that bill back);
      * ``warm_s`` — steady-state repeats on the warm backend.

    Also reports launches-per-drain (fused strictly lower), the morphed
    B-waste comparator, and the warm **overlap ratio** of the two-deep
    pipelined dispatch queue: host seconds booking/stacking while
    launches were in flight vs host seconds blocked on the device.
    """
    import dataclasses
    import os as _os
    import shutil as _shutil
    import tempfile as _tempfile
    import time as _time

    from repro.compile.persist import PersistentProgramCache
    from repro.core import DMLData, DMLPlan
    from repro.core.session import compile_request
    from repro.data import make_plr_data
    from repro.serverless import PoolConfig, WaveBackend

    pool = PoolConfig(n_workers=16, memory_mb=1024)
    cases = [(DMLPlan.for_model("plr", n_folds=3, n_rep=n_rep,
                                learner="ridge", learner_params={"reg": 1.0},
                                seed=100 + i, pool=pool),
              DMLData.from_dict(make_plr_data(n_obs=100 + i, dim_x=8,
                                              theta=0.5, seed=i)))
             for i in range(n_requests)]
    n_tasks = sum(p.resampling.n_rep * p.resampling.n_folds * p.n_nuisance
                  for p, _ in cases)

    def drain(backend):
        reqs = [compile_request(p, d) for p, d in cases]
        t0 = _time.perf_counter()
        info = backend.run_requests(reqs)
        return _time.perf_counter() - t0, info

    out = {"n_requests": n_requests, "n_tasks": n_tasks,
           "warm_rounds": warm_rounds}
    cache_root = _tempfile.mkdtemp(prefix="bench_progcache_")
    try:
        arms = (("fused", dict(fuse=True, coalesce=True)),
                ("unfused", dict(fuse=False, coalesce=False)))
        for label, knobs in arms:
            arm_dir = _os.path.join(cache_root, label)
            # seeder: trace-cold, fills the persistent stores
            seeder = WaveBackend(dataclasses.replace(pool, **knobs))
            seeder.compiler.persist = PersistentProgramCache(arm_dir)
            cold_trace_s, _ = drain(seeder)
            # disk-cold: fresh backend, fresh in-memory caches — every
            # program must come off the seeded disk stores.  Single-shot
            # cold drains are noisy on a loaded host (one slow LAPACK
            # re-compile skews the whole drain), so take the best of
            # three fresh backends, each seeing the same seeded stores
            cold_s = 1e9
            for _ in range(3):
                backend = WaveBackend(dataclasses.replace(pool, **knobs))
                backend.compiler.persist = PersistentProgramCache(arm_dir)
                s, _ = drain(backend)
                cold_s = min(cold_s, s)
            misses_cold = backend.compiler.stats.misses
            launches0 = backend.compiler.stats.launches
            warm_s, last_info = 1e9, None
            for _ in range(warm_rounds):
                s, info = drain(backend)
                if s < warm_s:
                    warm_s, last_info = s, info
            stats = backend.compiler.stats
            out[f"cold_trace_s_{label}"] = cold_trace_s
            out[f"cold_s_{label}"] = cold_s
            out[f"warm_s_{label}"] = warm_s
            out[f"tasks_per_sec_cold_trace_{label}"] = n_tasks / cold_trace_s
            out[f"tasks_per_sec_cold_{label}"] = n_tasks / cold_s
            out[f"tasks_per_sec_warm_{label}"] = n_tasks / warm_s
            out[f"launches_per_drain_{label}"] = \
                (stats.launches - launches0) / warm_rounds
            out[f"blocks_per_drain_{label}"] = \
                stats.blocks / (warm_rounds + 1)
            out[f"programs_compiled_disk_cold_{label}"] = misses_cold
            if label == "fused":
                out["fused_launches_total"] = stats.fused_launches
                out["coalesced_blocks_total"] = stats.coalesced_blocks
                out["padding_waste_b_pct"] = \
                    100.0 * stats.padding.b_waste_frac
                out["padding_waste_b_morphed_pct"] = \
                    100.0 * stats.padding.b_waste_frac_morphed
                out["disk_hits_cold"] = stats.disk_hits
                out["persist"] = backend.compiler.persist.summary()
                d = last_info.dispatch
                out["overlap_ratio_warm"] = d.overlap_ratio
                out["host_overlap_s_warm"] = d.host_overlap_s
                out["harvest_wait_s_warm"] = d.wait_s
    finally:
        _shutil.rmtree(cache_root, ignore_errors=True)
    out["warm_speedup_fused_vs_unfused"] = \
        out["warm_s_unfused"] / out["warm_s_fused"]
    out["cold_speedup_fused_vs_unfused"] = \
        out["cold_s_unfused"] / out["cold_s_fused"]
    return out


SERVING_FAMILIES = [
    ("ridge", {"reg": 1.0}),
    ("ols", {}),
    ("lasso", {"reg": 0.01}),
    ("kernel_ridge", {"reg": 1.0, "n_landmarks": 32}),
    ("mlp", {"hidden": (8,), "n_steps": 20}),
]


def _serving_cases(n_requests_per_family: int, n_rep: int, *,
                   n_obs_stride: int = 11):
    """The steady-serving request population the asyncdrain/topology
    benches share: every learner family (+ IRM for logistic), one
    (label, plan, data) triple per request.  Labels are unique per
    request — the parity dict must never let a passing replica mask a
    failing one.  Same-family replicas share their family's N (distinct
    seeds keep the datasets and feature pages distinct), so they land in
    one aligned-N bucket and their tail blocks can coalesce into shared
    launches — the cross-shape morphing path the asyncdrain smoke gate
    measures (an old per-replica N offset silently split every replica
    into its own bucket and kept morphing permanently idle).  Returns
    (cases, tasks per round)."""
    from repro.core import DMLData, DMLPlan
    from repro.data import make_irm_data, make_plr_data

    cases = []
    for i, (name, params) in enumerate(SERVING_FAMILIES):
        for j in range(n_requests_per_family):
            data = DMLData.from_dict(make_plr_data(
                n_obs=100 + n_obs_stride * i, dim_x=6, theta=0.5,
                seed=10 * i + j))
            plan = DMLPlan.for_model(
                "plr", learner=name, learner_params=params, n_folds=3,
                n_rep=n_rep, seed=100 + 10 * i + j)
            label = name if n_requests_per_family == 1 else f"{name}.{j}"
            cases.append((label, plan, data))
    cases.append(("irm_logistic",
                  DMLPlan.for_model("irm", learner="ridge", n_folds=3,
                                    n_rep=n_rep, seed=999),
                  DMLData.from_dict(make_irm_data(n_obs=140, dim_x=5,
                                                  theta=0.4, seed=99))))
    n_tasks_round = sum(p.resampling.n_rep * p.resampling.n_folds
                        * p.n_nuisance for _, p, _ in cases)
    return cases, n_tasks_round


def async_drain(n_requests_per_family: int = 1, n_rep: int = 2,
                rounds: int = 3) -> Dict:
    """The continuous-admission drain engine on steady-state serving
    traffic: every learner family concurrently, the same datasets
    re-estimated round after round through ONE warm session (the
    serving-loop reality the device-resident page pool exists for).

    round 0 (warmup)  — cold compiles + page transfers.
    rounds 1..R       — steady state: the page pool must serve every
                        feature page from device residency (hit rate 1.0,
                        zero host->device bytes) while the occupancy
                        autoscaler sizes the waves.

    Also proves the determinism contract end-to-end: each request's final
    prediction tensor is compared bitwise against a synchronous
    ``InlineBackend`` drain of the same request, per learner family.
    """
    import numpy as np

    from repro.core import DMLSession
    from repro.core.session import compile_request
    from repro.serverless import InlineBackend, PoolConfig

    cases, n_tasks_round = _serving_cases(n_requests_per_family, n_rep)

    pool = PoolConfig(n_workers=8, memory_mb=1024, autoscale=True,
                      min_workers=1, max_workers=32)
    sess = DMLSession(backend="wave", pool=pool)

    def one_round():
        rids = [sess.submit(p, d) for _, p, d in cases]
        sess.run()
        return rids

    one_round()                                     # warmup (cold)
    pages0 = sess.backend.pages.stats.snapshot()
    compile0 = sess.backend.compiler.stats.misses
    t0 = time.perf_counter()
    for _ in range(rounds):
        rids = one_round()
    steady_s = time.perf_counter() - t0
    pages = sess.backend.pages.stats.delta(pages0)
    padding = sess.backend.compiler.stats.padding

    # bitwise parity vs the synchronous inline path, per family
    parity = {}
    for (label, plan, data), rid in zip(cases, rids):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        parity[label] = bool(np.array_equal(
            sess.request(rid).gathered_preds(), ref.gathered_preds()))

    decisions = sess.last_run_info.autoscale
    return {
        "n_requests": len(cases),
        "rounds": rounds,
        "n_tasks_per_round": n_tasks_round,
        "steady_s": steady_s,
        "steady_tasks_per_sec": n_tasks_round * rounds / steady_s,
        "page_pool_hit_rate": pages.hit_rate,
        "page_bytes_h2d_steady": pages.bytes_h2d,
        "transfer_bytes_saved": pages.bytes_saved,
        "page_evictions": pages.evictions,
        "stack_hits": pages.stack_hits,
        "programs_compiled_steady": sess.backend.compiler.stats.misses
                                    - compile0,
        "padding_waste_pct": 100.0 * padding.waste_frac,
        "padding_waste_b_pct": 100.0 * padding.b_waste_frac,
        "padding_waste_b_pow2_pct": 100.0 * padding.b_waste_frac_pow2,
        "padding_waste_b_morphed_pct": 100.0 * padding.b_waste_frac_morphed,
        "padding_waste_n_pct": 100.0 * padding.n_waste_frac,
        "padding_waste_n_pow2_pct": 100.0 * padding.n_waste_frac_pow2,
        "padding_waste_p_pct": 100.0 * padding.p_waste_frac,
        "autoscale_workers_min": min(d.n_workers for d in decisions)
                                 if decisions else None,
        "autoscale_workers_max": max(d.n_workers for d in decisions)
                                 if decisions else None,
        "bitwise_parity": parity,
        "bitwise_parity_all": all(parity.values()),
    }


def topology_drain(n_hosts: int = 2, n_requests_per_family: int = 1,
                   n_rep: int = 2, rounds: int = 3) -> Dict:
    """The topology backend on steady-state serving traffic: every
    learner family over ``n_hosts`` simulated host meshes through ONE
    warm session, re-estimated round after round (ISSUE 4 acceptance
    bench -> BENCH_topology.json).

    round 0 (warmup)  — cold placement seeds per-host page residency.
    rounds 1..R       — steady state: placement must route every bucket
                        back to its resident host (per-host hit rate
                        >= 0.9, ZERO cross-host page transfers), while
                        each mesh's autoscaler lane sizes its own waves
                        with roofline-priced candidates.

    Bitwise parity vs a single-host InlineBackend drain is checked per
    learner family — placement/stealing must never move an estimate.
    """
    import numpy as np

    from repro.core import DMLSession
    from repro.core.session import compile_request
    from repro.serverless import InlineBackend, PoolConfig

    # wide N stride: requests land in distinct pow2 N-buckets so
    # placement has several buckets to spread over the hosts
    cases, n_tasks_round = _serving_cases(n_requests_per_family, n_rep,
                                          n_obs_stride=110)

    pool = PoolConfig(n_workers=8, memory_mb=1024, autoscale=True,
                      min_workers=1, max_workers=8, n_hosts=n_hosts)
    sess = DMLSession(backend="topology", pool=pool)

    def one_round():
        rids = [sess.submit(p, d) for _, p, d in cases]
        sess.run()
        return rids

    one_round()                                     # warmup (cold)
    first_decisions = list(sess.last_run_info.autoscale)
    topo = sess.backend.topology
    host_warm0 = [h.pool.stats.snapshot() for h in topo.hosts]
    fetches0 = topo.directory.fetches
    t0 = time.perf_counter()
    for _ in range(rounds):
        rids = one_round()
    steady_s = time.perf_counter() - t0

    host_stats = []
    for h, warm0 in zip(topo.hosts, host_warm0):
        d = h.pool.stats.delta(warm0)
        host_stats.append({
            "host_id": h.host_id,
            "n_devices": h.n_devices,
            "page_hit_rate": d.hit_rate,
            "page_hits": d.hits, "page_misses": d.misses,
            "bytes_h2d": d.bytes_h2d, "bytes_d2d": d.bytes_d2d,
            "served_traffic": (d.hits + d.misses) > 0,
        })
    busy = [h for h in host_stats if h["served_traffic"]]

    # bitwise parity vs the single-host synchronous inline path
    parity = {}
    for (label, plan, data), rid in zip(cases, rids):
        ref = compile_request(plan, data)
        InlineBackend().run_requests([ref])
        parity[label] = bool(np.array_equal(
            sess.request(rid).gathered_preds(), ref.gathered_preds()))

    info = sess.last_run_info
    decisions = info.autoscale
    t = info.topology
    return {
        "n_hosts": n_hosts,
        "n_requests": len(cases),
        "rounds": rounds,
        "n_tasks_per_round": n_tasks_round,
        "steady_s": steady_s,
        "steady_tasks_per_sec": n_tasks_round * rounds / steady_s,
        "hosts": host_stats,
        "min_busy_host_hit_rate": min(h["page_hit_rate"] for h in busy)
                                  if busy else 0.0,
        "cross_host_fetches_steady": topo.directory.fetches - fetches0,
        "cross_host_fetches_total": topo.directory.fetches,
        "cross_host_bytes_total": topo.directory.bytes_fetched,
        "steals_last_drain": t.steals,
        "steals_per_host": {h.host_id: h.steals for h in t.hosts},
        "waves_per_host": {h.host_id: h.waves for h in t.hosts},
        "placements_last_drain": len(t.placements),
        "resident_placements_last_drain":
            sum(1 for *_, s in t.placements if s > 0),
        "autoscale_decisions": len(decisions),
        "autoscale_priced_by": sorted({d.priced_by for d in decisions}),
        "autoscale_hosts": sorted({d.host for d in decisions}),
        # the cold drain's first decision: roofline-priced candidates
        # (n_workers, est_time_s, est_gb_s, score) before any EMA exists
        "autoscale_first_drain_priced_by":
            sorted({d.priced_by for d in first_decisions}),
        "autoscale_roofline_candidates":
            [list(c) for c in first_decisions[0].candidate_costs]
            if first_decisions else [],
        "bitwise_parity": parity,
        "bitwise_parity_all": all(parity.values()),
    }


def chaos_drain(n_requests_per_family: int = 1, n_rep: int = 2,
                rounds: int = 2, fault_rates=(0.1, 0.3)) -> Dict:
    """Fault-tolerance bench (ISSUE 10 -> BENCH_chaos.json): the
    chaos-hardened fast path priced against its own fault-free baseline.

      goodput    — steady tasks/sec at each injected fault rate vs the
                   fault-free drain on the same pool shape (failures
                   re-enter the pending view and retry, so the ratio is
                   the price of re-execution, not of a slow path).
      hedge      — a straggler-heavy drain with deadlines armed: how
                   often the hedged duplicate races past the held
                   original (hit rate), and the wall-clock written off
                   as hedge_waste_s (the loser's span — never billed).
      host_loss  — kill one of two topology hosts mid-flight: wall
                   clock from the kill to every admitted ledger
                   complete (recovery latency), orphaned buckets
                   re-dispatched on the survivor.

    All sections run warm (a full warmup drain precedes every timing)
    and every section re-checks bitwise parity vs the inline path —
    chaos changes the schedule, never the estimate.  The smoke gates:
    goodput >= 0.7x fault-free at the 10% fault rate, and ZERO lost
    invocations anywhere (every admitted ledger completes).
    """
    import numpy as np

    from repro.core import DMLSession
    from repro.core.session import compile_request
    from repro.serverless import InlineBackend, PoolConfig, make_backend

    cases, n_tasks_round = _serving_cases(n_requests_per_family, n_rep)

    def parity_vs_inline(get_req):
        parity = {}
        for label, plan, data in cases:
            ref = compile_request(plan, data)
            InlineBackend().run_requests([ref])
            parity[label] = bool(np.array_equal(
                get_req(label).gathered_preds(), ref.gathered_preds()))
        return parity

    def warm_session(pool):
        sess = DMLSession(backend="wave", pool=pool)

        def one_round():
            rids = [sess.submit(p, d) for _, p, d in cases]
            sess.run()
            return rids

        one_round()                         # warmup: compiles + pages
        return sess, one_round

    # ---- goodput vs fault rate -------------------------------------
    # the baseline and every fault rate run INTERLEAVED, round by
    # round, and each mode is scored by its fastest round — the two
    # drains are ~30-90ms each, so un-interleaved block timing would
    # measure machine-load drift, not the retry path's cost
    base_sess, base_round = warm_session(
        PoolConfig(n_workers=8, memory_mb=1024))
    faulty = [(rate, *warm_session(
        PoolConfig(n_workers=8, memory_mb=1024, failure_rate=rate,
                   max_retries=10, seed=0))) for rate in fault_rates]
    base_ts, fault_ts, fault_rids = [], {r: [] for r in fault_rates}, {}
    for _ in range(rounds):
        t0 = time.perf_counter()
        base_round()
        base_ts.append(time.perf_counter() - t0)
        for rate, sess, one_round in faulty:
            t0 = time.perf_counter()
            fault_rids[rate] = one_round()
            fault_ts[rate].append(time.perf_counter() - t0)
    baseline_tps = n_tasks_round / min(base_ts)

    goodput = {}
    zero_lost = True
    for rate, sess, _ in faulty:
        by_label = {label: sess.request(rid)
                    for (label, _, _), rid in zip(cases, fault_rids[rate])}
        parity = parity_vs_inline(by_label.__getitem__)
        d = sess.last_run_info.dispatch
        complete = all(r.ledger.complete for r in by_label.values())
        zero_lost &= complete and d.lost == 0
        tps = n_tasks_round / min(fault_ts[rate])
        goodput[str(rate)] = {
            "tasks_per_sec": tps,
            "goodput_ratio": tps / baseline_tps,
            "failures_last_round": sum(r.report.failures
                                       for r in by_label.values()),
            "lost": d.lost,
            "all_ledgers_complete": complete,
            "bitwise_parity_all": all(parity.values()),
        }

    # ---- hedge race under held stragglers --------------------------
    # hold >> hedge deadline + bucket wall: the duplicate must have
    # room to finish while the straggling original is still held, or
    # the race degenerates to the original always winning
    sess, one_round = warm_session(
        PoolConfig(n_workers=8, memory_mb=1024, straggler_rate=0.5,
                   straggler_hold_s=0.12, hedge_after_s=0.005,
                   max_retries=10, seed=0))
    rids = one_round()
    by_label = {label: sess.request(rid)
                for (label, _, _), rid in zip(cases, rids)}
    parity = parity_vs_inline(by_label.__getitem__)
    d = sess.last_run_info.dispatch
    complete = all(r.ledger.complete for r in by_label.values())
    zero_lost &= complete and d.lost == 0
    hedge = {
        "hedges": d.hedges,
        "hedge_wins": d.hedge_wins,
        "hedge_hit_rate": d.hedge_wins / d.hedges if d.hedges else None,
        "cancelled": d.cancelled,
        "hedge_waste_s": d.hedge_waste_s,
        "all_ledgers_complete": complete,
        "bitwise_parity_all": all(parity.values()),
    }

    # ---- host-loss recovery ----------------------------------------
    pool = PoolConfig(n_workers=4, memory_mb=1024, n_hosts=2)
    backend = make_backend("topology", pool)
    backend.run_requests([compile_request(p, d) for _, p, d in cases])
    reqs = {label: compile_request(p, d) for label, p, d in cases}
    state = backend.begin_drain()
    for r in reqs.values():
        backend.admit(state, r)
    t_kill = None
    orphans = 0
    for _ in range(5000):
        if t_kill is None:
            q = state.queues.get(0)
            if q is not None and q.in_flight > 0:
                t_kill = time.perf_counter()
                orphans = backend.kill_host(state, 0)
                continue
        if not backend.step(state):
            break
    recovery_s = time.perf_counter() - t_kill if t_kill else None
    complete = all(r.ledger.complete for r in reqs.values())
    zero_lost &= complete
    parity = parity_vs_inline(reqs.__getitem__)
    info = state.info.topology
    host_loss = {
        "killed_host": 0 if t_kill else None,
        "recovery_latency_s": recovery_s,
        "orphaned_buckets": orphans,
        "lost_buckets": info.lost_buckets,
        "host_losses": info.host_losses,
        "all_ledgers_complete": complete,
        "bitwise_parity_all": all(parity.values()),
    }

    return {
        "n_requests": len(cases),
        "rounds": rounds,
        "n_tasks_per_round": n_tasks_round,
        "baseline_tasks_per_sec": baseline_tps,
        "fault_rates": list(fault_rates),
        "goodput": goodput,
        "hedge": hedge,
        "host_loss": host_loss,
        "zero_lost_invocations": zero_lost,
        "bitwise_parity_all":
            all(g["bitwise_parity_all"] for g in goodput.values())
            and hedge["bitwise_parity_all"]
            and host_loss["bitwise_parity_all"],
    }


def fusion_speedup(n_tasks: int = 64) -> Dict:
    """Fused batched cross-fit vs per-task loop (same math)."""
    import jax
    import jax.numpy as jnp
    from repro.learners import get_learner
    from repro.data import make_bonus_data

    data = make_bonus_data()
    x = jnp.asarray(data["x"])
    n = x.shape[0]
    rng = np.random.default_rng(0)
    w = jnp.asarray((rng.random((n_tasks, n)) > 0.2).astype(np.float32))
    y = jnp.asarray(np.tile(data["y"], (n_tasks, 1)))
    fn = get_learner("ridge", {"reg": 1.0})
    key = jax.random.key(0)

    fused = jax.jit(lambda: fn(x, y, w, key))
    jax.block_until_ready(fused())
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(fused())
    fused_s = (time.perf_counter() - t0) / 3

    single = jax.jit(lambda yt, wt: fn(x, yt[None], wt[None], key))
    jax.block_until_ready(single(y[0], w[0]))
    t0 = time.perf_counter()
    for t in range(n_tasks):
        jax.block_until_ready(single(y[t], w[t]))
    loop_s = time.perf_counter() - t0

    return {"n_tasks": n_tasks, "fused_s": fused_s, "loop_s": loop_s,
            "speedup": loop_s / fused_s}


def kernel_compare() -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.crossfit_gram import crossfit_gram_pallas

    k = jax.random.key(0)
    x = jax.random.normal(k, (5120, 18), jnp.float32)
    w = (jax.random.uniform(jax.random.fold_in(k, 1), (64, 5120)) > 0.2) \
        .astype(jnp.float32)
    y = jax.random.normal(jax.random.fold_in(k, 2), (64, 5120), jnp.float32)
    g_p, b_p = crossfit_gram_pallas(
        jnp.pad(x, ((0, 0), (0, 110))), w, y, block_t=8, block_n=512,
        interpret=True)
    g_r, b_r = ref.crossfit_gram_ref(x, w, y)
    err = float(jnp.max(jnp.abs(g_p[:, :18, :18] - g_r)))

    fn = jax.jit(lambda: ref.crossfit_gram_ref(x, w, y))
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(10):
        jax.block_until_ready(fn())
    oracle_us = (time.perf_counter() - t0) / 10 * 1e6
    return {"max_abs_err": err, "oracle_us_per_call": oracle_us,
            "tasks": 64, "n_obs": 5120}


def axis_planner(fast: bool = True, repeats: int = 3) -> Dict:
    """ISSUE 8 per-bucket parallelization-axis planner bench
    (-> ``BENCH_axisplan.json``).

    Measures the three layouts the planner prices against each other and
    checks its two invariants:

      * tall-N tasks/s — whole-N task-parallel Gram vs the streaming
        blocked path (``chunk_tall_n`` + ``batched_gram_blocked``) vs
        the in-mesh data-parallel executor;
      * wide-P tasks/s — whole Gram vs the feature-parallel column
        executor;
      * decision mix — ``plan_bucket_axis`` over the canonical shape
        grid on the canonical 8-device mesh (pure pricing, no devices
        needed), counted per chosen axis;
      * ``planner_never_worse`` — nowhere on the grid is an executable
        candidate priced strictly cheaper than the chosen one (the CI
        gate; holds by construction, so a False means the argmin broke);
      * sharded-fused warm speedup — the real ``run_bucket`` fused
        launch on a ridge bucket, unsharded cache vs
        ``make_sharded_compiler(mesh)``, plus a measured
        parallel-headroom probe (m sequential matmuls vs one shard_map
        over the mesh).  The probe calibrates ``speedup_floor``
        (ISSUE 9): the CI gate demands parity-or-better where the host
        really has spare cores and decays to a catastrophic-overhead
        sanity floor on saturated or 1-device runners — a 1-vCPU
        runner cannot win by sharding;
      * end-to-end tall-N drain (ISSUE 9) — a ridge bucket made tall
        relative to an overridden page ceiling drains twice through
        ``ShardedBackend``: once executing the planner's chunk-paged
        data layout, once with the axis mesh withheld (HEAD's
        price-then-ignore behavior).  Reports tasks/s for both, the
        decision->executed mix from ``BackendRunInfo.axis_plans``, and
        feeds the planner-executed-never-strictly-worse CI gate.
    """
    import os

    import jax
    import jax.numpy as jnp
    from repro.compile import ProgramCache, plan_buckets, run_bucket
    from repro.compile.buckets import BucketKey, plan_bucket_axis
    from repro.core import DMLData, DMLPlan
    from repro.core.session import compile_request
    from repro.data import make_plr_data
    from repro.kernels import ops
    from repro.launch.mesh import make_host_mesh
    from repro.serverless.backends import make_sharded_compiler
    from repro.sharding.gram import data_parallel_gram, feature_parallel_gram

    mesh = make_host_mesh()
    m = int(mesh.shape["data"])

    def timeit(fn):
        jax.block_until_ready(fn())
        t0 = time.perf_counter()
        for _ in range(repeats):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / repeats

    rng = np.random.default_rng(0)

    def _case(b, n, p):
        xs = jnp.asarray(rng.standard_normal((b, n, p)), jnp.float32)
        w = jnp.asarray((rng.random((b, n)) > 0.2), jnp.float32)
        y = jnp.asarray(rng.standard_normal((b, n)), jnp.float32)
        return xs, w, y

    # ---- tall-N: task-parallel whole-N vs streaming blocked vs in-mesh
    b, n, p = (2, 1 << 14, 8) if fast else (4, 1 << 16, 8)
    xs, w, y = _case(b, n, p)
    t_task = timeit(lambda: ops.batched_gram(xs, w, y, reg=0.5))
    xc, wc, yc = ops.chunk_tall_n(xs, w, y, max(n // 8, 256))
    t_block = timeit(lambda: ops.batched_gram_blocked(xc, wc, yc, reg=0.5))
    t_data = timeit(lambda: data_parallel_gram(mesh, xs, w, y, reg=0.5))
    tall = {"b": b, "n": n, "p": p,
            "task_tasks_per_sec": b / t_task,
            "blocked_stream_tasks_per_sec": b / t_block,
            "data_parallel_tasks_per_sec": b / t_data}

    # ---- wide-P: whole Gram vs the feature-parallel column split
    bw, nw, pw = (1, 512, 1024) if fast else (2, 1024, 4096)
    xs, w, y = _case(bw, nw, pw)
    t_task_w = timeit(lambda: ops.batched_gram(xs, w, y, reg=0.5))
    t_feat = timeit(lambda: feature_parallel_gram(mesh, xs, w, y, reg=0.5))
    wide = {"b": bw, "n": nw, "p": pw,
            "task_tasks_per_sec": bw / t_task_w,
            "feature_parallel_tasks_per_sec": bw / t_feat}

    # ---- decision mix + the never-strictly-worse invariant ------------
    shapes = [("ridge", (("reg", 1.0),)), ("ols", ()),
              ("lasso", (("reg", 0.01), ("n_iter", 200))),
              ("logistic", (("reg", 1.0), ("n_iter", 100))),
              ("mlp", (("hidden", (8,)), ("n_steps", 100)))]
    mix = {"task": 0, "data": 0, "feature": 0}
    never_worse = True
    for learner, ptuple in shapes:
        for n_pad in (256, 4096, 1 << 17):
            for b_ in (1, 16, 64):
                for ndev in sorted({m, 8}):
                    d = plan_bucket_axis(BucketKey((learner, ptuple),
                                                   n_pad, 32),
                                         n_tasks=b_, n_devices=ndev)
                    if ndev == 8:
                        mix[d.axis] += 1
                    for ax, sh, est, ok in d.candidate_costs:
                        if ok and est < d.est_s \
                                and (ax, sh) != (d.axis, d.shards):
                            never_worse = False

    # ---- parallel-headroom probe: does this host win by sharding? ----
    if m == 1:
        headroom = 1.0
    else:
        from jax.sharding import PartitionSpec as P
        k = 128 if fast else 256
        a = jnp.asarray(rng.standard_normal((m, k, k)), jnp.float32)
        seq = jax.jit(lambda a: jnp.einsum("mij,mjk->mik", a, a))
        par = jax.jit(jax.shard_map(
            lambda a: jnp.einsum("mij,mjk->mik", a, a), mesh=mesh,
            in_specs=(P("data"),), out_specs=P("data"), check_vma=False))
        headroom = timeit(lambda: seq(a)) / max(timeit(lambda: par(a)),
                                                1e-12)

    # ---- sharded-fused vs unsharded fused warm launch (real path) ----
    n_obs, dim_x = (512, 16) if fast else (2048, 32)
    cases = []
    for i in range(2):
        data = DMLData.from_dict(make_plr_data(
            n_obs=n_obs, dim_x=dim_x, theta=0.5, seed=50 + i))
        plan = DMLPlan.for_model("plr", learner="ridge",
                                 learner_params={"reg": 1.0}, n_folds=3,
                                 n_rep=2, seed=70 + i)
        cases.append((plan, data))
    reqs = [compile_request(p, d) for p, d in cases]
    bplan = plan_buckets(reqs)
    (bkey,) = bplan.buckets
    entries = [(ri, int(i)) for ri, req in enumerate(reqs)
               for i in req.ledger.pending()]
    cache = ProgramCache()
    t_unsharded = timeit(
        lambda: run_bucket(bplan, cache, bkey, entries, fuse=True))
    sharded = make_sharded_compiler(mesh)
    t_sharded = timeit(
        lambda: run_bucket(bplan, sharded, bkey, entries, fuse=True,
                           b_align=m))
    assert sharded.stats.fused_launches >= 1

    # the headroom-calibrated speedup floor (ISSUE 9): on a host with
    # real parallel headroom the gate demands parity-or-better (1.0);
    # on a saturated 1-vCPU runner it decays toward the catastrophic-
    # overhead floor (0.35 — below that the sharded path is retracing).
    # A 1-device mesh can never win by sharding (only the wrapper tax
    # shows), so it keeps just the catastrophic floor.
    speedup_floor = 0.35 if m == 1 \
        else min(max(0.6 * headroom, 0.35), 1.0)

    # ---- end-to-end tall-N drain: executed data axis vs forced task --
    # A bucket made tall relative to an overridden device-page ceiling
    # so the chunk-paged data layout engages at bench size: the planner
    # arm drains through ShardedBackend (decision executed in-mesh);
    # the task arm is the same backend with its axis mesh withheld —
    # exactly HEAD's behavior of pricing-then-ignoring the plan.
    from repro.launch import roofline
    from repro.serverless import ShardedBackend

    e2e_n = 2048 if fast else 8192
    e2e_page = 256 if fast else 1024
    e2e_data = DMLData.from_dict(make_plr_data(
        n_obs=e2e_n, dim_x=8, theta=0.5, seed=90))
    e2e_plan = DMLPlan.for_model("plr", learner="ridge",
                                 learner_params={"reg": 1.0}, n_folds=3,
                                 n_rep=2, seed=91)
    saved_page = roofline.DEVICE_PAGE_ROWS
    roofline.DEVICE_PAGE_ROWS = e2e_page
    try:
        arms = {}
        plans_seen = []
        for arm in ("task", "data"):
            backend = ShardedBackend()
            if arm == "task":
                backend._axis_mesh = lambda: None
            n_inv = None

            def drain():
                nonlocal n_inv
                req = compile_request(e2e_plan, e2e_data)
                n_inv = len(req.ledger.pending())
                info = backend.run_requests([req])
                plans_seen[:] = info.axis_plans
                return []              # timeit blocks on the drain

            arms[arm] = n_inv_s = timeit(drain)
            arms[arm] = {"s": n_inv_s, "tasks_per_sec": n_inv / n_inv_s}
            if arm == "data":
                executed_mix = {}
                for d in plans_seen:
                    k = f"{d.axis}->{d.executed}"
                    executed_mix[k] = executed_mix.get(k, 0) + 1
    finally:
        roofline.DEVICE_PAGE_ROWS = saved_page
    e2e = {
        "n_obs": e2e_n,
        "page_rows_override": e2e_page,
        "task_axis_tasks_per_sec": arms["task"]["tasks_per_sec"],
        "executed_data_tasks_per_sec": arms["data"]["tasks_per_sec"],
        "speedup_data_vs_task": (arms["data"]["tasks_per_sec"]
                                 / max(arms["task"]["tasks_per_sec"],
                                       1e-12)),
        # planner axis -> executed axis counts from the drained
        # decisions (BackendRunInfo.axis_plans): the drain must have
        # *run* the chunk-paged data layout, not fallen back
        "decision_vs_executed": executed_mix,
        "planned_executed": all(d.executed == d.axis
                                for d in plans_seen),
    }

    return {
        "mesh_devices": m,
        "host_cores": os.cpu_count() or 1,
        "parallel_headroom": headroom,
        "tall_n": tall,
        "wide_p": wide,
        "decision_mix_8dev": mix,
        "planner_never_worse": never_worse,
        "e2e_tall_drain": e2e,
        "sharded_fused": {
            "n_entries": len(entries),
            "n_obs": n_obs,
            "warm_unsharded_s": t_unsharded,
            "warm_sharded_s": t_sharded,
            "warm_speedup_sharded_vs_unsharded": t_unsharded / t_sharded,
            "speedup_floor": speedup_floor,
            "speedup_gate_enforced": True,
        },
    }
