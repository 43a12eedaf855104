#!/usr/bin/env python3
"""Smoke test of the DML estimation path on a TPU.

Run it from the repository root on a machine with a TPU:

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips of one host

One chip: the paper's case study (PLR on the Pennsylvania bonus schema,
N=5099, 17 controls, K=5, M=100, L=2, ridge reg 1.0) at both scaling
levels, submitted into one ``DMLSession`` on the default ``wave``
backend, cold then warm.  The script checks that the Gram program holds
the Mosaic kernel, that theta agrees with a float64 numpy reference
within 1% of its standard error, that both scaling levels give bitwise
the same theta, and that theta and SE are finite.

Four chips: a tall PLR ridge request (N=131072, 12 controls, K=5, M=2)
whose padded N exceeds one device page, on the ``sharded`` backend over
the four-chip mesh and on the ``topology`` backend with one chip per
host, each compared with the same request on one chip.

The process is the only one that touches the chip; it starts no child.
The last line of standard output is one JSON object naming the device.
On any failure, a missing TPU included, the script exits non-zero and
prints no such line.  Timings it prints are smoke timings of one run,
not benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the gap to the one-chip theta the in-mesh executors are held to
# (tests/test_axis_exec.py)
AXIS_TOL = 5e-4


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def device_phase(count: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']}")
    check(dev["count"] >= count, f"need {count} chips, found {dev['count']}")
    return dev


# ---------------------------------------------------------------------------
# float64 reference: plain numpy PLR partialling-out, independent of repro
# ---------------------------------------------------------------------------
def ridge_crossfit_f64(x, target, masks, reg):
    """Cross-fitted ridge predictions, (M, N) float64.  Intercept
    unpenalized; each fold's model is fit on the other folds' rows."""
    n = x.shape[0]
    xa = np.concatenate([x.astype(np.float64), np.ones((n, 1))], axis=1)
    pen = np.full(xa.shape[1], float(reg))
    pen[-1] = 0.0
    t = target.astype(np.float64)
    out = np.zeros(masks.shape[::2])
    for m in range(masks.shape[0]):
        for k in range(masks.shape[1]):
            test = masks[m, k]
            tr = xa[~test]
            beta = np.linalg.solve(tr.T @ tr + np.diag(pen), tr.T @ t[~test])
            out[m, test] = xa[test] @ beta
    return out


def plr_reference(x, y, d, masks, reg, level=0.95):
    """theta, SE and CI of PLR partialling-out, median-aggregated over
    the M repetitions (Chernozhukov et al. 2018, DoubleML's rule)."""
    from statistics import NormalDist
    u = y.astype(np.float64) - ridge_crossfit_f64(x, y, masks, reg)
    v = d.astype(np.float64) - ridge_crossfit_f64(x, d, masks, reg)
    psi_a, psi_b = -v * v, v * u
    thetas = -psi_b.sum(1) / psi_a.sum(1)
    psi = psi_a * thetas[:, None] + psi_b
    ses = np.sqrt(np.mean(psi * psi, 1) / np.mean(psi_a, 1) ** 2
                  / x.shape[0])
    theta = float(np.median(thetas))
    se = float(np.sqrt(np.median(ses ** 2 + (thetas - theta) ** 2)))
    q = NormalDist().inv_cdf(0.5 + level / 2)
    return theta, se, (theta - q * se, theta + q * se)


# ---------------------------------------------------------------------------
# one chip: W1 through DMLSession
# ---------------------------------------------------------------------------
def _drain(sess, plans, data):
    rids = [sess.submit(p, data) for p in plans]
    t0 = time.perf_counter()
    sess.run()
    wall = time.perf_counter() - t0
    return [sess.result(r) for r in rids], wall


def _gram_program_has_kernel(compiler) -> bool:
    """Lower one cached ridge bucket program at its exact launch avals
    and look for the Mosaic custom call."""
    from repro.compile.persist import program_avals
    for pkey, prog in compiler._programs.items():
        key, b_pad, d_pad = pkey[:3]
        if key.learner[0] != "ridge" or not hasattr(prog, "lower"):
            continue
        g = pkey[3] if len(pkey) == 4 else None
        text = prog.lower(*program_avals(key, b_pad, d_pad, g)).as_text()
        log(f"[kernel] ridge bucket n_pad={key.n_pad} p_pad={key.p_pad} "
            f"b_pad={b_pad} g={g}: tpu_custom_call="
            f"{'tpu_custom_call' in text}")
        return "tpu_custom_call" in text
    raise AssertionError("no ridge bucket program was compiled")


def one_chip_phase() -> None:
    from repro.configs.dml_plr_bonus import CONFIG
    from repro.core import DMLData, DMLPlan, DMLSession
    from repro.core.session import compile_request
    from repro.data import make_bonus_data

    data = DMLData.from_dict(make_bonus_data())
    params = dict(CONFIG.learner_params)
    plans = [DMLPlan.for_model(CONFIG.model, learner=CONFIG.learner,
                               learner_params=params,
                               n_folds=CONFIG.n_folds, n_rep=CONFIG.n_rep,
                               seed=CONFIG.seed, scaling=scaling)
             for scaling in ("n_rep", "n_folds*n_rep")]
    log(f"[w1] PLR bonus N={data.n_obs} P={data.dim_x} K={CONFIG.n_folds} "
        f"M={CONFIG.n_rep} L=2 learner={CONFIG.learner} {params} "
        f"scalings={[p.scaling for p in plans]}")

    sess = DMLSession(backend="wave")
    stats = sess.backend.compiler.stats
    cold, cold_s = _drain(sess, plans, data)
    compiles_cold, launches_cold = stats.misses, stats.launches
    warm, warm_s = _drain(sess, plans, data)
    log(f"[w1] smoke timing, not a benchmark: cold {cold_s:.3f} s "
        f"(compiles {compiles_cold}, launches {launches_cold}), warm "
        f"{warm_s:.3f} s (compiles {stats.misses - compiles_cold}, "
        f"launches {stats.launches - launches_cold})")
    for plan, r in zip(plans, cold):
        log(f"[w1] scaling={plan.scaling}: theta={r.theta!r} se={r.se!r} "
            f"ci=({r.ci[0]!r}, {r.ci[1]!r})")

    check(_gram_program_has_kernel(sess.backend.compiler),
          "the ridge Gram program holds no tpu_custom_call")

    results = cold + warm
    check(all(np.isfinite(r.theta) and np.isfinite(r.se) for r in results),
          "non-finite theta or SE")
    thetas = [r.theta for r in results]
    same = all(t == thetas[0] for t in thetas)
    log(f"[determinism] both scaling levels, cold and warm, bitwise "
        f"equal theta: {same}")
    check(same, f"thetas differ across scaling levels or runs: {thetas}")

    inline = DMLSession(backend="inline")
    r_in, _ = _drain(inline, plans[:1], data)
    log(f"[determinism] inline backend theta={r_in[0].theta!r}: bitwise "
        f"equal to wave: {r_in[0].theta == thetas[0]}")

    req = compile_request(plans[0], data)
    t_ref, se_ref, ci_ref = plr_reference(data.x, data.y, data.d,
                                          req.fold_masks, params["reg"])
    gap = abs(thetas[0] - t_ref)
    log(f"[reference] float64 numpy: theta={t_ref!r} se={se_ref!r} "
        f"ci=({ci_ref[0]!r}, {ci_ref[1]!r}); |theta - theta_ref|={gap!r} "
        f"= {gap / se_ref!r} SE (limit 0.01 SE)")
    check(gap <= 0.01 * se_ref, "theta is more than 1% of SE from the "
          "float64 reference")


# ---------------------------------------------------------------------------
# four chips: the tall request on the sharded and topology backends
# ---------------------------------------------------------------------------
def _devices_of(tree) -> set:
    import jax
    return {d for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "devices") for d in leaf.devices()}


def four_chip_phase() -> None:
    import jax

    from repro.core import DMLData, DMLPlan, DMLSession
    from repro.data import make_plr_data
    from repro.launch.roofline import DEVICE_PAGE_ROWS
    from repro.serverless import PoolConfig

    data = DMLData.from_dict(make_plr_data(n_obs=131072, dim_x=12, seed=0))
    plan = DMLPlan.for_model("plr", learner="ridge",
                             learner_params={"reg": 1.0}, n_folds=5,
                             n_rep=2, seed=42)
    log(f"[tall] PLR ridge N={data.n_obs} P={data.dim_x} K=5 M=2 L=2; "
        f"DEVICE_PAGE_ROWS={DEVICE_PAGE_ROWS}")

    ref, ref_s = _drain(DMLSession(backend="wave"), [plan], data)
    theta_1 = ref[0].theta
    log(f"[tall] one chip (wave): theta={theta_1!r} se={ref[0].se!r} "
        f"smoke timing {ref_s:.3f} s")
    check(np.isfinite(theta_1) and np.isfinite(ref[0].se),
          "non-finite one-chip theta or SE")

    # the topology places each request's invocations in a bucket as one
    # unit, so one request runs on one host: three companion requests
    # on other data of the same shape (the same bucket) give all four
    # hosts work; only the first is compared with the one-chip theta
    companions = [DMLData.from_dict(make_plr_data(
        n_obs=131072, dim_x=12, seed=i)) for i in (1, 2, 3)]
    all_devs = set(jax.devices())
    for name, pool, extra in (("sharded", None, []),
                              ("topology", PoolConfig(n_hosts=4),
                               companions)):
        sess = DMLSession(backend=name, pool=pool)
        rids = [sess.submit(plan, d) for d in [data] + extra]
        t0 = time.perf_counter()
        sess.run()
        wall = time.perf_counter() - t0
        res = [sess.result(r) for r in rids]
        info = sess.last_run_info
        mix = [(d.axis, d.shards, d.executed, d.n_pad)
               for d in info.axis_plans]
        gap = abs(res[0].theta - theta_1)
        log(f"[tall] {name}: theta={res[0].theta!r} se={res[0].se!r} "
            f"|theta - one chip|={gap!r} (limit {AXIS_TOL}); "
            f"{len(res)} request(s), smoke timing {wall:.3f} s")
        log(f"[tall] {name}: axis (planned, shards, executed, n_pad): {mix}")
        check(all(np.isfinite(r.theta) and np.isfinite(r.se) for r in res),
              f"{name}: non-finite theta or SE")
        check(all(d.n_pad > DEVICE_PAGE_ROWS for d in info.axis_plans),
              f"{name}: a bucket fits one page; the test is moot")
        check(all(d.executed == "data" for d in info.axis_plans),
              f"{name}: a bucket did not execute the data axis")
        if name == "sharded":
            mesh_devs = set(np.asarray(sess.backend.mesh.devices).flat)
            log(f"[tall] sharded mesh devices: "
                f"{sorted(d.id for d in mesh_devs)}")
            check(mesh_devs == all_devs, "the mesh does not span the chips")
        else:
            hosts = sess.backend.topology.hosts
            placed = sorted(h for _, _, h, _ in info.topology.placements)
            page_devs = [sorted(d.id for d in _devices_of(
                list(h.pool._pages.values()))) for h in hosts]
            log(f"[tall] topology: unit placements (host ids) {placed}; "
                f"lead device per host {[h.device.id for h in hosts]}; "
                f"devices holding each host's pages {page_devs}")
            used = {d for devs in page_devs for d in devs}
            check(used == {d.id for d in all_devs},
                  "topology pages do not span the four chips")
        check(gap <= AXIS_TOL, f"{name}: theta gap {gap} exceeds {AXIS_TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phase and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)
    try:
        dev = device_phase(4 if args.four_chips else 1)
        sys.path.insert(0, os.path.join(ROOT, "src"))
        if args.four_chips:
            four_chip_phase()
        else:
            one_chip_phase()
    except Exception:                      # report, then fail the run
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
