"""Score-function unit tests: linearity, orthogonality, SE sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import aggregate_thetas
from repro.core.scores import (
    SPECS, evaluate_score, irm_score, plr_score, score_se,
    solve_theta,
)


def _plr_fixture(n=400, theta=0.7, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, 3)).astype(np.float32)
    g = np.tanh(x[:, 0])
    m = 0.5 * x[:, 1]
    d = m + rng.normal(size=n).astype(np.float32)
    y = theta * d + g + rng.normal(size=n).astype(np.float32)
    data = {"y": jnp.asarray(y), "d": jnp.asarray(d)}
    eta = {"ml_l": jnp.asarray(theta * m + g), "ml_m": jnp.asarray(m)}
    return data, eta, theta


def test_plr_score_linearity():
    data, eta, theta = _plr_fixture()
    pa, pb = plr_score(data, eta)
    # psi(theta) = theta*psi_a + psi_b must be zero at the solution
    th = solve_theta(pa, pb)
    psi = th * pa + pb
    assert abs(float(jnp.mean(psi))) < 1e-5


def test_plr_recovers_theta_with_true_nuisance():
    data, eta, theta = _plr_fixture()
    pa, pb = plr_score(data, eta)
    th = float(solve_theta(pa, pb))
    assert abs(th - theta) < 0.15


def test_plr_neyman_orthogonality():
    """d/dr E[psi(theta0, eta0 + r*h)] at r=0 must vanish."""
    data, eta, theta = _plr_fixture(n=20_000)
    rng = np.random.default_rng(1)
    h_l = jnp.asarray(rng.normal(size=data["y"].shape).astype(np.float32))
    h_m = jnp.asarray(rng.normal(size=data["y"].shape).astype(np.float32))

    def mean_psi(r):
        pert = {"ml_l": eta["ml_l"] + r * h_l, "ml_m": eta["ml_m"] + r * h_m}
        pa, pb = plr_score(data, pert)
        return jnp.mean(theta * pa + pb)

    d0 = float(jax.grad(mean_psi)(0.0))
    # scale-free comparison: the second derivative is O(E[h_l h_m])
    d2 = float(jax.grad(jax.grad(mean_psi))(0.0))
    assert abs(d0) < 1e-2 * max(abs(d2), 1.0)


def test_non_orthogonal_score_fails_the_same_check():
    """A naive (prediction-error) score violates orthogonality — the reason
    DML exists.  psi_naive = d*(y - d*theta - ghat)."""
    data, eta, theta = _plr_fixture(n=20_000)
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.normal(size=data["y"].shape).astype(np.float32))

    def mean_psi_naive(r):
        ghat = (eta["ml_l"] - theta * eta["ml_m"]) + r * h
        return jnp.mean(data["d"] * (data["y"] - data["d"] * theta - ghat))

    d0 = float(jax.grad(mean_psi_naive)(0.0))
    assert abs(d0) > 1e-2          # first-order sensitivity is O(E[d*h]) != 0


def test_score_se_positive_and_shrinks():
    data, eta, _ = _plr_fixture(n=400)
    pa, pb = plr_score(data, eta)
    th = solve_theta(pa, pb)
    se400 = float(score_se(pa, pb, th))
    data2, eta2, _ = _plr_fixture(n=6400)
    pa2, pb2 = plr_score(data2, eta2)
    se6400 = float(score_se(pa2, pb2, solve_theta(pa2, pb2)))
    assert se400 > 0 and se6400 > 0
    assert se6400 < se400


def test_irm_score_ate_identity():
    n = 50_000
    rng = np.random.default_rng(3)
    x = rng.normal(size=n).astype(np.float32)
    m = 1 / (1 + np.exp(-x))
    d = (rng.random(n) < m).astype(np.float32)
    g0 = np.tanh(x)
    theta = 0.3
    y = (g0 + theta * d + 0.1 * rng.normal(size=n)).astype(np.float32)
    data = {"y": jnp.asarray(y), "d": jnp.asarray(d)}
    eta = {"ml_g0": jnp.asarray(g0), "ml_g1": jnp.asarray(g0 + theta),
           "ml_m": jnp.asarray(m.astype(np.float32))}
    pa, pb = irm_score(data, eta)
    assert abs(float(solve_theta(pa, pb)) - theta) < 0.05


def test_all_specs_have_consistent_nuisance_counts():
    assert SPECS["plr"].n_nuisance == 2
    assert SPECS["pliv"].n_nuisance == 3
    assert SPECS["irm"].n_nuisance == 3
    assert SPECS["iivm"].n_nuisance == 5


def _host_fixture(model, m=3, n=257, theta=0.7, seed=1):
    """Host float32 arrays for every role and nuisance of ``model``:
    binary d/z where the model needs them, propensities in (0, 1), and
    predictions near the truth so theta stays away from 0."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n).astype(np.float32)
    p = (1 / (1 + np.exp(-x))).astype(np.float32)
    binary = model in ("irm", "iivm")
    z = (rng.random(n) < p).astype(np.float32) if model == "iivm" \
        else (x + rng.normal(size=n)).astype(np.float32)
    d = (rng.random(n) < p).astype(np.float32) if binary \
        else (0.5 * x + 0.5 * z + rng.normal(size=n)).astype(np.float32)
    y = (np.tanh(x) + theta * d + rng.normal(size=n)).astype(np.float32)
    data = {"y": y[None], "d": d[None], "z": z[None]}

    def near(v):
        return (v + 0.1 * rng.normal(size=(m, n))).astype(np.float32)
    truth = {"ml_l": theta * d + np.tanh(x), "ml_m": 0.5 * x,
             "ml_r": 0.5 * x, "ml_g0": np.tanh(x), "ml_g1": np.tanh(x) + theta}
    if model == "pliv":
        truth["ml_m"] = x
    if binary:
        truth["ml_m"] = p
        truth["ml_r0"], truth["ml_r1"] = 0.8 * p, 0.8 * p + 0.2
    names = [ns[0] for ns in SPECS[model].nuisances]
    preds = {k: near(truth[k]) for k in names}
    if binary:                                  # keep clipped propensities
        for k in ("ml_m", "ml_r0", "ml_r1"):
            if k in preds:
                preds[k] = np.clip(preds[k], 0.05, 0.95)
    return data, preds


def _close(host, dev, rel=2e-6):
    assert isinstance(host, np.ndarray) and host.dtype == np.float32
    dev = np.asarray(dev)
    scale = float(np.max(np.abs(dev)))
    np.testing.assert_allclose(host, dev, rtol=rel, atol=rel * scale)


@pytest.mark.parametrize("model,score", [
    ("plr", "partialling out"), ("plr", "IV-type"), ("pliv", "default"),
    ("irm", "ATE"), ("irm", "ATTE"), ("iivm", "default")])
def test_host_inputs_compute_in_numpy_like_the_jnp_path(model, score):
    data, preds = _host_fixture(model)
    pa, pb = evaluate_score(model, data, preds, score)
    jd = {k: jnp.asarray(v) for k, v in data.items()}
    jp = {k: jnp.asarray(v) for k, v in preds.items()}
    ja, jb = evaluate_score(model, jd, jp, score)
    assert isinstance(ja, jax.Array)
    _close(pa, ja)
    _close(pb, jb)
    th, jth = solve_theta(pa, pb), solve_theta(ja, jb)
    _close(th, jth)
    _close(score_se(pa, pb, th), score_se(ja, jb, jth))


@pytest.mark.parametrize("method", ["median", "mean"])
def test_host_aggregation_matches_the_jnp_path(method):
    rng = np.random.default_rng(4)
    thetas = (0.5 + 0.05 * rng.normal(size=8)).astype(np.float32)
    ses = (0.1 + 0.01 * rng.random(8)).astype(np.float32)
    host = aggregate_thetas(thetas, ses, method)
    dev = aggregate_thetas(jnp.asarray(thetas), jnp.asarray(ses), method)
    np.testing.assert_allclose(host, dev, rtol=2e-6)
