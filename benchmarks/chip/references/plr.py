"""Plain reference for partially linear regression, partialling out.

Independent of ``repro``: numpy in float64.  The nuisances E[y|x] and
E[d|x] are ridge fits with an unpenalized intercept, cross-fitted over
K folds and repeated M times; theta and SE are median-aggregated over
the repetitions (Chernozhukov et al. 2018, DoubleML's rule).  The float64
fit is the one in ``chip_smoke.py::plr_reference``, copied here.

The fold draw is the resampling rule the service documents for a plan's
seed (one Philox stream per repetition, keyed ``seed + 7919 * m``, a
permutation split into K near-equal folds), drawn here again rather
than taken from the program.

``control`` is the same estimator with every matrix product computed in
bfloat16 three-pass arithmetic (``Precision.HIGH`` on a TPU, emulated
here with explicit bf16 splits so it reads the same on any backend): the
nearest precision below the float32 ``HIGHEST`` that the service states.
"""
from __future__ import annotations

from statistics import NormalDist

import numpy as np


def fold_masks(n_obs: int, n_folds: int, n_rep: int, seed: int) -> np.ndarray:
    """(M, K, N) bool; mask[m, k, i] is True when row i is in fold k."""
    masks = np.zeros((n_rep, n_folds, n_obs), dtype=bool)
    for m in range(n_rep):
        rng = np.random.Generator(np.random.Philox(key=seed + 7919 * m))
        perm = rng.permutation(n_obs)
        for k, chunk in enumerate(np.array_split(perm, n_folds)):
            masks[m, k, chunk] = True
    return masks


def _crossfit(x, target, masks, reg, fit):
    n = x.shape[0]
    xa = np.concatenate([x.astype(np.float64), np.ones((n, 1))], axis=1)
    pen = np.full(xa.shape[1], float(reg))
    pen[-1] = 0.0
    t = target.astype(np.float64)
    out = np.zeros(masks.shape[::2])
    for m in range(masks.shape[0]):
        for k in range(masks.shape[1]):
            test = masks[m, k]
            out[m, test] = fit(xa[~test], t[~test], pen, xa[test])
    return out


def _ridge_f64(tr, t, pen, te):
    beta = np.linalg.solve(tr.T @ tr + np.diag(pen), tr.T @ t)
    return te @ beta


def _aggregate(x, y, d, l_hat, m_hat, level):
    u = y.astype(np.float64) - l_hat
    v = d.astype(np.float64) - m_hat
    psi_a, psi_b = -v * v, v * u
    thetas = -psi_b.sum(1) / psi_a.sum(1)
    psi = psi_a * thetas[:, None] + psi_b
    ses = np.sqrt(np.mean(psi * psi, 1) / np.mean(psi_a, 1) ** 2
                  / x.shape[0])
    theta = float(np.median(thetas))
    se = float(np.sqrt(np.median(ses ** 2 + (thetas - theta) ** 2)))
    q = NormalDist().inv_cdf(0.5 + level / 2)
    return {"theta": theta, "se": se, "ci": (theta - q * se, theta + q * se),
            "thetas": thetas, "ses": ses}


def reference(x, y, d, masks, reg, level=0.95) -> dict:
    """theta, se, ci and the per-repetition thetas and ses, float64."""
    return _aggregate(x, y, d, _crossfit(x, y, masks, reg, _ridge_f64),
                      _crossfit(x, d, masks, reg, _ridge_f64), level)


# ---------------------------------------------------------------------------
# the control: the reference one precision step down
# ---------------------------------------------------------------------------
def _bf16_split(a):
    import jax.numpy as jnp
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (a - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _matmul_bf16x3(a, b):
    """a @ b in bf16_3x: hi*hi + hi*lo + lo*hi, products exact in f32."""
    import jax
    import jax.numpy as jnp
    ah, al = _bf16_split(jnp.asarray(a, jnp.float32))
    bh, bl = _bf16_split(jnp.asarray(b, jnp.float32))
    mm = lambda p, q: jnp.matmul(p, q, precision=jax.lax.Precision.HIGHEST)
    return mm(ah, bl) + mm(al, bh) + mm(ah, bh)


def _ridge_control(tr, t, pen, te):
    import jax.numpy as jnp
    tr = np.asarray(tr, np.float32)
    gram = _matmul_bf16x3(tr.T, tr) + jnp.diag(jnp.asarray(pen, jnp.float32))
    mom = _matmul_bf16x3(tr.T, np.asarray(t, np.float32)[:, None])
    beta = jnp.linalg.solve(gram, mom)
    return np.asarray(_matmul_bf16x3(np.asarray(te, np.float32), beta),
                      np.float64)[:, 0]


def control(x, y, d, masks, reg, level=0.95) -> dict:
    """The reference with its products in bf16_3x and its solves in
    float32: what the service would return one precision step down."""
    return _aggregate(x, y, d, _crossfit(x, y, masks, reg, _ridge_control),
                      _crossfit(x, d, masks, reg, _ridge_control), level)
