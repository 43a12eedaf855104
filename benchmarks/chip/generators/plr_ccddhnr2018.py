"""The PLR design of Chernozhukov et al. (2018) section 5, as DoubleML's
``make_plr_CCDDHNR2018`` draws it: Toeplitz(0.7) Gaussian controls,
nonlinear confounding through x0 and x2, theta0 = 0.5.

A copy of ``make_plr_data`` in ``src/repro/data/dgp.py`` kept with the
benchmark, so that a change to the program's data module does not move
the yardstick.
"""
from __future__ import annotations

import numpy as np


def make(cfg: dict, rng: np.random.Generator) -> dict:
    """One dataset of ``cfg["n_obs"]`` rows and ``cfg["dim_x"]`` controls."""
    n, p = int(cfg["n_obs"]), int(cfg["dim_x"])
    theta = float(cfg.get("theta0", 0.5))
    rho = float(cfg.get("toeplitz_rho", 0.7))
    idx = np.arange(p)
    chol = np.linalg.cholesky(rho ** np.abs(idx[:, None] - idx[None, :]))
    x = rng.standard_normal((n, p)) @ chol.T
    m0 = x[:, 0] + 0.25 * np.exp(x[:, 2]) / (1 + np.exp(x[:, 2]))
    g0 = np.exp(x[:, 0]) / (1 + np.exp(x[:, 0])) + 0.25 * x[:, 2]
    d = m0 + rng.standard_normal(n)
    y = theta * d + g0 + rng.standard_normal(n)
    return {"x": x.astype(np.float32), "y": y.astype(np.float32),
            "d": d.astype(np.float32)}
