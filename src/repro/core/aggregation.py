"""Aggregation over repeated sample splits (paper §3, final step).

theta_tilde = Median_m(theta_m); the variance aggregation follows
Chernozhukov et al. (2018) remark 3.1 / the DoubleML package:
sigma^2 = Median_m( sigma_m^2 + (theta_m - theta_tilde)^2 ), which accounts
for the across-split variability.
"""
from __future__ import annotations

from typing import Tuple

from repro.core.scores import _xp
from repro.scipy_free_stats import norm_ppf


def aggregate_thetas(thetas, ses, method: str = "median") -> Tuple[float, float]:
    xp = _xp(thetas, ses)
    thetas = xp.asarray(thetas)
    ses = xp.asarray(ses)
    if method == "median":
        theta = xp.median(thetas)
        var = xp.median(ses**2 + (thetas - theta) ** 2)
    elif method == "mean":
        theta = xp.mean(thetas)
        var = xp.mean(ses**2 + (thetas - theta) ** 2)
    else:
        raise ValueError(method)
    return float(theta), float(xp.sqrt(var))


def confint(theta: float, se: float, level: float = 0.95):
    q = norm_ppf(0.5 + level / 2)
    return theta - q * se, theta + q * se
