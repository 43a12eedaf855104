"""The comparison that decides ``correct``.

Each number compares the service's answers with the plain reference's
for the same requests: theta, its standard error and the per-repetition
thetas and standard errors; a theta gap is taken in units of the
reference's SE, an SE gap relative to the reference's SE.  ``_gap``
numbers are the widest gap over the compared requests, ``_rms`` numbers
the root mean square over all their repetitions.  A configuration's
``limits`` name the numbers it is held to and the limit of each; a run
is correct when
every such number is at or below its limit, at least one request was
compared, and no request failed.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

Pair = Tuple[Dict, Dict]                 # (service answer, reference)


def _theta_gap_se(pairs: List[Pair]) -> float:
    return max(abs(a["theta"] - r["theta"]) / r["se"] for a, r in pairs)


def _se_gap_rel(pairs: List[Pair]) -> float:
    return max(abs(a["se"] - r["se"]) / r["se"] for a, r in pairs)


def _rep_gaps(pairs: List[Pair]) -> np.ndarray:
    return np.concatenate([
        np.abs(np.asarray(a["thetas"], np.float64) - r["thetas"]) / r["se"]
        for a, r in pairs])


def _rep_theta_gap_se(pairs: List[Pair]) -> float:
    return float(np.max(_rep_gaps(pairs)))


def _rep_theta_rms_se(pairs: List[Pair]) -> float:
    return float(np.sqrt(np.mean(_rep_gaps(pairs) ** 2)))


def _rep_se_rms_rel(pairs: List[Pair]) -> float:
    gaps = np.concatenate([
        (np.asarray(a["ses"], np.float64) - r["ses"]) / r["ses"]
        for a, r in pairs])
    return float(np.sqrt(np.mean(gaps ** 2)))


NUMBERS: Dict[str, Callable[[List[Pair]], float]] = {
    "theta_gap_se": _theta_gap_se,
    "se_gap_rel": _se_gap_rel,
    "rep_theta_gap_se": _rep_theta_gap_se,
    "rep_theta_rms_se": _rep_theta_rms_se,
    "rep_se_rms_rel": _rep_se_rms_rel,
}


def numbers(pairs: List[Pair], names) -> Dict[str, float]:
    return {n: float(NUMBERS[n](pairs)) for n in names}


def judge(pairs: List[Pair], limits: Dict[str, float],
          failed: int) -> Tuple[bool, Dict[str, List[float]]]:
    """(correct, {name: [number, limit]}), the numbers in ``limits``'
    order followed by the failed and compared request counts."""
    checks: Dict[str, List[float]] = {}
    ok = bool(pairs) and failed == 0
    if pairs:
        for name, value in numbers(pairs, limits).items():
            checks[name] = [value, float(limits[name])]
            ok = ok and np.isfinite(value) and value <= limits[name]
    checks["failed_requests"] = [failed, 0]
    checks["compared_requests_min1"] = [len(pairs), 1]
    return ok, checks
