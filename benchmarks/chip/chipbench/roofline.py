"""Peaks by device kind, and the least work of a request's Gram fits.

The least work is the algorithm's, at real shapes, so it reads the same
whatever implements it.  For one repetition of a linear learner over N
rows and P controls (P + 1 columns with the intercept), the K fold Grams
share X across folds and nuisances: the Gram of all rows costs
2 N (P+1)^2 FLOPs, and each of the L nuisances' moment X'y another
2 N (P+1).  The bytes are X read once per request (4 N P) and each
target once (4 N per nuisance).  Nothing is counted at padded shapes or
per task lane.

The peak is the published bfloat16 one, which bounds the float32
``HIGHEST`` work the kernels do from above, so a share of it cannot
pass 100% unless the work is over-counted or the kernel time misses
part of the work.
"""
from __future__ import annotations

from typing import Dict

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak row of a device kind; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peak for device kind {device_kind!r}"
                       f" (have {sorted(PEAKS)})")
    return PEAKS[device_kind]


def gram_rep_flops(n: int, p: int, n_nuisance: int) -> float:
    """Least FLOPs of one repetition's Gram work (all folds, all
    nuisances)."""
    cols = p + 1
    return 2.0 * n * cols * cols + n_nuisance * 2.0 * n * cols


def gram_request_bytes(n: int, p: int, n_nuisance: int) -> float:
    """Least bytes of one request: X once and each target once, f32."""
    return 4.0 * n * p + 4.0 * n * n_nuisance


def least_gram_s(fits: int, cfg: dict, device_kind: str) -> float:
    """The least time the chip could take for the Gram work of ``fits``
    nuisance fits of configuration ``cfg``: the larger of its FLOPs over
    the peak rate and its bytes over the peak bandwidth."""
    n, p = int(cfg["n_obs"]), int(cfg["dim_x"])
    k, m, l = int(cfg["n_folds"]), int(cfg["n_rep"]), int(cfg["n_nuisance"])
    reps = fits / (k * l)
    requests = fits / (m * k * l)
    peak = peaks(device_kind)
    return max(reps * gram_rep_flops(n, p, l) / peak["flops_per_s"],
               requests * gram_request_bytes(n, p, l) / peak["bytes_per_s"])
