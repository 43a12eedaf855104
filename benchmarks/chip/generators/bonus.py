"""Pennsylvania reemployment bonus schema: the paper's case study data.

Kept with the benchmark, so that a change to the program's data module
does not move the yardstick.  The schema is DoubleML's preprocessing of
the bonus experiment (Chernozhukov et al. 2018; Kurz 2021 section 5):
N=5099 rows, outcome log unemployment duration, binary bonus treatment,
and the 15 binary controls of ``fetch_bonus``'s ``x_cols``.
The published CSV is not available offline, so the rows are drawn from
a seed with the schema's marginals and a planted effect.
"""
from __future__ import annotations

import numpy as np

N_BONUS = 5099
X_COLS = [
    "female", "black", "othrace", "dep1", "dep2",
    "q2", "q3", "q4", "q5", "q6",
    "agelt35", "agegt54", "durable", "lusd", "husd",
]
TRUE_EFFECT = -0.08


def make(cfg: dict, rng: np.random.Generator) -> dict:
    """One bonus-schema dataset of ``cfg["n_obs"]`` rows."""
    n = int(cfg["n_obs"])
    if int(cfg["dim_x"]) != len(X_COLS):
        raise ValueError(f"the bonus schema has {len(X_COLS)} controls, "
                         f"the configuration asks for {cfg['dim_x']}")
    cols = {}
    probs = {
        "female": 0.39, "black": 0.11, "othrace": 0.01, "agelt35": 0.43,
        "agegt54": 0.11, "durable": 0.17, "lusd": 0.40, "husd": 0.27,
    }
    for c, p in probs.items():
        cols[c] = (rng.random(n) < p).astype(np.float32)
    # dependants 0, 1, or 2 and more: dep1 and dep2 never both set
    dep = rng.choice(3, size=n, p=[0.55, 0.20, 0.25])
    for i in (1, 2):
        cols[f"dep{i}"] = (dep == i).astype(np.float32)
    q = rng.integers(1, 7, n)
    for i in range(2, 7):
        cols[f"q{i}"] = (q == i).astype(np.float32)
    x = np.stack([cols[c] for c in X_COLS], axis=1)
    d = (rng.random(n) < 0.34).astype(np.float32)
    beta = rng.normal(0.0, 0.15, x.shape[1])
    y = 2.1 + x @ beta + TRUE_EFFECT * d + rng.gumbel(0.0, 0.55, n)
    return {"x": x.astype(np.float32), "y": y.astype(np.float32), "d": d}
