"""The one traffic generator: every mix is a JSON file of parameters.

Keys of a mix file:

``loop``         ``"closed"``: ``outstanding`` requests are kept in the
                 service, a new one submitted as each completes;
                 ``"open"``: requests are due on a schedule whether or
                 not earlier ones have finished, ``rate_per_s`` of them.
``data``         ``"shared"``: every request estimates on the one dataset
                 drawn from the run's seed; ``"fresh"``: request ``i``
                 carries a dataset drawn from (seed, i), never reused.
``warmup_requests`` (closed) completions before the window opens;
``warmup_bursts`` (open) sizes of bursts submitted at once and drained
                 before the warm-up arrivals;
``warmup_s``     (open) seconds of arrivals at the same rate before it.
``grace_s``      (open) how long past the window's close requests due in
                 it may take before they count as failed.

Every seed gets the same work: a closed loop's requests all have one
size, and an open loop's inter-arrival gaps are one fixed set of
exponential quantiles that the seed only puts in another order.  The seed decides the fold draws, the data and that order.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import List

import numpy as np

# a plan's resampling seed keys repetition m's folds at seed + 7919 * m
# and the learner key at seed + l; keep all of them in 31 bits
PLAN_SEED_MAX = 2 ** 30


def stream(seed: int, *salt) -> np.random.Generator:
    """An independent Philox stream for (seed, salt...): the seed fills
    the key's low 64 bits, a checksum of the salt the high ones."""
    tag = zlib.crc32(repr(salt).encode())
    key = (int(seed) % 2 ** 64) | (tag << 64)
    return np.random.Generator(np.random.Philox(key=key))


@dataclass
class Request:
    index: int
    plan_seed: int
    scaling: str


def request(seed: int, index: int, config: dict) -> Request:
    scalings = config.get("scalings", ["n_rep"])
    plan_seed = int(stream(seed, "plan", index).integers(0, PLAN_SEED_MAX))
    return Request(index, plan_seed, scalings[index % len(scalings)])


def data_stream(seed: int, traffic: dict, index: int) -> np.random.Generator:
    """The generator stream of request ``index``'s dataset."""
    if traffic["data"] == "shared":
        return np.random.Generator(np.random.Philox(key=int(seed) % 2 ** 64))
    if traffic["data"] == "fresh":
        return stream(seed, "data", index)
    raise ValueError(f"unknown data rule {traffic['data']!r}")


def arrivals(seed: int, traffic: dict, duration: float,
             phase: str) -> List[float]:
    """Due times in [0, duration) of an open loop's phase.

    n = round(rate * duration) gaps at the exponential distribution's n
    quantiles, scaled to span the phase exactly, in an order drawn from
    (seed, phase): every seed offers the same gaps, and runs of short
    gaps (bursts) fall where the order puts them."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * duration)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= duration / gaps.sum()
    order = stream(seed, "arrivals", phase).permutation(gaps)
    return list(np.concatenate([[0.0], np.cumsum(order)[:-1]]))
