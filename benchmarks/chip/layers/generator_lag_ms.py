"""client layer (the benchmark's open-loop generator): the 95th
percentile of submit time minus due time, in milliseconds, over the
requests due in the window."""

import numpy as np


def read(w):
    if not w.lags_s:
        return None
    return float(np.percentile(np.asarray(w.lags_s), 95)) * 1e3
