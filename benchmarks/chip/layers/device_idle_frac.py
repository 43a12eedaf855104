"""device layer: 1 minus the union of device-operation intervals over
the traced window, the mean over the chips the cell uses."""

from chipbench import trace


def read(w):
    if w.trace is None:
        return None
    return trace.idle_frac(w.trace)
