"""planner layer (compile/buckets.py): the share of the window's padded
program cells that carry no real data (``CompileStats.padding``)."""


def read(w):
    padded = w.counters["padded_cells"]
    if padded <= 0:
        return None
    return 1.0 - w.counters["true_cells"] / padded
