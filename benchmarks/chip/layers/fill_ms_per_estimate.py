"""scheduler layer (compile/buckets.py's pending view, read by
serverless/backends.py and topology.py): milliseconds of the service's
``planner.fill`` spans (the wave fill, or the pending view by bucket)
per completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(w, prog.total_ns("planner.fill"))
