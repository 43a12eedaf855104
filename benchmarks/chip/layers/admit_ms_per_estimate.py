"""session and planner layers (core/session.py, compile/buckets.py):
milliseconds of the service's ``session.admit`` spans (compile_request
and the planner's admit of every queued request) per completed
estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(w, prog.total_ns("session.admit"))
