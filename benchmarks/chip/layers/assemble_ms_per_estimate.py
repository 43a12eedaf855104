"""session layer (core/session.py::assemble_result): milliseconds of
the service's ``session.assemble`` spans (stitching, score, solve and
intervals of one finished request) per completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(w, prog.total_ns("session.assemble"))
