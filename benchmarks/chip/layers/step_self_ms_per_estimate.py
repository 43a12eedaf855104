"""scheduler layer (serverless/backends.py, topology.py): self time of
the service's ``backend.step`` spans per completed estimate, in
milliseconds: the ledger scans, wave latches and hedge checks of a step
outside its fill, dispatch and harvest."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(
        w, prog.self_ns().get("backend.step", 0.0))
