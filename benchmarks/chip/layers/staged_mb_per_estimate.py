"""program layer (compile/program.py): megabytes (10^6 bytes) of launch
operands staged on the host (the ``staged_bytes`` of the service's
``program.stage`` spans: y, w, valid, key data, page index) per
completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None or not w.completed:
        return None
    return prog.arg_sum("program.stage", "staged_bytes") \
        / len(w.completed) / 1e6
