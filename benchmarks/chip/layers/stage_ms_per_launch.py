"""program layer (compile/program.py): milliseconds per device launch
of the service's ``program.stage`` spans (page stack, y/w/valid, key
data and page index) plus its ``program.launch`` spans (the call that
hands them to the chip)."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    launches = prog.count("program.launch")
    if not launches:
        return None
    ns = prog.total_ns("program.stage") + prog.total_ns("program.launch")
    return ns / launches / 1e6
