"""program layer against the device (compile/program.py): the share of
the traced window in which the chip is idle while the service is inside
a ``program.stage`` or ``program.launch`` span, on the trace's clock:
the chip waiting for its next launch to be built.  The mean over the
chips the cell uses."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.idle_under(prog, w.trace,
                                ("program.stage", "program.launch"))
