"""dispatch layer (serverless/dispatch.py, BucketDispatch.harvest in
compile/program.py): milliseconds per completed estimate of the
service's ``program.harvest`` spans less their ``program.wait``
children: the device-to-host copy and the per-lane scatter, not the
blocked wait (``DispatchStats.wait_s`` has that)."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(
        w, prog.total_ns("program.harvest") - prog.total_ns("program.wait"))
