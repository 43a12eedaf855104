"""topology layer (serverless/topology.py, sharding/policy.py):
milliseconds of the router's ``topology.route`` and ``topology.steal``
spans per completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None or not prog.count("topology.route"):
        return None
    return progspans.per_estimate_ms(
        w, prog.total_ns("topology.route") + prog.total_ns("topology.steal"))
