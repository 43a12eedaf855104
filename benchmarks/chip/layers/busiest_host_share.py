"""topology layer (serverless/topology.py): the largest share of the
window's dispatched invocations that one host's ``topology.wave`` spans
took (arg ``invocations``, by arg ``host``).  1/hosts is an even spread;
1.0 is one host doing all the work."""
from collections import defaultdict

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    per_host = defaultdict(float)
    for s in prog.named("topology.wave"):
        per_host[s[6]["host"]] += s[6]["invocations"]
    total = sum(per_host.values())
    return max(per_host.values()) / total if total else None
