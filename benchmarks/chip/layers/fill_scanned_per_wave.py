"""scheduler layer (compile/buckets.py's pending view): request lists
and entries one ``planner.fill`` read, the mean over the window's fills
(arg ``scanned``).  None where the fills record no ``scanned``, as a
service that scans every ledger does not."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    fills = prog.named("planner.fill")
    if not fills or any("scanned" not in s[6] for s in fills):
        return None
    return prog.arg_sum("planner.fill", "scanned") / len(fills)
