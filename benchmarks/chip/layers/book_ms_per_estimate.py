"""ledger layer (serverless/ledger.py, the booking in
serverless/backends.py): milliseconds of the service's ``ledger.book``
spans (a harvested bucket's booking continuation: ledgers, bills, wave
settlement, finalization, checkpoint) per completed estimate."""

from chipbench import progspans


def read(w):
    prog = progspans.load(w)
    if prog is None:
        return None
    return progspans.per_estimate_ms(w, prog.total_ns("ledger.book"))
