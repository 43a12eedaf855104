"""session layer (core/session.py): host milliseconds per completed
estimate.  The benchmark's spans around ``submit``/``poll``/``result``
on the host clock, less the time the service itself reports blocked on
the device (``DispatchStats.wait_s``), over the window's completions."""


def read(w):
    if not w.completed:
        return None
    host = sum(v for k, v in w.spans.items() if k.startswith("session."))
    return (host - w.counters["dispatch_wait_s"]) / len(w.completed) * 1e3
